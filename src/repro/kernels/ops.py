"""Jit'd public wrappers for the Pallas kernels with oracle dispatch.

``backend="ref"`` runs the pure-jnp oracle (XLA — also the default inside
the simulator so HLO cost analysis sees true FLOPs); ``backend="pallas"``
runs the Pallas kernel, compiled when the program is lowered for a TPU
and interpreted when it is lowered for the CPU.  The choice is made per
lowering platform (:func:`jax.lax.platform_dependent`), never from the
process's default backend, so a program compiled ahead of time for a
TPU always carries the compiled kernel.  Any other platform raises.
"""
from __future__ import annotations

import functools

import jax

from . import ref
from .flash_attention import flash_attention_pallas
from .router_phase import router_arbitrate_pallas


def _pallas(kernel, *args, **kw):
    """Call a Pallas ``kernel`` compiled on TPU, interpreted on CPU."""
    return jax.lax.platform_dependent(
        *args,
        cpu=functools.partial(kernel, interpret=True, **kw),
        tpu=functools.partial(kernel, interpret=False, **kw))


def arbitrate(age, valid, we, dc, dr, vp, backend: str = "ref"):
    """Phase-2 router arbitration. See :func:`repro.kernels.ref.arbitrate_ref`."""
    if backend == "ref":
        return ref.arbitrate_ref(age, valid, we, dc, dr, vp)
    return _pallas(router_arbitrate_pallas, age, valid, we, dc, dr, vp)


def attention(q, k, v, causal: bool = True, backend: str = "ref"):
    """Multi-head attention. See :func:`repro.kernels.ref.attention_ref`."""
    if backend == "ref":
        return ref.attention_ref(q, k, v, causal=causal)
    return _pallas(flash_attention_pallas, q, k, v, causal=causal)
