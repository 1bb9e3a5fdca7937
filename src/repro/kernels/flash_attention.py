"""Pallas TPU kernel: blocked causal attention (online softmax).

Used by the LM stack for training/prefill when
``ModelConfig.use_pallas_attention`` is set.  Tiles: (BLOCK_Q x head_dim)
query tiles resident in VMEM stream over (BLOCK_K x head_dim) key/value
tiles; running max/denominator keep the softmax numerically exact.
Oracle: :func:`repro.kernels.ref.attention_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, block_q,
                 block_k, seq_k):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale         # (block_q, d)

    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc = jnp.zeros_like(q)

    num_k = seq_k // block_k

    def body(kj, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        s = q @ k.T                                      # (block_q, block_k)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            kpos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + p @ v
        return m_new, l_new, acc_new

    if causal:
        # only key blocks at or before this query block contribute
        upper = jnp.minimum(num_k, (qi + 1) * block_q // block_k
                            + (1 if block_q % block_k else 0))
        upper = jnp.maximum(upper, 1)
    else:
        upper = num_k
    m, l, acc = jax.lax.fori_loop(0, upper, body, (m, l, acc))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, block_q=DEFAULT_BLOCK_Q,
                           block_k=DEFAULT_BLOCK_K, interpret=True):
    """q: (B, H, S, D); k, v: (B, H, T, D).  Returns (B, H, S, D)."""
    b, h, s, d = q.shape
    t = k.shape[2]
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    scale = 1.0 / (d ** 0.5)
    grid = (b, h, s // block_q)

    kernel = functools.partial(_attn_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, seq_k=t)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, t, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, t, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v)
