"""Pure-jnp oracles for the Pallas kernels.

``arbitrate_ref`` is the semantic definition of the paper's Phase-2 router
arbitration (age-priority sort + PMDR port selection + deflection) — the
vectorized simulator calls it directly, and the Pallas kernel in
:mod:`repro.kernels.router_phase` must match it bit-for-bit.

``attention_ref`` is the oracle for the blocked flash-attention kernel.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

I32 = jnp.int32


def arbitrate_ref(age: jnp.ndarray, valid: jnp.ndarray, we: jnp.ndarray,
                  dc: jnp.ndarray, dr: jnp.ndarray,
                  vp: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Age-priority greedy port assignment for all routers at once.

    Args:
      age:   (N, S) candidate flit ages (S=5: 4 input ports + injection).
      valid: (N, S) bool candidate present.
      we:    (N, S) bool candidate wanted to eject but was refused (S11/S14).
      dc:    (N, S) dst_col - col  (sign gives the desired X direction).
      dr:    (N, S) dst_row - row.
      vp:    (N, 4) bool port physically exists (mesh edges).

    Returns:
      assigned: (N, S) port index in 0..3, or -1 for invalid candidates.
      deflect:  (N, S) bool — candidate did not get its first preference.

    The chosen candidate's row is read by a one-hot select over the slots
    (as in ``state.node_get``), never a gather: a TPU gather of it costs
    about 1.8 ms at 208x208.
    """
    n, s_ = age.shape
    ports = jnp.arange(4, dtype=I32)
    slot = jnp.arange(s_, dtype=I32)

    # priority key: age desc, slot asc (injection = last slot, loses ties)
    key = jnp.where(valid, age * 8 + (s_ + 2 - slot), -1)

    # PMDR preference scores (S9) per port 0..3: lower = preferred; a
    # desired direction scores 0 (X) or 1 (Y), any other port 10 + port.  A
    # desired direction only scores if the port exists (matches serial
    # `_prefs` vp filter; for in-mesh destinations it always exists).
    score = jnp.stack([jnp.where(dr < 0, 1, 10), jnp.where(dc > 0, 0, 11),
                       jnp.where(dr > 0, 1, 12), jnp.where(dc < 0, 0, 13)],
                      axis=-1).astype(I32)
    score = jnp.where(vp[:, None, :], score, 1000)           # (N, S, 4)

    taken = jnp.zeros((n, 4), bool)
    done = ~valid
    assigned = jnp.full((n, s_), -1, I32)
    deflect = jnp.zeros((n, s_), bool)
    for _ in range(s_):
        kk = jnp.where(done, -1, key)
        best = jnp.argmax(kk, axis=1)
        has = jnp.max(kk, axis=1) >= 0
        sel = slot[None, :] == best[:, None]                  # (N, S)
        bscore = jnp.sum(jnp.where(sel[:, :, None], score, 0), axis=1)
        eff = bscore + taken.astype(I32) * 10000
        port = jnp.argmin(eff, axis=1).astype(I32)
        onehot_b = sel & has[:, None]
        onehot_p = (ports[None, :] == port[:, None]) & has[:, None]
        assigned = jnp.where(onehot_b, port[:, None], assigned)
        fp = jnp.argmin(bscore, axis=1).astype(I32)           # first pref
        wej = jnp.any(sel & we, axis=1)
        defl = wej | (port != fp)
        deflect = jnp.where(onehot_b, defl[:, None], deflect)
        taken = taken | onehot_p
        done = done | onehot_b
    return assigned, deflect


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True, scale: float | None = None
                  ) -> jnp.ndarray:
    """Reference attention. q,k,v: (B, H, S, D) / (B, H, T, D)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    logits = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        s_, t_ = logits.shape[-2:]
        mask = jnp.tril(jnp.ones((s_, t_), bool), t_ - s_)
        logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", w, v.astype(jnp.float32)).astype(q.dtype)
