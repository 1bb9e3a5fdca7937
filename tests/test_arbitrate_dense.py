"""``arbitrate_ref`` reads its tables by one-hot selects; it must give
the answers of the gather form it replaced, bit for bit.

``_arbitrate_gather`` is that form, kept here verbatim as the oracle.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.ref import arbitrate_ref

I32 = jnp.int32


def _arbitrate_gather(age, valid, we, dc, dr, vp):
    """The gather form of ``arbitrate_ref`` (``take_along_axis`` reads and
    ``.at[].set`` table writes)."""
    n, s_ = age.shape
    ports = jnp.arange(4, dtype=I32)
    slot = jnp.arange(s_, dtype=I32)

    # priority key: age desc, slot asc (injection = last slot, loses ties)
    key = jnp.where(valid, age * 8 + (s_ + 2 - slot), -1)

    # PMDR preference scores (S9): lower = preferred.  A desired direction
    # only scores if the port exists (matches serial `_prefs` vp filter; for
    # in-mesh destinations the desired port always exists).
    score = jnp.broadcast_to(10 + ports[None, None, :], (n, s_, 4))
    score = score.at[:, :, 1].set(jnp.where(dc > 0, 0, score[:, :, 1]))
    score = score.at[:, :, 3].set(jnp.where(dc < 0, 0, score[:, :, 3]))
    score = score.at[:, :, 2].set(jnp.where(dr > 0, 1, score[:, :, 2]))
    score = score.at[:, :, 0].set(jnp.where(dr < 0, 1, score[:, :, 0]))
    score = jnp.where(vp[:, None, :], score, 1000)
    first_pref = jnp.argmin(score, axis=2).astype(I32)

    taken = jnp.zeros((n, 4), bool)
    done = ~valid
    assigned = jnp.full((n, s_), -1, I32)
    deflect = jnp.zeros((n, s_), bool)
    for _ in range(s_):
        kk = jnp.where(done, -1, key)
        best = jnp.argmax(kk, axis=1)
        has = jnp.max(kk, axis=1) >= 0
        bscore = jnp.take_along_axis(score, best[:, None, None].repeat(4, 2),
                                     axis=1)[:, 0, :]
        eff = bscore + taken.astype(I32) * 10000
        port = jnp.argmin(eff, axis=1).astype(I32)
        onehot_b = (slot[None, :] == best[:, None]) & has[:, None]
        onehot_p = (ports[None, :] == port[:, None]) & has[:, None]
        assigned = jnp.where(onehot_b, port[:, None], assigned)
        fp = jnp.take_along_axis(first_pref, best[:, None], axis=1)[:, 0]
        wej = jnp.take_along_axis(we, best[:, None], axis=1)[:, 0]
        defl = wej | (port != fp)
        deflect = jnp.where(onehot_b, defl[:, None], deflect)
        taken = taken | onehot_p
        done = done | onehot_b
    return assigned, deflect


def _mesh_ports(n, rng):
    """Valid ports of ``n`` routers placed on a 4-wide mesh: the edge
    rows and columns miss their outward ports (N=0, E=1, S=2, W=3)."""
    rows = max(2, -(-n // 4))
    node = rng.permutation(rows * 4)[:n]
    r, c = node // 4, node % 4
    return np.stack([r > 0, c < 3, r < rows - 1, c > 0], axis=1)


def _case(kind, n, seed):
    """One set of arbitration inputs of ``kind``; candidates never
    outnumber a router's ports (the bufferless invariant)."""
    rng = np.random.default_rng(seed)
    age = rng.integers(0, 64, (n, 5)).astype(np.int32)
    vp = rng.random((n, 4)) < 0.85
    valid = rng.random((n, 5)) < 0.6
    dc = rng.integers(-3, 4, (n, 5)).astype(np.int32)
    dr = rng.integers(-3, 4, (n, 5)).astype(np.int32)
    if kind == "age_ties":       # every slot of a router the same age, or two
        age = np.repeat(rng.integers(0, 3, (n, 1)), 5, axis=1)
        age[:, rng.integers(0, 5)] += rng.integers(0, 2, n)
        age = age.astype(np.int32)
        valid = rng.random((n, 5)) < 0.9
    elif kind == "mesh_edges":   # ports missing as at the mesh's edges
        vp = _mesh_ports(n, rng)
    elif kind == "dst_self":     # candidates at their destination
        home = rng.random((n, 5)) < 0.5
        dc = np.where(home, 0, dc).astype(np.int32)
        dr = np.where(home, 0, dr).astype(np.int32)
    elif kind == "empty_slots":  # sparse slots, some routers with none
        valid = rng.random((n, 5)) < 0.2
        valid[rng.random(n) < 0.3] = False
    vp |= np.sum(vp, 1, keepdims=True) == 0
    for i in range(n):
        idx = np.where(valid[i])[0]
        valid[i, idx[int(vp[i].sum()):]] = False
    we = valid & (rng.random((n, 5)) < 0.2)
    if kind == "dst_self":
        we |= valid & (dc == 0) & (dr == 0) & (rng.random((n, 5)) < 0.7)
    return age, valid, we, dc, dr, vp


KINDS = ["random", "age_ties", "mesh_edges", "dst_self", "empty_slots"]


@pytest.mark.parametrize("n", [1, 7, 130, 1024])
@pytest.mark.parametrize("kind", KINDS)
def test_select_form_equals_gather_form(kind, n):
    for seed in range(3):
        case = [jnp.asarray(x) for x in
                _case(kind, n, 1000 * KINDS.index(kind) + 10 * n + seed)]
        a0, d0 = jax.jit(_arbitrate_gather)(*case)
        a1, d1 = jax.jit(arbitrate_ref)(*case)
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a0))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))


def test_select_form_equals_gather_form_under_vmap():
    """A batch of 8 scenarios, as the sweep runs it."""
    cases = [_case(KINDS[b % len(KINDS)], 130, 7000 + b) for b in range(8)]
    batch = [jnp.asarray(np.stack(xs)) for xs in zip(*cases)]
    a0, d0 = jax.jit(jax.vmap(_arbitrate_gather))(*batch)
    a1, d1 = jax.jit(jax.vmap(arbitrate_ref))(*batch)
    assert a1.shape == (8, 130, 5)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a0))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))
