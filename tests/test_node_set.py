"""``state.node_set`` and ``state.node_get``, the per-node slot selects
the phases write their per-node state and read their caches through,
against the scatter and the gather they replace:
``arr.at[node, *idx].set(where(mask, val, arr[node, *idx]))`` and
``arr[node, *idx]``."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.state import node_get, node_set

N = 7
#: name -> (array shape, number of slot axes indexed): per-node slots
#: (pending queue, forwarding table), set x way caches, and slots with a
#: trailing field row (send queue, reorder buffer)
LAYOUTS = {"N,K": ((N, 5), 1), "N,S,W": ((N, 4, 3), 2),
           "N,Q,F": ((N, 6, 5), 1)}
#: (mask, index, value) cases: a mixed mask at random slots, an
#: all-false mask, all-true masks at the first and at the last slot, and
#: a scalar value (the caches' resets write 0 and -1)
CASES = [("mixed", "random", "row"), ("none", "last", "row"),
         ("all", "first", "row"), ("all", "last", "row"),
         ("mixed", "random", "scalar")]


def _scatter_ref(arr, idx, mask, val):
    node = jnp.arange(arr.shape[0])
    at = (node,) + idx
    old = arr[at]
    m = mask.reshape(mask.shape + (1,) * (old.ndim - 1))
    return arr.at[at].set(jnp.where(m, val, old).astype(arr.dtype))


def _inputs(rng, layout, dtype, case):
    shape, k = LAYOUTS[layout]
    lo, hi = (-100, 100) if dtype == np.int8 else (-30000, 30000)
    arr = rng.integers(lo, hi, shape).astype(dtype)
    mask_kind, index_kind, value_kind = case
    mask = {"mixed": rng.random(N) < 0.5, "none": np.zeros(N, bool),
            "all": np.ones(N, bool)}[mask_kind]
    idx = tuple({"random": rng.integers(0, n, N), "first": np.zeros(N),
                 "last": np.full(N, n - 1)}[index_kind].astype(np.int32)
                for n in shape[1:1 + k])
    val = rng.integers(lo, hi, (N,) + shape[1 + k:]).astype(np.int32)
    return arr, idx, mask, val if value_kind == "row" else np.int32(-1)


@pytest.mark.parametrize("vmapped", [False, True], ids=["solo", "vmap"])
@pytest.mark.parametrize("case", CASES, ids=["-".join(c) for c in CASES])
@pytest.mark.parametrize("dtype", [np.int8, np.int16, np.int32],
                         ids=["int8", "int16", "int32"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_node_set_matches_scatter(layout, dtype, case, vmapped):
    rng = np.random.default_rng(0)
    if vmapped:   # a batch of 3 scenarios, as the sweep runs the phases
        batch = [_inputs(rng, layout, dtype, case) for _ in range(3)]
        args = [jnp.asarray(np.stack(x)) for x in zip(*batch)]
        args[1] = tuple(jnp.asarray(np.stack(x))
                        for x in zip(*(b[1] for b in batch)))
        got = jax.vmap(node_set)(*args)
        want = jax.vmap(_scatter_ref)(*args)
    else:
        arr, idx, mask, val = _inputs(rng, layout, dtype, case)
        args = (jnp.asarray(arr), tuple(map(jnp.asarray, idx)),
                jnp.asarray(mask), jnp.asarray(val))
        got = node_set(*args)
        want = _scatter_ref(*args)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if case[0] == "none":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(args[0]))


#: read cases: a full index (one element, or one field row) at random,
#: first and last slots, and a partial index (the first slot axis only:
#: a whole set of ways, as the cache probes read it)
READS = [("full", "random"), ("full", "first"), ("full", "last"),
         ("partial", "random")]


@pytest.mark.parametrize("vmapped", [False, True], ids=["solo", "vmap"])
@pytest.mark.parametrize("case", READS, ids=["-".join(c) for c in READS])
@pytest.mark.parametrize("dtype", [np.int8, np.int16, np.int32],
                         ids=["int8", "int16", "int32"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_node_get_matches_gather(layout, dtype, case, vmapped):
    rng = np.random.default_rng(1)
    reach, index_kind = case

    def inputs():
        arr, idx, _, _ = _inputs(rng, layout, dtype, ("all", index_kind,
                                                      "row"))
        return arr, idx[:1] if reach == "partial" else idx

    def gather(arr, idx):
        return arr[(jnp.arange(arr.shape[0]),) + idx]

    if vmapped:
        batch = [inputs() for _ in range(3)]
        arr = jnp.asarray(np.stack([b[0] for b in batch]))
        idx = tuple(jnp.asarray(np.stack(x))
                    for x in zip(*(b[1] for b in batch)))
        got, want = jax.vmap(node_get)(arr, idx), jax.vmap(gather)(arr, idx)
    else:
        arr, idx = inputs()
        arr, idx = jnp.asarray(arr), tuple(map(jnp.asarray, idx))
        got, want = node_get(arr, idx), gather(arr, idx)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
