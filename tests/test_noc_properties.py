"""Property-based tests of the bufferless NoC invariants."""
import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings, strategies as st

import jax.numpy as jnp

from repro.core.config import SimConfig
from repro.core.ref_serial import SerialSim
from repro.core.sim import run
from repro.core.workloads import app_trace, random_trace
from repro.kernels.ref import arbitrate_ref


# ---------------------------------------------------------------------------
# arbitration properties (the paper's Fig. 3 router, §4.2 guarantees)
# ---------------------------------------------------------------------------

def random_arb_case(rng, n):
    age = rng.integers(0, 64, (n, 5)).astype(np.int32)
    vp = rng.random((n, 4)) < 0.85
    vp |= np.sum(vp, 1, keepdims=True) == 0
    valid = rng.random((n, 5)) < 0.6
    # bufferless invariant: candidates <= valid ports
    for i in range(n):
        nv = int(vp[i].sum())
        idx = np.where(valid[i])[0]
        for j in idx[nv:]:
            valid[i, j] = False
    we = (rng.random((n, 5)) < 0.2) & valid
    dc = rng.integers(-3, 4, (n, 5)).astype(np.int32)
    dr = rng.integers(-3, 4, (n, 5)).astype(np.int32)
    return age, valid, we, dc, dr, vp


@settings(max_examples=20, deadline=None,
          suppress_health_check=list(HealthCheck))
@given(seed=st.integers(0, 10_000), n=st.integers(1, 64))
def test_arbitration_invariants(seed, n):
    rng = np.random.default_rng(seed)
    age, valid, we, dc, dr, vp = random_arb_case(rng, n)
    assigned, deflect = map(np.asarray, arbitrate_ref(
        *map(jnp.asarray, (age, valid, we, dc, dr, vp))))

    for i in range(n):
        got = assigned[i][valid[i]]
        # every valid candidate is assigned a port
        assert np.all(got >= 0)
        # ports are distinct
        assert len(set(got.tolist())) == len(got)
        # only physically existing ports are used
        assert all(vp[i, p] for p in got)
        # invalid candidates get nothing
        assert np.all(assigned[i][~valid[i]] == -1)
        # age priority: an older flit never gets a strictly worse port than
        # a younger flit *both wanting the same primary* — weaker form:
        # the oldest flit with a unique max age is never deflected unless
        # it wanted ejection or its primary port does not exist
        ages = np.where(valid[i], age[i], -1)
        if (ages == ages.max()).sum() == 1 and ages.max() >= 0:
            j = int(np.argmax(ages))
            if not we[i, j]:
                assert not deflect[i, j], (i, j)


# ---------------------------------------------------------------------------
# system-level conservation / liveness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("app,dist", [("matmul", False), ("random", True)])
def test_flit_conservation_and_liveness(app, dist):
    cfg = SimConfig(rows=4, cols=4, addr_bits=14, migrate_threshold=2,
                    centralized_directory=not dist)
    tr = (random_trace(cfg, 40, 3) if app == "random"
          else app_trace(cfg, app, 40, 3))
    stats = run(cfg, tr)
    assert stats["finished"] == 1, "simulation must terminate"
    # every injected flit is eventually delivered (bufferless: no drops)
    assert stats["injected"] == stats["flits_delivered"]
    assert stats["send_drop"] == 0
    # request/reply conservation: a redirected request is received at both
    # the stale owner and the forward target (paper §3.3 redirection)
    assert stats["req_rcvd"] == stats["req_made"] + stats["redirection"]
    assert stats["reply_sent"] == stats["reply_rcvd"]
    assert stats["wb_sent"] == stats["wb_rcvd"]
    assert stats["migrations"] == stats["migrations_done"]


def test_directory_consistency_at_quiescence():
    """At finish: each L2 tag appears once, and the directory points at it."""
    cfg = SimConfig(rows=4, cols=4, addr_bits=14, migrate_threshold=2)
    tr = random_trace(cfg, 40, 9)
    s = SerialSim(cfg, tr)
    s.run()
    assert s.finished()
    seen = {}
    n = cfg.num_nodes
    for node in range(n):
        tags = s.l2_tag[node][s.l2_tag[node] >= 0]
        for t in tags.tolist():
            assert t not in seen, f"tag {t} duplicated: {seen[t]} and {node}"
            seen[t] = node
    for t, node in seen.items():
        assert s.dir_loc[t] == node, (t, node, s.dir_loc[t])
    # no dangling directory entries
    for t in np.where(s.dir_loc >= 0)[0].tolist():
        assert t in seen, f"directory points at missing block {t}"


def test_migration_actually_triggers():
    """A node hammering remote blocks pulls them over (paper §3.3).

    Node 3 installs 8 blocks; node 0 first spins on a private block long
    enough for those installs to land, then hammers node 3's blocks with a
    1-way L1 that thrashes (all 8 L1 tags map to set 0), so every access
    re-requests remotely and the streak counter fires."""
    from repro.core.config import CacheConfig
    cfg = SimConfig(rows=2, cols=2, addr_bits=14, migrate_threshold=2,
                    l1_miss_cycles=1, l2_hit_cycles=1, mem_cycles=5,
                    cache=CacheConfig(l1_sets=2, l1_ways=1, l1_block=32,
                                      l2_sets=8, l2_ways=2, l2_block=64))
    n = cfg.num_nodes
    blocks = np.array([64 * i for i in range(1, 9)], np.int32)
    private = 64 * 100
    prefix = 200
    tr = np.full((n, prefix + 64), private, np.int32)  # idle nodes spin
    tr[3, :8] = blocks
    tr[3, 8:] = 64 * 101
    tr[0, prefix:] = np.tile(blocks, 8)
    stats = run(cfg, tr)
    assert stats["finished"] == 1
    assert stats["migrations"] >= 1, stats
    assert stats["migrations"] == stats["migrations_done"]


def test_migration_handler_unit():
    """Unit: repeated REQs from one node flip the streak counter and emit a
    B2 migration packet (vectorized phase-1a handler)."""
    import jax.numpy as jnp
    from repro.core import state as S
    from repro.core.cache import phase1a
    from repro.core.config import MSG_B2, MSG_REQ
    from repro.core.state import init_state, make_node_ctx
    from repro.core.config import CacheConfig

    cfg = SimConfig(rows=2, cols=2, addr_bits=14, migrate_threshold=2,
                    cache=CacheConfig(4, 2, 32, 4, 2, 64))
    tr = np.zeros((4, 4), np.int32)
    st = init_state(cfg, tr)
    ctx = make_node_ctx(cfg)
    # node 1 holds tag 7 in its L2
    st = st._replace(l2_tag=st.l2_tag.at[1, 7 % 4, 0].set(7))
    mig = 0
    for _ in range(2):   # two REQs from node 2 (threshold=2)
        pc = jnp.zeros((4, cfg.pc_depth, S.NUM_P), jnp.int32)
        pc = pc.at[1, 0].set(jnp.asarray([1, MSG_REQ, 2, 2, 7], jnp.int32))
        st = st._replace(pc=pc)
        st = phase1a(st, cfg, ctx)
    stats = {k: int(v) for k, v in zip(
        __import__("repro.core.ref_serial", fromlist=["STAT_NAMES"]).STAT_NAMES,
        np.asarray(st.stats))}
    assert stats["migrations"] == 1, stats
    assert int(st.l2_mig[1, 7 % 4, 0]) == 1
    # the B2 descriptor is in node 1's send queue
    q = np.asarray(st.q_desc[1])
    typs = q[:int(st.q_size[1]), 0].tolist()
    assert MSG_B2 in typs, typs


def test_centralized_directory_is_a_hotspot():
    """The paper's observation: the centralized directory serializes."""
    import dataclasses
    cfg = SimConfig(rows=6, cols=6, addr_bits=16)
    tr = random_trace(cfg, 20, 2)
    central = run(cfg, tr)
    dist = run(dataclasses.replace(cfg, centralized_directory=False), tr)
    assert central["finished"] == 1 and dist["finished"] == 1
    assert central["cycles"] > dist["cycles"], (central["cycles"],
                                                dist["cycles"])
