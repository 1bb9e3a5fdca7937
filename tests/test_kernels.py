"""The Pallas router kernel vs its pure-jnp oracle (interpret mode)."""
import numpy as np
from hypothesis import HealthCheck, given, settings, strategies as st

import jax.numpy as jnp

from repro.kernels.ref import arbitrate_ref
from repro.kernels.router_phase import router_arbitrate_pallas
from tests.test_noc_properties import random_arb_case


@settings(max_examples=10, deadline=None,
          suppress_health_check=list(HealthCheck))
@given(seed=st.integers(0, 10_000), n=st.integers(1, 400))
def test_router_kernel_bit_exact(seed, n):
    rng = np.random.default_rng(seed)
    case = random_arb_case(rng, n)
    a0, d0 = arbitrate_ref(*map(jnp.asarray, case))
    a1, d1 = router_arbitrate_pallas(*map(jnp.asarray, case), interpret=True)
    assert np.array_equal(np.asarray(a0), np.asarray(a1))
    assert np.array_equal(np.asarray(d0), np.asarray(d1))


def test_pallas_router_inside_simulator():
    """End-to-end: the sim with the Pallas router equals the ref path."""
    import dataclasses
    from repro.core.config import SimConfig
    from repro.core.sim import run
    from repro.core.workloads import app_trace
    cfg = SimConfig(rows=3, cols=3, addr_bits=13, migrate_threshold=2)
    tr = app_trace(cfg, "matmul", 15, 1)
    a = run(cfg, tr)
    b = run(dataclasses.replace(cfg, use_pallas_router=True), tr)
    assert a == b

