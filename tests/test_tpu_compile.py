"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed with jax, so the main path's programs can
be compiled for a v5e that is only *described*
(``jax.experimental.topologies``).  That catches what CPU interpret mode
cannot: a Pallas kernel the Mosaic compiler refuses, a kernel silently
replaced by its interpreted twin, a program that does not fit the chip's
16 GB, a sharded step without its halo collectives.  Nothing runs, so
these say nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and
pytest-xdist workers must all collect the same tests.  The persistent
compile cache is off around these compiles (an entry written for a
described chip cannot be read back without one).
"""
import importlib.util
import os
import re
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.config import SimConfig
from repro.core.state import init_state

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, shardings):
    """ShapeDtypeStructs of ``tree``, each leaf placed by the matching
    leaf of ``shardings``."""
    return jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        tree, shardings)


def test_router_kernel_compiles_for_v5e_at_paper_scale(one_chip):
    """The phase-2 arbitration kernel, compiled (not interpreted), for
    all 43,264 routers of the paper's 208x208 mesh."""
    from repro.kernels.router_phase import router_arbitrate_pallas
    n = 208 * 208
    args = [jax.ShapeDtypeStruct((n, 5), dt, sharding=one_chip)
            for dt in (jnp.int32, jnp.bool_, jnp.bool_, jnp.int32,
                       jnp.int32)]
    args.append(jax.ShapeDtypeStruct((n, 4), jnp.bool_, sharding=one_chip))
    compiled = router_arbitrate_pallas.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_xla_arbitration_reads_no_gathers_at_paper_scale(one_chip):
    """The arbitration every cell runs (``use_pallas_router`` off), for
    all 43,264 routers: its tables are built and read by dense selects,
    so it holds no gather and no dynamic-update-slice and plans no temp."""
    from repro.kernels.ref import arbitrate_ref
    n = 208 * 208
    args = [jax.ShapeDtypeStruct((n, 5), dt, sharding=one_chip)
            for dt in (jnp.int32, jnp.bool_, jnp.bool_, jnp.int32,
                       jnp.int32)]
    args.append(jax.ShapeDtypeStruct((n, 4), jnp.bool_, sharding=one_chip))
    compiled = jax.jit(arbitrate_ref).lower(*args).compile()
    text = compiled.as_text()
    assert " gather(" not in text
    assert " dynamic-update-slice(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0


#: the 64x64 run loop's planned temp plus output bytes before per-node
#: state was written by slot selects (compiled for a described v5e by the
#: TPU compiler installed with jax 0.9.0): 478,382,592 temp + 14,539,264
#: out.  With the selects it plans 143,458,304 + 14,539,264.
LOOP64_TEMP_PLUS_OUT_BEFORE_SELECTS = 492_921_856


@pytest.fixture(scope="module")
def loop64(one_chip):
    """The 64x64 packed run loop with the Pallas router, compiled once
    for a described v5e; ``(cfg, compiled)``."""
    from repro.core.sim import _run_jit
    cfg = SimConfig(rows=64, cols=64, centralized_directory=False,
                    state_dtype_policy="packed", use_pallas_router=True)
    state = jax.eval_shape(
        lambda t: init_state(cfg, t),
        jax.ShapeDtypeStruct((cfg.num_nodes, 20), jnp.int32))
    state = _shapes(state, jax.tree.map(lambda _: one_chip, state))
    cap = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return cfg, _run_jit.lower(state, cfg, cap, 1).compile()


def test_sim_loop_with_pallas_router_compiles_for_one_v5e(loop64):
    """The whole run loop lowered for a v5e carries the compiled router
    kernel (not its interpreted twin) and fits one chip's memory."""
    _, compiled = loop64
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < need < V5E_HBM_BYTES, need


def test_sim_loop_writes_per_node_state_without_scatters(loop64):
    """Per-node state is written by dense slot selects
    (``state.node_set``): the only scatters left in the run loop are the
    directory's (``dir_loc`` is indexed by tag across the mesh), and the
    loop plans no more temp memory than it did with per-node scatters."""
    import re
    from repro.core.state import dir_shape
    cfg, compiled = loop64
    shapes = re.findall(r"= [a-z0-9]+\[([0-9,]*)\]\S* scatter\(",
                        compiled.as_text())
    sizes = {int(np.prod([int(d) for d in shp.split(",") if d]))
             for shp in shapes}
    assert shapes and sizes == {int(np.prod(dir_shape(cfg)))}, shapes
    mem = compiled.memory_analysis()
    planned = mem.temp_size_in_bytes + mem.output_size_in_bytes
    assert planned <= LOOP64_TEMP_PLUS_OUT_BEFORE_SELECTS, planned


def _sharded_step(topo, rows, cols, n_cycles):
    """The spatial shard_map step over a described 2x2 chip mesh,
    compiled."""
    from repro.core.sharded import make_sharded_step, state_specs, to_grid
    from repro.core.state import make_geometry
    cfg = SimConfig(rows=rows, cols=cols, centralized_directory=False,
                    dir_layout="home", state_dtype_policy="packed")
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    grid_spec = NamedSharding(mesh, P("data", "model"))
    state = jax.eval_shape(
        lambda t: to_grid(init_state(cfg, t), cfg),
        jax.ShapeDtypeStruct((cfg.num_nodes, 20), jnp.int32))
    specs = state_specs(cfg, ("data",), ("model",))
    state = _shapes(state, jax.tree.map(
        lambda p: NamedSharding(mesh, p), specs,
        is_leaf=lambda x: isinstance(x, P)))
    geo = make_geometry(cfg.rows, cfg.cols)
    rc = (cfg.rows, cfg.cols)
    geo_args = [jax.ShapeDtypeStruct(rc, jnp.int32, sharding=grid_spec)] * 3
    geo_args.append(jax.ShapeDtypeStruct(rc + (4,), geo.valid_port.dtype,
                                         sharding=grid_spec))
    step = make_sharded_step(cfg, mesh)(n_cycles)
    return step.lower(state, *geo_args).compile()


def test_sharded_step_compiles_for_v5e_2x2(topo):
    """The spatial shard_map step over a described 2x2 chip mesh: its
    phase-3 halo exchange lowers to collective-permutes."""
    compiled = _sharded_step(topo, 64, 64, 8)
    text = compiled.as_text()
    assert "collective-permute" in text
    mem = compiled.memory_analysis()
    assert 0 < mem.argument_size_in_bytes < V5E_HBM_BYTES


def test_halo_bytes_of_the_benchmark_are_the_compiled_permutes(topo):
    """``halo_ici_share.2x2`` counts the bytes of the halo exchange from
    the mesh's shape: they are the operands of the four collective
    permutes the step compiles to, here over unequal 12x20 tiles."""
    root = Path(__file__).resolve().parent.parent
    if str(root) not in sys.path:   # the reader imports chipbench
        sys.path.insert(0, str(root))
    path = root / "chipbench" / "metrics" / "halo_ici_share.2x2.py"
    spec = importlib.util.spec_from_file_location("halo_ici_share_2x2", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    compiled = _sharded_step(topo, 24, 40, 8)
    # `%collective-permute-start.1 = (s32[1,20,10]{...}, s32[...], ...)`
    # (or the synchronous form): the first shape is the slab sent
    shapes = re.findall(
        r"= \(?s32\[([\d,]*)\][^\n]*?collective-permute(?:-start)?\(",
        compiled.as_text())
    assert len(shapes) == 4, shapes
    sent = sum(4 * int(np.prod([int(d) for d in shp.split(",")]))
               for shp in shapes)
    assert sent == reader.halo_bytes_per_cycle(24, 40)
