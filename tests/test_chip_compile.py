"""Compiles for a described TPU v5e: what the chip's compiler would refuse
fails here, at no chip time.  Nothing runs, so nothing is timed.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import mesh2d, plan_fast, torus
from repro.core.routes import dimension_orders
from repro.kernels.possibility.kernel import possibility_v_pallas
from repro.kernels.simstep import ops as simstep_ops
from repro.noc import sim
from repro.noc.simconfig import Algo, SimConfig


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _on(one_chip, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)


def _meta(side: int, cfg: SimConfig) -> dict:
    t = mesh2d(side, side)
    n, p = t.num_nodes, t.num_ports
    return dict(N=n, P=p, V=cfg.num_vcs, NIN=n * p * cfg.num_vcs,
                P_LOCAL=t.port_local, NDIM=t.ndim,
                O=len(dimension_orders(t.ndim)), C=t.num_channels)


def _compile_runner(one_chip, meta, cfg, lanes=1, cycles=4):
    """Compile the campaign runner (scan of the resolved step, vmapped
    over lanes) for one v5e chip; returns the compiled executable."""
    state = dict(jax.eval_shape(lambda: sim.fresh_state(meta, cfg)))
    state = {k: jax.ShapeDtypeStruct((lanes,) + v.shape, v.dtype)
             for k, v in state.items()}
    runner = sim.get_runner(meta, cfg, cycles, multi_device=False)
    return runner.lower(_on(one_chip, sim.abstract_tables(meta)),
                        _on(one_chip, state)).compile()


@pytest.mark.parametrize("side", [8, 64])
def test_fused_step_compiles(one_chip, side):
    cfg = SimConfig(algo=Algo.XY)
    meta = _meta(side, cfg)
    mem = _compile_runner(one_chip, meta, cfg).memory_analysis()
    assert mem.argument_size_in_bytes > 0


@pytest.mark.parametrize("side", [16, 64])
def test_possibility_kernel_compiles(one_chip, side):
    n = side * side
    i32 = jax.ShapeDtypeStruct((n, n), jnp.int32, sharding=one_chip)
    f32 = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
    compiled = jax.jit(possibility_v_pallas, static_argnames=("offset",)
                       ).lower(i32, i32, f32, i32, offset=0).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_resolved_simstep_path_compiles(one_chip):
    """Whatever the ladder picks on TPU, compiled, never interpreted."""
    cfg = SimConfig(algo=Algo.BIDOR)
    meta = _meta(32, cfg)
    path, _, interp = simstep_ops.resolve_path(meta, cfg)
    assert not interp
    compiled = _compile_runner(one_chip, meta, cfg, lanes=2)
    assert (path == "dense") == ("tpu_custom_call" not in compiled.as_text())


def test_planner_compiles_with_the_tpu_defaults(one_chip, monkeypatch):
    """The device planner as a TPU process builds it — Pallas possibility
    pass, fp32 — single and batched (the campaign's pattern axis)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(plan_fast, "_STATICS_CACHE", {})
    assert plan_fast._use_pallas_default()
    t = torus(16, 16)
    st = plan_fast.plan_statics(t)
    n, c = st.n, st.c

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    scalars = (s((), jnp.float32), s((), jnp.int32))
    for lanes in (1, 2):
        args = (s((n, n), jnp.int32), s((lanes, n, n), jnp.float32),
                s((lanes, n), jnp.float32), s((lanes,), jnp.bool_),
                s((c,), jnp.bool_), s((n, n), jnp.bool_)) + scalars
        compiled = st.core_batched.lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
