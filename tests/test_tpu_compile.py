"""The main path's Pallas kernels compile for a TPU v5e (no chip needed).

The TPU compiler ships with libtpu and compiles for a chip that is
described, not attached: these tests lower the fused fabric-step core,
``fused_accumulate`` and one whole batched engine entry with
``interpret=False`` and compile them for one v5e chip. They catch what the
Pallas interpreter cannot (unaligned slices, VMEM overruns, lowering
gaps) at no chip time. Nothing runs, so they say nothing about results or
speed.

The topology is described inside a module-scoped fixture only: loading
libtpu at import time would make the test workers collect different
tests. Keep every such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# engine-bench LUMI shapes: nodes -> (F, L, H, n_sw, n_src)
ENGINE_DIMS = {16: (15, 351, 7, 180, 15), 64: (63, 1265, 7, 464, 63)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _spec(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                sharding=sharding)


def _core_args(n_nodes, sharding, batch=0):
    F, L, H, _, _ = ENGINE_DIMS[n_nodes]
    lead = (batch,) if batch else ()

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=sharding)

    f32, i32 = jnp.float32, jnp.int32
    return (s((F, H), i32), s((F,), f32), s((F,), i32), s((F,), f32),
            s((L + 1,), f32), s((L + 1,), f32), s((L + 1,), f32),
            s((L + 1,), i32), s((L + 1,), i32)) + tuple(
                s((), f32) for _ in range(5))


def _core(n_nodes, with_aux=False):
    from repro.kernels import fabric_step

    _, _, _, n_sw, n_src = ENGINE_DIMS[n_nodes]

    def core(*args):
        return fabric_step.fabric_step_core(
            *args, n_src=n_src, n_sw=n_sw, with_aux=with_aux,
            interpret=False)
    return core


@pytest.mark.parametrize("n_nodes,with_aux", [(16, False), (16, True),
                                              (64, False)])
def test_fabric_step_core_compiles_for_v5e(one_chip, n_nodes, with_aux):
    compiled = jax.jit(_core(n_nodes, with_aux)).lower(
        *_core_args(n_nodes, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fabric_step_core_vmapped_compiles_for_v5e(one_chip):
    """The engine's batched form: vmap adds a grid axis to the kernel."""
    compiled = jax.jit(jax.vmap(_core(16))).lower(
        *_core_args(16, one_chip, batch=4)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_accumulate_compiles_for_v5e(one_chip):
    from repro.kernels import fused_reduce

    acc = jax.ShapeDtypeStruct((512, 1024), jnp.float32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((512, 1024), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda a, b: fused_reduce.fused_accumulate(
        a, b, scale=0.5, interpret=False)).lower(acc, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_run_cells_engine_compiles_for_v5e(one_chip, monkeypatch):
    """One whole batched engine entry (chunked while_loop over the step
    scan) at 16 nodes on the fused kernel: the kernel is lowered by
    Mosaic, not interpreted."""
    from repro.core import bench, congestion as cong
    from repro.core.fabric import simulator as sim, systems
    from repro.kernels import ops as kernel_ops

    # the step calls ops.fabric_step_core, whose interpret default asks
    # the (CPU) default backend: steer it as the chip would
    monkeypatch.setattr(kernel_ops, "_default_interpret", lambda: False)
    case = bench.build_case(systems.get_system("lumi"), 16,
                            "ring_allreduce", "incast")
    dt = bench.choose_dt(case.topo, case.n_victims, 1 << 20, case.lat())
    params = sim.stack_params([case.cell_params(1 << 20, prof, dt)
                               for prof in (cong.no_congestion(),
                                            cong.steady())])
    geom = jax.tree_util.tree_map(lambda x: _spec(x, one_chip), case.geom)
    params = jax.tree_util.tree_map(lambda x: _spec(x, one_chip), params)
    n_iters = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = sim._run_cells_jit.lower(
        geom, params, n_iters, chunk=64, max_chunks=2, stride=8,
        backend="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_named_scopes_leave_the_chip_engine_unchanged(one_chip, monkeypatch):
    """The engine's named scopes are metadata on the chip too: compiled
    for a v5e with and without them, the module's text agrees once the
    per-op metadata and the debug tables are taken out (the Pallas
    kernel's body included)."""
    import contextlib
    import re

    from repro.core import bench, congestion as cong
    from repro.core.fabric import simulator as sim, systems
    from repro.kernels import ops as kernel_ops

    monkeypatch.setattr(kernel_ops, "_default_interpret", lambda: False)
    case = bench.build_case(systems.get_system("lumi"), 16,
                            "ring_allreduce", "incast")
    dt = bench.choose_dt(case.topo, case.n_victims, 1 << 20, case.lat())
    params = sim.stack_params([case.cell_params(1 << 20, prof, dt)
                               for prof in (cong.no_congestion(),
                                            cong.steady())])
    geom = jax.tree_util.tree_map(lambda x: _spec(x, one_chip), case.geom)
    params = jax.tree_util.tree_map(lambda x: _spec(x, one_chip), params)
    n_iters = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def compiled_text():
        def engine(*args, **kw):       # a new function: traced anew
            return sim._run_cells_jit.__wrapped__(*args, **kw)

        return jax.jit(engine, static_argnames=(
            "chunk", "max_chunks", "stride", "backend")).lower(
                geom, params, n_iters, chunk=64, max_chunks=2, stride=8,
                backend="pallas").compile().as_text()

    def strip(text):
        text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|"
                      r"StackFrames)\n(.+\n)*", "\n", text)
        return re.sub(r",? metadata=\{[^}]*\}", "", text)

    scoped = compiled_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled_text()
    assert "fabric_step/step_core" in scoped
    assert "/fabric_step/" not in plain
    assert "tpu_custom_call" in plain
    assert strip(scoped) == strip(plain)
