"""Sharded sweep launcher (launch/sweep.py) + persistent compile cache.

In-process tests use a 1-device mesh (the tier-1 suite must not force a
host device count — conftest.py); the multi-device bit-identity and
warm-cache properties are exercised through the launcher's own CPU
rehearsal (``run_smoke(2, tiny=True)``), which forces devices in fresh
children.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bench, congestion as cong
from repro.core.fabric import simulator as sim_lib, systems
from repro.core.mitigation import score as mscore, search as msearch
from repro.launch import sweep
from repro.launch.mesh import make_sweep_mesh

CELLS = [("cresco8", 8), ("cresco8", 12)]
GRID_KW = dict(n_iters=6, warmup=2)


def _rows_equal(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        for f in ("system", "n_nodes", "vector_bytes", "profile"):
            assert getattr(ra, f) == getattr(rb, f)
        for f in ("t_uncongested_s", "t_congested_s", "ratio"):
            va, vb = getattr(ra, f), getattr(rb, f)
            assert va == vb or (np.isnan(va) and np.isnan(vb)), \
                (f, va, vb)  # bit-identical, not approx


def test_shard_bounds():
    assert sweep._shard_bounds(10, 4) == [(0, 3), (3, 6), (6, 8), (8, 10)]
    assert sweep._shard_bounds(4, 4) == [(0, 1), (1, 2), (2, 3), (3, 4)]
    # fewer items than devices: empty shards are skipped, not dispatched
    assert sweep._shard_bounds(2, 8) == [(0, 1), (1, 2)]
    assert [hi - lo for lo, hi in sweep._shard_bounds(17, 4)] \
        == [5, 4, 4, 4]


def test_pad_batch():
    tree = {"a": np.arange(10).reshape(5, 2), "b": np.ones(5)}
    padded = sim_lib.pad_batch(tree, 4)
    assert padded["a"].shape == (8, 2) and padded["b"].shape == (8,)
    np.testing.assert_array_equal(padded["a"][:5], tree["a"])
    np.testing.assert_array_equal(padded["a"][5:], tree["a"][[0, 0, 0]])
    # already a multiple: returned untouched
    assert sim_lib.pad_batch(tree, 5) is tree
    # axis-1 padding (candidate lanes)
    p1 = sim_lib.pad_batch({"x": np.arange(6).reshape(2, 3)}, 2, axis=1)
    assert p1["x"].shape == (2, 4)
    np.testing.assert_array_equal(p1["x"][:, 3], p1["x"][:, 0])


def test_device_launcher_bit_identical_to_plain():
    """run_scale_grid through the per-device dispatcher (1-device mesh —
    every executable is the plain single-device jit) reproduces the
    plain path bit for bit; ShardedOut marshals lazily."""
    plain = bench.run_scale_grid(CELLS, "ring_allgather", "incast",
                                 [1 << 20], [cong.steady()], **GRID_KW)
    mesh = make_sweep_mesh()
    sharded = bench.run_scale_grid(CELLS, "ring_allgather", "incast",
                                   [1 << 20], [cong.steady()], mesh=mesh,
                                   **GRID_KW)
    _rows_equal(plain, sharded)


def test_shard_map_entry_bit_identical_on_one_device_mesh():
    """simulator.run_cells_hetero(mesh=...) — the shard_map dispatch —
    is bit-identical to the plain batched call on a 1-device mesh, and
    the sharded executable is memoized per mesh (one trace, reused)."""
    sysp = systems.get_system("cresco8")
    cases = [bench.build_case(sysp, n, "ring_allgather", "incast")
             for _, n in CELLS]
    dims, stacked = bench.bucket_stack([c.geom for c in cases])
    rows = []
    for case in cases:
        dt = bench.choose_dt(case.topo, case.n_victims, 1 << 20, case.lat())
        p = case.cell_params(1 << 20, cong.steady(), dt,
                             n_flows=dims.n_flows)
        rows.append(sim_lib.stack_params([p, p]))
    params = sim_lib.stack_params(rows)
    kw = dict(chunk=512, max_chunks=40, stride=8)
    n_it = jnp.asarray(6, jnp.int32)

    plain = sim_lib.run_cells_hetero(stacked, params, n_it, **kw)
    mesh = make_sweep_mesh()
    before = sim_lib.trace_count("run_cells_hetero_sharded")
    out1 = sim_lib.run_cells_hetero(stacked, params, n_it, mesh=mesh, **kw)
    out2 = sim_lib.run_cells_hetero(stacked, params, n_it, mesh=mesh, **kw)
    assert sim_lib.trace_count("run_cells_hetero_sharded") - before <= 1
    for k in plain:
        a = np.asarray(plain[k])
        np.testing.assert_array_equal(a, np.asarray(out1[k]), err_msg=k)
        np.testing.assert_array_equal(a, np.asarray(out2[k]), err_msg=k)

    # lane sharding slices the candidate axis instead of the cell axis
    lane = sim_lib.run_cells_hetero(stacked, params, n_it, mesh=mesh,
                                    shard_axis="lane", **kw)
    for k in plain:
        np.testing.assert_array_equal(np.asarray(plain[k]),
                                      np.asarray(lane[k]), err_msg=k)


def test_launch_then_collect_matches_blocking_run():
    """launch_scale_grid returns without marshalling; .results() later
    yields exactly what the blocking run_scale_grid returns — so grids
    launched back-to-back overlap marshal with in-flight compute."""
    args = (CELLS, "ring_allgather", "incast", [1 << 20], [cong.steady()])
    pending = bench.launch_scale_grid(*args, **GRID_KW)
    blocking = bench.run_scale_grid(*args, **GRID_KW)
    _rows_equal(pending.results(), blocking)


def test_run_candidates_launcher_parity():
    """The mitigation search's lane-sharded launcher path (candidates
    ride vmap lanes) matches the plain call bit for bit on one device."""
    panel = mscore.panel_from_scenario(quick=True)[:1]
    cands = [msearch.default_candidate(),
             msearch.Candidate(policy=1, name="ecmp")]
    plain = msearch.run_candidates(panel, cands, n_iters=6, warmup=2)
    mesh = make_sweep_mesh()
    sharded = msearch.run_candidates(panel, cands, n_iters=6, warmup=2,
                                     mesh=mesh)
    assert len(plain) == len(sharded) == len(panel) * len(cands)
    for ra, rb in zip(plain, sharded):
        assert (ra.cell, ra.candidate) == (rb.cell, rb.candidate)
        assert ra.ratio == rb.ratio or (np.isnan(ra.ratio)
                                        and np.isnan(rb.ratio))
        assert ra.victim_bytes == rb.victim_bytes
        assert ra.aggr_bytes == rb.aggr_bytes


@pytest.fixture
def fresh_compile_cache(monkeypatch):
    """Let a test arm the cache anew; the process's jax config comes back
    as it was afterwards."""
    import jax

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setattr(sim_lib, "_COMPILE_CACHE_DIR", None)
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_compile_cache_env_resolution(tmp_path, monkeypatch,
                                      fresh_compile_cache):
    """ensure_compile_cache: $JAX_COMPILATION_CACHE_DIR wins, the fixed
    path in the checkout is the fallback, and the first activation
    sticks (idempotent)."""
    env_dir = str(tmp_path / "env")
    monkeypatch.setenv(sim_lib.COMPILE_CACHE_ENV, env_dir)
    active = sim_lib.ensure_compile_cache()
    assert active == env_dir and os.path.isdir(active)
    # already active: a later change of the variable is no re-point
    monkeypatch.setenv(sim_lib.COMPILE_CACHE_ENV, str(tmp_path / "other"))
    assert sim_lib.ensure_compile_cache() == active

    monkeypatch.setattr(sim_lib, "_COMPILE_CACHE_DIR", None)
    monkeypatch.delenv(sim_lib.COMPILE_CACHE_ENV)
    fixed = sim_lib.ensure_compile_cache()
    assert fixed == sim_lib.DEFAULT_COMPILE_CACHE_DIR
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.dirname(fixed) == checkout and os.path.isdir(fixed)


def test_compile_cache_env_never_overridden(tmp_path, monkeypatch,
                                            fresh_compile_cache):
    """With $JAX_COMPILATION_CACHE_DIR set, no directory in the code
    (the dry run's old artifact path, the checkout default) is where
    jax is pointed."""
    import jax

    want = str(tmp_path / "outside")
    monkeypatch.setenv(sim_lib.COMPILE_CACHE_ENV, want)
    assert sim_lib.ensure_compile_cache(min_compile_secs=10.0) == want
    assert jax.config.jax_compilation_cache_dir == want
    assert want != sim_lib.DEFAULT_COMPILE_CACHE_DIR


def test_force_host_device_count_appends(monkeypatch):
    from repro.jax_compat import force_host_device_count
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_foo=1 --xla_force_host_platform_device_count=3")
    force_host_device_count(8)
    flags = os.environ["XLA_FLAGS"].split()
    assert "--xla_foo=1" in flags  # user flag survives
    assert flags.count("--xla_force_host_platform_device_count=8") == 1
    assert not any(f.endswith("=3") for f in flags)  # replaced, not stacked


def test_dryrun_import_preserves_user_xla_flags(tmp_path):
    """Importing launch.dryrun used to OVERWRITE $XLA_FLAGS; it must now
    append its device-count flag after whatever the user set."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_cpu_enable_fast_math=false",
               PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c",
         "import repro.launch.dryrun, os; print(os.environ['XLA_FLAGS'])"],
        env=env, capture_output=True, text=True, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    flags = r.stdout.strip().split()
    assert "--xla_cpu_enable_fast_math=false" in flags
    assert "--xla_force_host_platform_device_count=512" in flags


def test_sweep_smoke_two_devices(tmp_path):
    """The acceptance harness end-to-end (subprocess children force 2
    CPU host devices): sharded launch bit-identical to single-device,
    cache populated under the test's own directory, warm relaunch
    cheaper than cold."""
    report = sweep.run_smoke(2, workdir=str(tmp_path), tiny=True)
    assert report["ok"], report["checks"]
    assert report["devices"] == "cpu virtual devices"
    assert os.listdir(tmp_path / "xla_cache")
    assert report["checks"]["bit_identical_scale"]
    assert report["checks"]["bit_identical_panel"]
    assert report["sharded_cold"]["n_devices"] == 2
