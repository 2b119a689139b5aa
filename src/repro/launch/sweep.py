"""Sharded experiment sweep launcher (ROADMAP item 2, DESIGN.md §14).

One batched engine call (``run_cells_hetero``) saturates a single
device; this layer partitions the batch across a 1-D device mesh and
overlaps host-side result marshalling with device compute:

* **per-device dispatch** (default) — the cell (or candidate-lane) axis
  is split into contiguous shards, each ``device_put`` onto its own
  device and dispatched through the SAME single-device jit executable
  the plain path uses. jax dispatch is async, so all shards run
  concurrently and the launcher returns a lazy output view
  (:class:`ShardedOut`) that concatenates per shard on first access —
  marshalling shard 0 overlaps compute of shards 1..N. Because the
  per-shard executables are the unpartitioned single-device program and
  vmapped ``while_loop`` lanes are independent (finished lanes freeze),
  this path is BIT-IDENTICAL to the single-device run — asserted by
  :func:`run_smoke` on CPU virtual devices and by ``chip_smoke.py
  --four-chips`` on four chips.
* **shard_map dispatch** (``dispatch='shard_map'``) — one jitted
  ``jax.shard_map`` call over the mesh (simulator.run_cells_hetero's
  ``mesh=`` entry). On a multi-device mesh
  XLA's *partitioned* compile reassociates the step's float accumulators
  by ~1 ulp vs the unpartitioned executable (deterministic; measured in
  DESIGN.md §14), so this mode is exact only on 1-device meshes and
  ulp-close otherwise.

A relaunched sweep skips XLA compilation through the engine's
persistent compile cache (:func:`simulator.ensure_compile_cache`).
:func:`run_smoke` demonstrates the cold/warm delta across fresh
processes; it is a CPU rehearsal on forced host devices, run from the
tests with a fresh work directory.

CLI:
  PYTHONPATH=src python -m repro.launch.sweep --out child.json ...
      # one measured workload process (spawned by run_smoke)
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import subprocess
import sys
import time
from collections.abc import Mapping

import numpy as np

# NOTE: jax / repro.core imports stay function-local so ``--child`` can
# amend XLA_FLAGS (device count) before the backend initializes.


def _shard_bounds(n: int, n_shards: int):
    """Contiguous balanced split of ``n`` items into at most ``n_shards``
    non-empty (lo, hi) ranges."""
    base, extra = divmod(n, n_shards)
    bounds, lo = [], 0
    for i in range(n_shards):
        width = base + (1 if i < extra else 0)
        if width == 0:
            break
        bounds.append((lo, lo + width))
        lo += width
    return bounds


def _tree_slice(tree, lo: int, hi: int, axis: int):
    import jax

    def cut(x):
        idx = [slice(None)] * np.ndim(x)
        idx[axis] = slice(lo, hi)
        return x[tuple(idx)]

    return jax.tree_util.tree_map(cut, tree)


class ShardedOut(Mapping):
    """Lazy view over per-shard run outputs: concatenates one key across
    shards on first access (np.asarray blocks per shard, so assembling
    early shards overlaps compute of later ones)."""

    def __init__(self, outs, axis: int):
        self._outs = outs
        self._axis = axis
        self._cache = {}

    def __getitem__(self, key):
        if key not in self._cache:
            self._cache[key] = np.concatenate(
                [np.asarray(o[key]) for o in self._outs], axis=self._axis)
        return self._cache[key]

    def __iter__(self):
        return iter(self._outs[0])

    def __len__(self):
        return len(self._outs[0])


def dispatch_hetero(geoms, params, n_iters, *, mesh, shard_axis="cell",
                    chunk=2048, max_chunks=98, stride=8,
                    **engine_kw) -> ShardedOut:
    """Per-device async dispatch of a run_cells_hetero batch: shard the
    requested axis across ``mesh``'s devices, dispatch every shard
    through the standard single-device jit (bit-identical executables),
    return without blocking."""
    import jax

    from repro.core import spans
    from repro.core.fabric import simulator as sim

    if shard_axis not in ("cell", "lane"):
        raise ValueError(f"shard_axis must be 'cell' or 'lane', "
                         f"got {shard_axis!r}")
    axis = 0 if shard_axis == "cell" else 1
    devices = list(mesh.devices.flat)
    n = int(jax.tree_util.tree_leaves(params)[0].shape[axis])
    outs = []
    for (lo, hi), dev in zip(_shard_bounds(n, len(devices)), devices):
        with spans.span(spans.SHARD, device=dev.id):
            g = geoms if axis == 1 else _tree_slice(geoms, lo, hi, 0)
            outs.append(sim.run_cells_hetero(
                jax.device_put(g, dev),
                jax.device_put(_tree_slice(params, lo, hi, axis), dev),
                jax.device_put(n_iters, dev),
                chunk=chunk, max_chunks=max_chunks, stride=stride,
                **engine_kw))
    return ShardedOut(outs, axis)


def device_launcher(mesh, *, shard_axis: str = "cell",
                    dispatch: str = "devices", donate: bool = False):
    """A launcher callable with run_cells_hetero's calling convention,
    bound to ``mesh`` — what bench.run_scale_grid / search.run_candidates
    plug in via their ``mesh=``/``launcher=`` kwargs."""
    if dispatch not in ("devices", "shard_map"):
        raise ValueError(f"dispatch must be 'devices' or 'shard_map', "
                         f"got {dispatch!r}")

    def launcher(geoms, params, n_iters, *, chunk=2048, max_chunks=98,
                 stride=8, **engine_kw):
        if dispatch == "shard_map":
            from repro.core.fabric import simulator as sim

            return sim.run_cells_hetero(geoms, params, n_iters,
                                        chunk=chunk, max_chunks=max_chunks,
                                        stride=stride, mesh=mesh,
                                        shard_axis=shard_axis,
                                        donate=donate, **engine_kw)
        return dispatch_hetero(geoms, params, n_iters, mesh=mesh,
                               shard_axis=shard_axis, chunk=chunk,
                               max_chunks=max_chunks, stride=stride,
                               **engine_kw)

    return launcher


def whatif_launcher(mesh, *, dispatch: str = "devices"):
    """Lane-sharded launcher for the what-if serving layer
    (runtime.whatif.WhatIfServer): a coalesced wave stacks queries on
    the cell axis and candidate generations on the lane axis, so
    sharding the lane axis spreads each wave's candidate lanes across
    the mesh while keeping the per-device executables (and hence the
    results) bit-identical to the single-device path."""
    return device_launcher(mesh, shard_axis="lane", dispatch=dispatch)


# --------------------------------------------------------------------------
# Measured child workload: quick scale sweep + mitigation panel
# --------------------------------------------------------------------------

TINY_CELLS = (("cresco8", 8), ("cresco8", 12))
QUICK_CELLS = (("cresco8", 16), ("cresco8", 64),
               ("lumi", 16), ("lumi", 64))
MiB = float(2 ** 20)


def _workload(tiny: bool):
    """The measured sweep: the quick ``scale_sweep`` grid (2 scales x
    2 systems) plus the quick mitigation panel x a small candidate
    space. ``tiny`` shrinks both for the tier-1 subprocess test."""
    from repro.core import congestion as cong
    from repro.core.fabric.routing import POLICY_ECMP, POLICY_NSLB
    from repro.core.mitigation import score as mscore
    from repro.core.mitigation import search as msearch

    cells = TINY_CELLS if tiny else QUICK_CELLS
    sizes = (MiB / 4,) if tiny else (2 * MiB,)
    grid = dict(cells=list(cells), victim_coll="ring_allgather",
                aggr_coll="alltoall", sizes=sizes,
                profiles=(cong.steady(),),
                n_iters=6 if tiny else 15, warmup=2 if tiny else 3)
    panel = mscore.panel_from_scenario("mitigation_panel", quick=True)
    candidates = [msearch.default_candidate(),
                  msearch.Candidate(policy=POLICY_ECMP),
                  msearch.Candidate(policy=POLICY_NSLB)]
    if tiny:
        panel = panel[:1]
        candidates = candidates[:2]
    return grid, panel, candidates


def _result_rows(objs):
    rows = [dataclasses.asdict(r) for r in objs]
    for row in rows:  # canonical float types for the digest
        for k, v in row.items():
            if isinstance(v, (np.floating, np.integer)):
                row[k] = float(v)
    return rows


def _digest(rows) -> str:
    """Canonical bit-level digest of marshalled results: full-precision
    float repr, sorted keys — equal digests mean bit-identical runs."""
    blob = json.dumps(rows, sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode()).hexdigest()


def run_workload(mesh, *, tiny: bool, dispatch: str = "devices") -> dict:
    """Run the measured sweep once (launch both phases, then collect —
    the scale grid's host marshalling overlaps the panel's device
    compute) and return rows + digests."""
    import jax

    from repro.core import bench
    from repro.core.mitigation import search as msearch

    grid, panel, candidates = _workload(tiny)
    t0 = time.perf_counter()
    scale_launcher = panel_launcher = None
    if mesh is not None:
        scale_launcher = device_launcher(mesh, shard_axis="cell",
                                         dispatch=dispatch)
        panel_launcher = device_launcher(mesh, shard_axis="lane",
                                         dispatch=dispatch)
    pending = bench.launch_scale_grid(
        grid["cells"], grid["victim_coll"], grid["aggr_coll"],
        grid["sizes"], grid["profiles"], n_iters=grid["n_iters"],
        warmup=grid["warmup"], launcher=scale_launcher)
    t_launch = time.perf_counter() - t0
    runs = msearch.run_candidates(panel, candidates,
                                  launcher=panel_launcher)
    scale_results = pending.results()
    wall = time.perf_counter() - t0
    scale_rows = _result_rows(scale_results)
    panel_rows = _result_rows(runs)
    return {
        "n_devices": len(jax.devices()),
        "dispatch": "single" if mesh is None else dispatch,
        "launch_s": round(t_launch, 4),
        "wall_s": round(wall, 3),
        "digest_scale": _digest(scale_rows),
        "digest_panel": _digest(panel_rows),
        "results_scale": scale_rows,
        "runs_panel": panel_rows,
    }


def compile_meter():
    """Tap jax's own monitoring events for a noise-free compile
    measurement. ``/jax/core/compile/backend_compile_duration`` wraps
    ``compile_or_get_cached``: on a persistent-cache miss it times the
    real XLA compile, on a hit only the cache retrieval — so its sum is
    exactly "seconds spent compiling (or loading) executables",
    untouched by device-compute wall noise. Hit/miss counts and jax's
    ``compile_time_saved_sec`` (stored compile time minus retrieval
    cost) ride along."""
    import jax.monitoring as jmon

    meter = {"backend_compile_s": 0.0, "compile_saved_s": 0.0,
             "cache_hits": 0, "cache_misses": 0}

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            meter["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            meter["cache_misses"] += 1

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            meter["backend_compile_s"] += duration
        elif event == "/jax/compilation_cache/compile_time_saved_sec":
            meter["compile_saved_s"] += duration

    jmon.register_event_listener(on_event)
    jmon.register_event_duration_secs_listener(on_duration)
    return meter


def child_main(args) -> dict:
    """One measured process: optional forced host-device count, the
    persistent compile cache its environment places, workload run twice
    (rerun digest must match — determinism assert). Compile cost is read from jax's
    monitoring events (see ``compile_meter``), not inferred from wall
    clock, so host-core contention between shards never enters the
    measurement."""
    from repro.core.fabric import simulator as sim
    from repro.launch.mesh import make_sweep_mesh

    meter = compile_meter()
    cache_dir = sim.ensure_compile_cache()
    mesh = None if args.single else make_sweep_mesh()
    first = run_workload(mesh, tiny=args.tiny, dispatch=args.dispatch)
    first_meter = dict(meter)
    second = run_workload(mesh, tiny=args.tiny, dispatch=args.dispatch)
    assert first["digest_scale"] == second["digest_scale"], \
        "non-deterministic rerun (scale grid)"
    assert first["digest_panel"] == second["digest_panel"], \
        "non-deterministic rerun (panel)"
    out = dict(first)
    out["wall_first_s"] = first["wall_s"]
    out["wall_second_s"] = second["wall_s"]
    out["launch_first_s"] = first["launch_s"]
    out["launch_second_s"] = second["launch_s"]
    # all executables are built during the first workload run (the
    # second hits the in-process jit cache — asserted via hit/miss
    # deltas staying flat), so the first run's meter IS the process's
    # compile bill: real XLA compiles when the persistent cache misses,
    # retrieval cost when it hits
    out["compile_s"] = round(first_meter["backend_compile_s"], 3)
    out["compile_saved_s"] = round(first_meter["compile_saved_s"], 3)
    out["cache_hits"] = first_meter["cache_hits"]
    out["cache_misses"] = first_meter["cache_misses"]
    out["trace_counts"] = dict(sim.TRACE_COUNTS)
    out["cache_dir"] = cache_dir
    out["cache_entries"] = len(os.listdir(cache_dir))
    return out


# --------------------------------------------------------------------------
# Smoke orchestration: single vs sharded-cold vs sharded-warm children
# --------------------------------------------------------------------------


def _spawn_child(*, host_devices, cache_dir, out_path, tiny, dispatch,
                 single=False):
    cmd = [sys.executable, "-m", "repro.launch.sweep",
           "--out", out_path, "--dispatch", dispatch]
    if single:
        cmd.append("--single")
    if host_devices and not single:
        cmd += ["--host-devices", str(host_devices)]
    if tiny:
        cmd.append("--tiny")
    # a CPU rehearsal on forced host devices: the child never contends
    # for an accelerator, and its cache is the directory it is handed
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir)
    env.setdefault("PYTHONPATH",
                   os.path.join(os.path.dirname(__file__), "..", ".."))
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"sweep child failed ({' '.join(cmd)}):\n"
                           f"{r.stdout[-2000:]}\n{r.stderr[-4000:]}")
    with open(out_path) as f:
        return json.load(f)


def run_smoke(host_devices: int, *, workdir: str, tiny: bool = False,
              dispatch: str = "devices") -> dict:
    """The sharded-launch rehearsal on CPU virtual devices: fresh
    children run the same workload (1) on a single device, (2) sharded
    cold (empty persistent cache under ``workdir``), (3) sharded warm
    (same cache dir). Asserts the sharded results are bit-identical to
    the single-device run and that the warm relaunch cuts compile time.
    ``workdir`` must be a fresh directory: the cold child needs an empty
    cache."""
    cache_dir = os.path.join(workdir, "xla_cache")
    single = _spawn_child(host_devices=0,
                          cache_dir=os.path.join(workdir, "xla_cache_single"),
                          out_path=os.path.join(workdir, "single.json"),
                          tiny=tiny, dispatch=dispatch, single=True)
    cold = _spawn_child(host_devices=host_devices, cache_dir=cache_dir,
                        out_path=os.path.join(workdir, "cold.json"),
                        tiny=tiny, dispatch=dispatch)
    warm = _spawn_child(host_devices=host_devices, cache_dir=cache_dir,
                        out_path=os.path.join(workdir, "warm.json"),
                        tiny=tiny, dispatch=dispatch)

    checks = {
        "devices_forced": cold["n_devices"] >= max(2, host_devices),
        "bit_identical_scale":
            single["digest_scale"] == cold["digest_scale"]
            == warm["digest_scale"],
        "bit_identical_panel":
            single["digest_panel"] == cold["digest_panel"]
            == warm["digest_panel"],
        "cache_populated": warm["cache_entries"] > 0,
        # the cold child starts on an empty dir (every compile a miss);
        # the warm relaunch must find those entries
        "cache_hit_on_relaunch":
            cold["cache_hits"] == 0 and warm["cache_hits"] > 0
            and warm["cache_misses"] < cold["cache_misses"],
        # compile_s is metered from jax's backend_compile events (real
        # XLA compiles on a miss, retrieval cost on a hit) — the warm
        # child must spend well under the cold child's compile bill
        "cache_cuts_compile":
            warm["compile_s"] < max(0.6 * cold["compile_s"], 0.05),
    }
    child_keys = ("n_devices", "wall_first_s", "wall_second_s",
                  "launch_first_s", "launch_second_s", "compile_s",
                  "compile_saved_s", "cache_hits", "cache_misses")
    report = {
        "devices": "cpu virtual devices",
        "host_devices": host_devices,
        "tiny": tiny,
        "dispatch": dispatch,
        "single": {k: single[k] for k in child_keys},
        "sharded_cold": {k: cold[k] for k in
                         child_keys + ("cache_entries",)},
        "sharded_warm": {k: warm[k] for k in
                         child_keys + ("cache_entries",)},
        "checks": checks,
        "ok": all(checks.values()),
    }
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="one measured sweep workload process (run_smoke's "
                    "child)")
    ap.add_argument("--single", action="store_true",
                    help="run the plain single-device path")
    ap.add_argument("--host-devices", type=int, default=8,
                    help="forced CPU host device count for sharded runs")
    ap.add_argument("--dispatch", default="devices",
                    choices=["devices", "shard_map"],
                    help="sharded execution mode (devices = bit-exact "
                         "per-device dispatch; shard_map = one "
                         "partitioned jit, ulp-close on multi-device)")
    ap.add_argument("--tiny", action="store_true",
                    help="shrunken workload (tier-1 subprocess test)")
    ap.add_argument("--out", required=True, help="write the JSON report")
    args = ap.parse_args(argv)

    if args.host_devices and not args.single:
        # must happen before the jax backend initializes
        from repro.jax_compat import force_host_device_count

        force_host_device_count(args.host_devices)
    report = child_main(args)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, default=repr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
