"""Smoke run of the fabric simulator's main path on a TPU.

    python chip_smoke.py               # one chip: engine, grid, what-if
    python chip_smoke.py --four-chips  # four chips: sharded sweep + what-if

Phases (one process, one line each: name, wall and compile seconds, the
step backend, PASS/FAIL):

* device  — the first device must be a TPU; there is no CPU fallback.
* engine  — the 4096-node LUMI-family engine-bench cell through
  ``simulator.run_cell`` on the fused kernel, compared with the lax
  oracle (``kernels/ref.py``).
* grid    — the fig5_steady quick grids at 256 nodes (12 rows) plus the
  mitigation panel's bursty Leonardo incast cell, through the scenario
  registry on the fused kernel, with a shortened iteration protocol;
  compared with the oracle's rows on the CPU
  (``artifacts/chip_smoke_cpu_rows.csv``) and, except for the 16384-flow
  AllToAll grids, with the same grids on the oracle on the chip.
* whatif  — three coalesced grid-agent what-if queries, bit-identical to
  serial per-query runs.
* whatif4 / sweep4 (``--four-chips`` only) — a lane-sharded what-if
  wave over four devices against the same wave on one device, and the
  sharded sweep launcher's quick workload (CRESCO8 and LUMI at 16 and 64
  nodes, the quick mitigation panel) over four devices against one
  device.

The last line of standard output is the contract line
``{"ok": true, "device": {...}}``; it is printed only when every phase
passed. Any failure exits non-zero.
"""
from __future__ import annotations

import argparse
import csv
import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import bench, scenarios  # noqa: E402
from repro.core.fabric import simulator as sim, systems  # noqa: E402
from repro.core.mitigation import agents  # noqa: E402
from repro.launch import sweep  # noqa: E402
from repro.runtime import whatif  # noqa: E402

MiB = float(1 << 20)

# Iteration times are quantised to simulation steps: an iteration ends on
# the step in which its last byte lands, so one float rounding that moves
# a completion across a step boundary moves the time by one ``dt``. The
# fused kernel sums a link's contributions in a different order than the
# oracle's scatter-add (fp32, DESIGN.md §13), and the bursty envelope's
# on/off edges come from float ``%`` and ``/`` of sim time
# (core/envelopes.py), which the TPU may round differently from the CPU;
# either moves a completion by at most one step. So a backend or a
# platform may differ by one step per reported time, and by nothing
# more.
TIME_TOL_STEPS = 1
# The grid protocol, shortened from the scenarios' own (fig5_steady: 25
# iterations, 5 of warmup): at 256 nodes the Leonardo incast 2 MiB lane
# runs ~5.7 ms per congested iteration at a 2 us step, so 25 iterations
# are ~70k steps of a 4-lane vmapped engine on each backend.
GRID_NODES = 256
GRID_ITERS, GRID_WARMUP = 6, 2
# CRESCO8's 256-node AllToAll 2 MiB lane collapses to ~12.4 ms per
# congested iteration at a 2 us step (~6200 steps), and its 4 lanes of
# 16384 flows take 14.2 ms per step on the fused kernel (v5e,
# benchmarks/step_probe.py): 6 iterations would be ~9 min. It runs 2
# iterations, 1 of warmup (~14k steps, ~3.5 min).
SHORT_GRIDS = {("cresco8", "alltoall"): (2, 1)}
# The AllToAll aggressor is all-pairs: at 256 nodes its grids hold 16384
# flows, and one pass of each on the oracle as well would add 16.5/30.1/
# 28.7 ms per step (CRESCO8/Leonardo/LUMI, v5e) for 14k/2k/2k steps. They
# run on the fused kernel only and are compared with the CPU oracle rows.
FUSED_ONLY = ("alltoall",)
# Delivered bytes of one flow may differ by what its line rate carries in
# one step (the same one-step quantum, in bytes).
BYTES_TOL_STEPS = 1
# shard_map dispatch partitions the compile, and XLA reassociates the
# step's float accumulators by ~1 ulp (DESIGN.md §14). choose_dt sizes a
# step at ~1/100 of an uncongested iteration, so one step is at most 1% of
# any reported time; the same bound is applied to every float of a row.
SHARD_MAP_RTOL = 1e-2

# The lax oracle's rows of the grid phase on the CPU, written by
# ``python -m benchmarks.smoke_cpu_rows``.
CPU_ROWS_PATH = os.path.join(ROOT, "artifacts", "chip_smoke_cpu_rows.csv")

ENGINE_NODES = 4096
WHATIF_NODES = 64
WHATIF_SYSTEMS = ("leonardo", "lumi", "cresco8")
WHATIF_KNOBS = ("hol_factor", "md")
WHATIF_KW = dict(n_iters=5, warmup=2, max_steps=50_000)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def fused_backend() -> str:
    """The backend the engine resolves with no override on this device;
    the fused phases refuse to run on anything but the kernel."""
    b = sim.resolve_step_backend()
    check(b == "pallas", f"step backend resolved to {b!r}, not the fused "
          "kernel (check $REPRO_FABRIC_KERNEL / set_step_backend)")
    return b


# --------------------------------------------------------------------------
# grid
# --------------------------------------------------------------------------


def grid_specs():
    """The grid phase's (scenario, grid) pairs: the fig5_steady quick
    grids at 256 nodes and the mitigation panel's bursty Leonardo incast
    cell, each scenario carrying the protocol its grid runs."""
    fig5 = scenarios.get("fig5_steady", quick=True)
    steady = []
    for g in fig5.grids:
        if g.n_nodes == GRID_NODES:
            it, wu = SHORT_GRIDS.get((g.system, g.aggressor),
                                     (GRID_ITERS, GRID_WARMUP))
            steady.append((dataclasses.replace(fig5, n_iters=it, warmup=wu),
                           g))
    panel = dataclasses.replace(scenarios.get("mitigation_panel", quick=True),
                                n_iters=GRID_ITERS, warmup=GRID_WARMUP)
    bursty = [(panel, g) for g in panel.grids
              if g.system == "leonardo" and g.n_nodes == 64]
    check(len(steady) == 6 and len(bursty) == 1,
          f"unexpected grid specs: {len(steady)} steady, {len(bursty)} "
          "bursty")
    return steady + bursty


def _row_key(r):
    return (r.system, int(r.n_nodes), r.aggressor, float(r.vector_bytes),
            r.profile)


def load_cpu_rows():
    with open(CPU_ROWS_PATH, newline="") as f:
        return {(d["system"], int(d["n_nodes"]), d["aggressor"],
                 float(d["vector_bytes"]), d["profile"]):
                (float(d["t_uncongested_us"]), float(d["t_congested_us"]))
                for d in csv.DictReader(f)}


def _grid_engine_args(grid):
    """The geometry and stacked lanes ``bench.run_grid`` builds for
    ``grid``, with each lane's dt."""
    case = bench.build_case(systems.get_system(grid.system), grid.n_nodes,
                            grid.victim, grid.aggressor, phased=grid.phased,
                            jobs=list(grid.jobs) or None)
    dts, params = bench.grid_params(case, grid.sizes, grid.profiles)
    return case.geom, params, dts


def _run_grid(scen, grid, meter):
    c0, t0 = meter["backend_compile_s"], time.perf_counter()
    rows = scenarios.run_grid_spec(scen, grid)
    print(f"  grid run {grid.system} n={grid.n_nodes} {grid.aggressor} "
          f"iters={scen.n_iters} warmup={scen.warmup} "
          f"backend={sim.resolve_step_backend()} "
          f"wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={meter['backend_compile_s'] - c0:.3f}", flush=True)
    return rows


def phase_grid(meter):
    cpu_rows = load_cpu_rows()
    specs = grid_specs()
    backend = fused_backend()
    chip = [_run_grid(scen, g, meter) for scen, g in specs]
    cross = [i for i, (_, g) in enumerate(specs)
             if g.aggressor not in FUSED_ONLY]
    sim.set_step_backend("ref")
    try:
        ref = {i: _run_grid(*specs[i], meter) for i in cross}
    finally:
        sim.set_step_backend(None)

    # the engine program of one grid, lowered and compiled here: a Mosaic
    # kernel is in the executable; the interpreter leaves none
    geom, params, _ = _grid_engine_args(specs[cross[0]][1])
    text = sim._run_cells_jit.lower(
        geom, params, jnp.asarray(GRID_ITERS, jnp.int32), chunk=2048,
        max_chunks=1, stride=8, backend=backend).compile().as_text()
    check("tpu_custom_call" in text, "no tpu_custom_call in the grid engine")

    n_rows = n_ref = 0
    problems = []  # every row is printed and checked before failing
    for i, ((_, grid), rows) in enumerate(zip(specs, chip)):
        dts = _grid_engine_args(grid)[2]
        per_size = 1 + len(grid.profiles)
        rrows = ref.get(i, [None] * len(rows))
        for j, (r, rr) in enumerate(zip(rows, rrows)):
            n_rows += 1
            dt = dts[(j // len(grid.profiles)) * per_size]
            key = _row_key(r)
            cpu = cpu_rows.get(key)
            print(f"  grid {r.system:9s} n={r.n_nodes:3d} {r.aggressor:8s} "
                  f"{r.vector_bytes / MiB:7.4f}MiB {r.profile:20s} "
                  f"dt={dt * 1e6:g}us t_u={r.t_uncongested_s * 1e6!r} "
                  f"(ref {rr and rr.t_uncongested_s * 1e6!r}, cpu "
                  f"{cpu and cpu[0]!r}) t_c={r.t_congested_s * 1e6!r} "
                  f"(ref {rr and rr.t_congested_s * 1e6!r}, cpu "
                  f"{cpu and cpu[1]!r}) ratio={r.ratio:.4f}", flush=True)
            if r.dnf or (rr is not None and rr.dnf):
                problems.append(f"{key}: did not finish")
            if rr is not None:
                n_ref += 1
                for f in ("t_uncongested_s", "t_congested_s"):
                    a, b = getattr(r, f), getattr(rr, f)
                    tol = TIME_TOL_STEPS * dt
                    if not (np.isfinite(a) and abs(a - b) <= tol):
                        problems.append(f"{key} {f}: chip {a} vs ref {b} "
                                        f"(tol {tol})")
            if cpu is None:
                problems.append(f"{key}: no CPU row")
                continue
            for f, b in zip(("t_uncongested_s", "t_congested_s"), cpu):
                a = getattr(r, f) * 1e6
                if not abs(a - b) <= TIME_TOL_STEPS * dt * 1e6:
                    problems.append(f"{key} {f}: chip {a}us vs CPU {b}us")
    check(not problems, f"{len(problems)} mismatches: "
          + "; ".join(problems))
    check(n_rows == 13 and n_ref == 7, f"{n_rows} rows, {n_ref} vs ref")
    return (f"rows={n_rows} vs_cpu=13 vs_ref={n_ref} "
            f"tpu_custom_call=yes")


# --------------------------------------------------------------------------
# engine
# --------------------------------------------------------------------------


def phase_engine():
    from benchmarks import engine_bench

    geom, params, dt = engine_bench._build(systems.get_system("lumi"),
                                           ENGINE_NODES)
    dims = sim.geometry_dims(geom)
    kw = dict(chunk=256, max_chunks=engine_bench.CELL_CHUNKS[ENGINE_NODES],
              stride=8)
    n_iters = jnp.asarray(4, jnp.int32)
    backend = fused_backend()
    t0 = time.perf_counter()
    text = sim._run_cell_jit.lower(geom, params, n_iters, backend=backend,
                                   **kw).compile().as_text()
    t_compile = time.perf_counter() - t0
    check("tpu_custom_call" in text, "no tpu_custom_call in the engine")
    t0 = time.perf_counter()
    out = jax.block_until_ready(sim.run_cell(geom, params, n_iters, **kw))
    t_run = time.perf_counter() - t0
    ref = jax.block_until_ready(sim.run_cell(geom, params, n_iters,
                                             backend="ref", **kw))
    out = {k: np.asarray(v) for k, v in out.items()}
    ref = {k: np.asarray(v) for k, v in ref.items()}
    for k, v in out.items():
        check(np.all(np.isfinite(v)), f"engine output {k} not finite")
    check(int(out["chunks"]) == int(ref["chunks"]), "chunk counts differ")
    check(np.array_equal(out["it"], ref["it"]),
          f"iterations differ: {out['it']} vs {ref['it']}")
    for j, n in enumerate(out["it"]):
        n = int(min(n, sim.TDONE_SLOTS))
        gap = np.max(np.abs(out["t_done"][j, :n] - ref["t_done"][j, :n]),
                     initial=0.0)
        check(gap <= TIME_TOL_STEPS * dt, f"job {j} t_done off by {gap}s")
    host_caps = np.asarray(params.host_caps)
    byte_gap = np.abs(out["fbytes"] - ref["fbytes"])
    check(np.all(byte_gap <= BYTES_TOL_STEPS * host_caps * dt),
          f"delivered bytes off by up to {byte_gap.max()}")
    steps = int(out["chunks"]) * kw["chunk"]
    return (f"F={dims.n_flows} L={dims.n_links} steps={steps} "
            f"engine_compile_s={t_compile:.3f} run_s={t_run:.3f} "
            f"it={out['it'].tolist()} max_fbytes_gap={byte_gap.max():.6g} "
            f"tpu_custom_call=yes")


# --------------------------------------------------------------------------
# what-if
# --------------------------------------------------------------------------


def _table(res):
    return repr({s.candidate: (s.ratio_min, s.ratio_mean, s.aggr_gbps,
                               s.jain, s.t_base_worst_rel)
                 for s in res.scores})


def _queries(systems_, batch):
    cands = tuple(agents.grid_candidates(WHATIF_KNOBS, points_per_knob=2))
    return [whatif.WhatIfQuery(system=s, n_nodes=WHATIF_NODES,
                               aggressor="incast",
                               vector_bytes=2 * MiB, agent="grid",
                               candidates=cands, budget=len(cands),
                               batch=batch)
            for s in systems_]


def _serve(queries, max_batch, **kw):
    srv = whatif.WhatIfServer(max_batch=max_batch, **WHATIF_KW, **kw)
    uids = [srv.submit(q) for q in queries]
    stats = srv.run_until_drained()
    return [srv.result(u) for u in uids], stats


def phase_whatif():
    fused_backend()
    queries = _queries(WHATIF_SYSTEMS, batch=2)
    coalesced, stats = _serve(queries, len(queries))
    serial_calls = 0
    for q, res in zip(queries, coalesced):
        (one,), s1 = _serve([q], 1)
        serial_calls += s1.coalesced_calls
        check(_table(res) == _table(one),
              f"{q.system}: coalesced answer differs from the serial run")
    check(stats.coalesced_calls < serial_calls,
          f"{stats.coalesced_calls} coalesced calls vs {serial_calls} "
          "serial")
    return (f"queries={len(queries)} calls {serial_calls}->"
            f"{stats.coalesced_calls} bit_identical=yes")


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------


def _close(a, b, rtol):
    if isinstance(a, float) or isinstance(b, float):
        a, b = float(a), float(b)
        return (np.isnan(a) and np.isnan(b)) or abs(a - b) <= rtol * abs(b)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(_close(x, y, rtol)
                                        for x, y in zip(a, b))
    return a == b


def phase_sweep4():
    from repro.launch.mesh import make_sweep_mesh

    fused_backend()
    mesh = make_sweep_mesh()
    check(mesh.devices.size == 4, f"mesh over {mesh.devices.size} devices")
    single = sweep.run_workload(None, tiny=False)
    per_dev = sweep.run_workload(mesh, tiny=False, dispatch="devices")
    for k in ("digest_scale", "digest_panel"):
        check(per_dev[k] == single[k],
              f"per-device dispatch {k} differs from one device")
    shmap = sweep.run_workload(mesh, tiny=False, dispatch="shard_map")
    worst = 0.0
    for k in ("results_scale", "runs_panel"):
        for ra, rb in zip(shmap[k], single[k]):
            for f, v in ra.items():
                check(_close(v, rb[f], SHARD_MAP_RTOL),
                      f"shard_map {k}.{f}: {v} vs one device {rb[f]}")
                if isinstance(v, float) and np.isfinite(v) and rb[f]:
                    worst = max(worst, abs(v - rb[f]) / abs(rb[f]))
    exact = all(shmap[k] == single[k]
                for k in ("digest_scale", "digest_panel"))
    return (f"devices=4 per_device=bit_identical shard_map_max_rel="
            f"{worst:.3g} (tol {SHARD_MAP_RTOL:g}) "
            f"shard_map_bit_identical={exact}")


def phase_whatif4():
    from repro.launch.mesh import make_sweep_mesh

    fused_backend()
    mesh = make_sweep_mesh()
    queries = _queries(("leonardo",), batch=4)  # one wave, four lanes
    (one,), _ = _serve(queries, 1)
    (four,), _ = _serve(queries, 1, launcher=sweep.whatif_launcher(mesh))
    check(_table(one) == _table(four),
          "lane-sharded what-if wave differs from one device")
    return "devices=4 lanes=4 bit_identical=yes"


# --------------------------------------------------------------------------


def device_info(n_expected):
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu", f"first device is {d.platform!r}, not a TPU")
    check(len(devs) >= n_expected,
          f"{len(devs)} devices, {n_expected} needed")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def run_phase(name, fn, meter):
    c0 = meter["backend_compile_s"]
    t0 = time.perf_counter()
    try:
        detail, ok = fn(), True
    except Exception as e:  # reported, and the run exits non-zero
        traceback.print_exc()
        detail, ok = f"{type(e).__name__}: {e}", False
    wall = time.perf_counter() - t0
    print(f"phase={name} wall_s={wall:.3f} "
          f"compile_s={meter['backend_compile_s'] - c0:.3f} "
          f"backend={sim.resolve_step_backend()} "
          f"{'PASS' if ok else 'FAIL'} {detail}", flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phases (needs 4 devices)")
    args = ap.parse_args(argv)

    meter = sweep.compile_meter()
    info = {}

    def phase_device():
        info.update(device_info(4 if args.four_chips else 1))
        if args.four_chips:
            check(info["count"] == 4, f"{info['count']} devices, not 4")
        return f"kind={info['kind']} count={info['count']}"

    if not run_phase("device", phase_device, meter):
        return 1
    # armed before the phases' ahead-of-time compiles, so the engine calls
    # that follow them find those executables in the cache
    sim.ensure_compile_cache()
    # riskiest last; the first failure ends the run
    phases = ([("whatif4", phase_whatif4), ("sweep4", phase_sweep4)]
              if args.four_chips else
              [("engine", phase_engine), ("grid", lambda: phase_grid(meter)),
               ("whatif", phase_whatif)])
    for name, fn in phases:
        if not run_phase(name, fn, meter):
            return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
