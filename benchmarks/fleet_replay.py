"""Fleet-scale stochastic workload replay throughput (ISSUE 8).

Replays seeded stochastic workloads (core/workload.py: Poisson short
flows + long-lived training tenants with per-tenant CC mixes) as batched
seed sweeps through the hetero engine, with streaming percentile metrics
(p50/p99/p99.9 queue delay, FCT CDFs, per-tenant slowdown) accumulated
inside the scan — no per-step trace is ever materialized.

Per seed-count it measures, over ALL systems stacked into one geometry
bucket (one compile, asserted via TRACE_COUNTS):

* ``seeds_per_sec`` and ``sim_s_per_wall_s`` — replay throughput: how
  many seeds (and simulated fabric-seconds) one wall-second buys.
* ``metrics_overhead`` — wall-time ratio of the metrics-on run vs the
  metrics-off run of the same batch (both traceless); the streaming
  accumulators must stay cheap next to the step core.

Sanity gates (fail the run, exit 1): p99 >= p50 on the aggregate queue
delay, short flows complete (FCT samples > 0), per-flow delivered bytes
respect the NIC capacity bound, and shorts never deliver more than the
seed drew for them.

``--check-against BENCH_engine.json`` compares the hardware-normalized
``metrics_overhead`` per seed count against the committed ``"replay"``
rows and fails on > ``--regress-margin`` relative regression (CI smoke).
A plain run (or ``--write``) updates ONLY the ``"replay"`` section of
the artifact, read-modify-write, so engine_bench rows are untouched.

Usage:
  PYTHONPATH=src python -m benchmarks.fleet_replay             # full
  PYTHONPATH=src python -m benchmarks.fleet_replay --quick \
      --check-against BENCH_engine.json                        # CI smoke
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

from benchmarks.common import POINT_KEYS, cached_sweep, expected_point_keys
from repro.core import scenarios as scen
from repro.core import workload as wl
from repro.core.fabric import simulator as sim

SEED_COUNTS_FULL = (256, 1024)
CAP_TOL = 1.05  # fp32 accumulation slack on the capacity bound


def _specs(points, quick: bool):
    """One WorkloadSpec per registry point (deduped by system/n_nodes)."""
    seen = {}
    for system, n_nodes, _ in points:
        key = (system, int(n_nodes))
        if key in seen:
            continue
        if quick:
            seen[key] = wl.WorkloadSpec(
                system=system, n_nodes=int(n_nodes), short_slots=16,
                arrivals_mean=8.0, horizon_s=4e-3,
                tenant_bytes=float(1 << 19))
        else:
            seen[key] = wl.WorkloadSpec(system=system, n_nodes=int(n_nodes))
    return list(seen.values())


def _timed_replay(templates, seeds, *, chunk, metrics):
    t0 = time.perf_counter()
    out, padded = wl.run_replay(templates, seeds, chunk=chunk,
                                metrics=metrics, with_trace=False)
    jax.block_until_ready(out)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out, padded = wl.run_replay(templates, seeds, chunk=chunk,
                                metrics=metrics, with_trace=False)
    jax.block_until_ready(out)
    steady = time.perf_counter() - t0
    return out, padded, steady, max(first - steady, 0.0)


def _sanity(out, padded, seeds, summaries):
    """Distribution + conservation gates; returns a list of failures."""
    fails = []
    for k, (t, s) in enumerate(zip(padded, summaries)):
        tag = f"{t.spec.system}/n{t.spec.n_nodes}"
        qd = s["qdelay_s"]
        if not np.isnan(qd["0.99"]) and qd["0.99"] < qd["0.5"]:
            fails.append(f"{tag}: p99 qdelay {qd['0.99']:.3g} < "
                         f"p50 {qd['0.5']:.3g}")
        if s["fct_samples"] <= 0:
            fails.append(f"{tag}: no short-flow completions")
        # capacity bound: no flow delivers more than its NIC could carry
        fb = np.asarray(out["fbytes"])[k]  # (B, F)
        cap = t.host_caps[None, :] * np.asarray(out["t"])[k][:, None]
        if (fb > cap * CAP_TOL + 1.0).any():
            fails.append(f"{tag}: delivered bytes exceed NIC capacity")
        # shorts conservation: delivered <= drawn + one Euler-step
        # quantum (the final step delivers a full rate*dt even when
        # rem < rate*dt)
        params = wl.lower_seeds(t, seeds)
        drawn = np.asarray(params.bytes_per_iter)[:, t.short_idx]
        got = fb[:, t.short_idx]
        quantum = t.host_caps[t.short_idx] * t.dt
        if (got > drawn + quantum[None, :] * CAP_TOL + 1.0).any():
            fails.append(f"{tag}: shorts delivered more than drawn")
    return fails


def run_seed_counts(points, seed_counts, quick: bool, chunk: int):
    templates = [wl.build_template(s) for s in _specs(points, quick)]
    rows = []
    for n_seeds in seed_counts:
        seeds = np.arange(n_seeds, dtype=np.int64)
        t0 = sim.trace_count("run_cells_hetero")
        out, padded, wall_m, compile_m = _timed_replay(
            templates, seeds, chunk=chunk, metrics=True)
        compiles_metrics = sim.trace_count("run_cells_hetero") - t0
        t0 = sim.trace_count("run_cells_hetero")
        _, _, wall_p, _ = _timed_replay(templates, seeds, chunk=chunk,
                                        metrics=False)
        compiles_plain = sim.trace_count("run_cells_hetero") - t0
        summaries = wl.summarize_replay(out, padded)
        sim_s = float(np.asarray(out["t"]).sum())
        overhead = wall_m / max(wall_p, 1e-9)
        fails = _sanity(out, padded, seeds, summaries)
        if compiles_metrics > 1:
            fails.append(f"{n_seeds} seeds: {compiles_metrics} compiles "
                         "for one bucket (expected <= 1)")
        rows.append({
            "n_seeds": n_seeds,
            "n_systems": len(templates),
            "wall_s_metrics": round(wall_m, 4),
            "wall_s_plain": round(wall_p, 4),
            "compile_s": round(compile_m, 3),
            "compiles_metrics": compiles_metrics,
            "compiles_plain": compiles_plain,
            "metrics_overhead": round(overhead, 4),
            "seeds_per_sec": round(n_seeds * len(templates) / wall_m, 2),
            "sim_s_per_wall_s": round(sim_s / wall_m, 3),
            "systems": summaries,
            "failures": fails,
        })
        print(f"  seeds={n_seeds:5d} wall={wall_m:.2f}s "
              f"(plain {wall_p:.2f}s, overhead x{overhead:.3f})  "
              f"{rows[-1]['seeds_per_sec']:.1f} seeds/s  "
              f"{rows[-1]['sim_s_per_wall_s']:.3g} sim-s/s  "
              f"compiles={compiles_metrics}")
        for s in summaries:
            print(f"    {s['system']:8s} n={s['n_nodes']:3d} "
                  f"qdelay p50={s['qdelay_s']['0.5']:.3g}s "
                  f"p99={s['qdelay_s']['0.99']:.3g}s  "
                  f"fct p99={s['fct_s']['0.99']:.3g}s "
                  f"({s['fct_samples']:.0f} completions)")
        for f in fails:
            print(f"    SANITY FAIL: {f}")
    return rows


def _csv_rows(scenario, rows):
    """Flatten per-system summaries into the registry's CSV cache (keyed
    by POINT_KEYS['fleet_replay']) — batched compute, per-point rows."""
    keys, _ = expected_point_keys(scenario)
    by_sys = {}
    for row in rows:
        for s in row["systems"]:
            by_sys[(s["system"], str(s["n_nodes"]), str(row["n_seeds"]))] = {
                "qdelay_p50_s": s["qdelay_s"]["0.5"],
                "qdelay_p99_s": s["qdelay_s"]["0.99"],
                "fct_p99_s": s["fct_s"]["0.99"],
                "fct_samples": s["fct_samples"],
                "seeds_per_sec": row["seeds_per_sec"],
                "metrics_overhead": row["metrics_overhead"],
            }

    def fn(system, n_nodes, n_seeds):
        return by_sys[(system, str(n_nodes), str(n_seeds))]

    points = [(s, str(n), str(ns)) for (s, n, ns) in scenario.points
              if (s, str(n), str(ns)) in by_sys]
    return cached_sweep("fleet_replay", keys, points, fn, force=True)


def check_against(rows, committed_path, margin):
    """Gate the hardware-normalized metrics_overhead ratio per seed
    count; absolute wall times are machine-dependent and never gated."""
    committed = json.loads(Path(committed_path).read_text())
    old_rows = committed.get("replay", {}).get("seed_counts", [])
    old = {r["n_seeds"]: r["metrics_overhead"] for r in old_rows}
    failures = []
    for r in rows:
        n = r["n_seeds"]
        if n not in old:
            continue
        if r["metrics_overhead"] > old[n] * (1.0 + margin):
            failures.append(
                f"seeds={n}: metrics_overhead {r['metrics_overhead']:.3f} "
                f"> committed {old[n]:.3f} + {margin:.0%}")
        else:
            print(f"  seeds={n}: metrics_overhead "
                  f"{r['metrics_overhead']:.3f} vs committed "
                  f"{old[n]:.3f} — OK")
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--quick", action="store_true",
                    help="few seeds x 2 small systems (CI smoke)")
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--seed-counts", default=None, metavar="N,N",
                    help="override the seed-count ladder (comma list)")
    ap.add_argument("--out", default="BENCH_engine.json")
    ap.add_argument("--check-against", default=None, metavar="JSON",
                    help="compare metrics_overhead per seed count against "
                    "a committed artifact; fail on regression")
    ap.add_argument("--regress-margin", type=float, default=0.10,
                    help="allowed relative overhead regression "
                    "(default 10%%)")
    ap.add_argument("--write", action="store_true",
                    help="write --out even in --check-against mode")
    args = ap.parse_args(argv)

    scenario = scen.get("fleet_replay", quick=args.quick)
    if args.seed_counts:
        seed_counts = tuple(int(s) for s in args.seed_counts.split(","))
    elif args.quick:
        seed_counts = tuple(sorted({int(ns) for _, _, ns
                                    in scenario.points}))
    else:
        seed_counts = SEED_COUNTS_FULL
    chunk = args.chunk or (512 if args.quick else 2048)
    print(f"fleet_replay: points={scenario.points} "
          f"seed_counts={seed_counts} chunk={chunk} "
          f"backend={jax.default_backend()}")
    t0 = time.time()
    rows = run_seed_counts(scenario.points, seed_counts, args.quick, chunk)
    _csv_rows(scenario, rows)

    replay = {
        "schema": 1,
        "quick": args.quick,
        "jax_backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "point_keys": POINT_KEYS["fleet_replay"],
        "wall_s": round(time.time() - t0, 1),
        "seed_counts": rows,
    }

    failures = [f for r in rows for f in r["failures"]]
    if args.check_against:
        failures += check_against(rows, args.check_against,
                                  args.regress_margin)
    if args.write or not args.check_against:
        # read-modify-write: only the "replay" section is ours
        path = Path(args.out)
        doc = json.loads(path.read_text()) if path.exists() else {}
        doc["replay"] = replay
        path.write_text(json.dumps(doc, indent=1) + "\n")
        print(f"wrote {args.out} (replay section)")
    if failures:
        print("FLEET REPLAY FAILURES:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        return 1
    print("fleet_replay: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
