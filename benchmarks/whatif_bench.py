"""What-if serving + learned-search benchmark (ISSUE 10).

Two measured demos, both recorded into ``BENCH_engine.json["whatif"]``
(read-modify-write — other sections untouched):

* **Agent convergence** — random walk, GA, CMA-ES and BO race to the
  bounded-grid winner's objective at equal evaluation budget on a fixed
  seeded panel (a collision-prone ECMP leaf-spine cell where the
  searched CC knobs actually move the victim ratio; the quick
  ``mitigation_panel`` cells are deliberately near-flat there). The
  acceptance gate: CMA-ES or BO reaches the grid target with STRICTLY
  fewer simulator evaluations than random walk.
* **Coalescing** — K=3 mixed-bucket what-if queries answered serially
  (one server each) vs coalesced (one server, shared waves). Gates:
  per-query scorecards bit-identical, and the coalesced path answers
  with strictly fewer engine dispatches.

``--check-against BENCH_engine.json`` additionally gates the two
hardware-independent ratios against the committed artifact:
``evals_ratio`` (best learned agent's evals-to-target over random's —
lower is better) and ``call_ratio`` (coalesced dispatches over serial —
lower is better). Wall-clock numbers ride along for trajectory only and
are never gated.

Usage:
  PYTHONPATH=src python -m benchmarks.whatif_bench --quick \
      --check-against BENCH_engine.json                      # CI smoke
  PYTHONPATH=src python -m benchmarks.whatif_bench           # write
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

from repro.core import congestion as cong
from repro.core.fabric import simulator as sim
from repro.core.fabric.systems import get_system
from repro.core.mitigation import agents
from repro.core.mitigation.search import PanelCell
from repro.runtime import whatif

MiB = float(1 << 20)
KiB = float(1 << 10)
KNOBS = ("hol_factor", "md")


def _convergence_panel():
    """The seeded race panel: ECMP collisions give the knobs a real
    objective gradient (probed spread ~0.50-0.56)."""
    return (PanelCell(name="ecmp8", system=get_system("nanjing_ecmp"),
                      n_nodes=8, victim="ring_allgather",
                      aggressor="alltoall", vector_bytes=4 * MiB,
                      profile=cong.steady()),)


def run_convergence(quick: bool) -> dict:
    budget, batch = (24, 8) if quick else (48, 8)
    kw = dict(n_iters=5, warmup=2, max_steps=60_000) if quick \
        else dict(n_iters=10, warmup=3)
    t0 = time.perf_counter()
    rep = agents.compare_agents(["random", "ga", "cmaes", "bo"],
                                _convergence_panel(), budget=budget,
                                batch=batch, knobs=KNOBS, seed=0, **kw)
    wall = time.perf_counter() - t0

    def reached(kind):
        e = rep["agents"][kind]["evals_to_target"]
        return float("inf") if e is None else float(e)

    best_learned = min(reached("cmaes"), reached("bo"))
    evals_ratio = (best_learned / reached("random")
                   if np.isfinite(reached("random"))
                   and np.isfinite(best_learned) else
                   (0.0 if np.isfinite(best_learned) else float("inf")))
    out = {
        "budget": budget, "batch": batch, "knobs": list(KNOBS),
        "target": rep["target"], "wall_s": round(wall, 2),
        "evals_ratio": round(evals_ratio, 4),
        "agents": {k: {"best_objective": d["best_objective"],
                       "evals_to_target": d["evals_to_target"],
                       "evals": d["evals"], "best": d["best"],
                       "traces": d["traces"],
                       "best_label": d["best_label"]}
                   for k, d in rep["agents"].items()},
    }
    failures = []
    if not best_learned < reached("random"):
        failures.append(
            f"convergence: best learned agent used {best_learned} evals "
            f"to target vs random's {reached('random')} — not strictly "
            "fewer")
    return out, failures


def _coalescing_queries(quick: bool):
    cands = tuple(agents.grid_candidates(
        KNOBS, points_per_knob=2 if quick else 3))
    return [
        whatif.WhatIfQuery(system="cresco8", n_nodes=8,
                           vector_bytes=256 * KiB, agent="grid",
                           candidates=cands, budget=len(cands), batch=2),
        whatif.WhatIfQuery(system="cresco8", n_nodes=16,
                           vector_bytes=128 * KiB, agent="grid",
                           candidates=cands, budget=len(cands), batch=2),
        whatif.WhatIfQuery(system="lumi", n_nodes=16,
                           vector_bytes=256 * KiB, agent="grid",
                           candidates=cands[:-1], budget=len(cands),
                           batch=2),
    ]


def _table(res):
    return {s.candidate: (s.ratio_min, s.ratio_mean, s.aggr_gbps,
                          s.jain, s.t_base_worst_rel)
            for s in res.scores}


def run_coalescing(quick: bool) -> dict:
    kw = dict(n_iters=5, warmup=2, max_steps=50_000) if quick \
        else dict(n_iters=10, warmup=3)
    queries = _coalescing_queries(quick)

    # coalesced first: it pays the compiles, so the serial pass (same
    # lane shapes per query) cannot look artificially slow
    srv = whatif.WhatIfServer(max_batch=len(queries), **kw)
    uids = [srv.submit(q) for q in queries]
    t0 = time.perf_counter()
    stats = srv.run_until_drained()
    wall_coal = time.perf_counter() - t0
    coalesced = [srv.result(u) for u in uids]

    serial = []
    serial_calls = 0
    t0 = time.perf_counter()
    for q in queries:
        s1 = whatif.WhatIfServer(max_batch=1, **kw)
        u = s1.submit(q)
        s1.run_until_drained()
        serial.append(s1.result(u))
        serial_calls += s1.stats.coalesced_calls
    wall_serial = time.perf_counter() - t0

    bit_identical = all(_table(a) == _table(b)
                        for a, b in zip(coalesced, serial))
    out = {
        "n_queries": len(queries),
        "mixed_buckets": True,
        "bit_identical": bit_identical,
        "coalesced_calls": stats.coalesced_calls,
        "serial_calls": serial_calls,
        "call_ratio": round(stats.coalesced_calls / serial_calls, 4),
        "lanes": stats.lanes,
        "wall_coalesced_s": round(wall_coal, 2),
        "wall_serial_s": round(wall_serial, 2),
        "winners": [{"query": f"{q.system}-{q.n_nodes}",
                     "winner": r.winner.candidate,
                     "finish_reason": r.finish_reason,
                     "evals": r.evals}
                    for q, r in zip(queries, coalesced)],
    }
    failures = []
    if not bit_identical:
        failures.append("coalescing: shared-wave scorecards differ from "
                        "serial per-query runs")
    if not stats.coalesced_calls < serial_calls:
        failures.append(
            f"coalescing: {stats.coalesced_calls} coalesced dispatches "
            f">= {serial_calls} serial — batching bought nothing")
    return out, failures


def check_against(section, committed_path, margin):
    """Gate the two hardware-independent ratios vs the committed
    artifact; wall times are machine-dependent and never gated."""
    committed = json.loads(Path(committed_path).read_text())
    old = committed.get("whatif", {})
    failures = []
    for key, path in (("evals_ratio", ("convergence", "evals_ratio")),
                      ("call_ratio", ("coalescing", "call_ratio"))):
        old_v = old.get(path[0], {}).get(path[1])
        new_v = section[path[0]][path[1]]
        if old_v is None:
            continue
        if new_v > old_v * (1.0 + margin):
            failures.append(f"{key}: {new_v:.3f} > committed "
                            f"{old_v:.3f} + {margin:.0%}")
        else:
            print(f"  {key}: {new_v:.3f} vs committed {old_v:.3f} — OK")
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--quick", action="store_true",
                    help="small budgets + 2-point grids (CI smoke)")
    ap.add_argument("--out", default="BENCH_engine.json")
    ap.add_argument("--check-against", default=None, metavar="JSON",
                    help="gate evals_ratio / call_ratio against a "
                    "committed artifact; fail on regression")
    ap.add_argument("--regress-margin", type=float, default=0.30,
                    help="allowed relative ratio regression "
                    "(default 30%%)")
    ap.add_argument("--write", action="store_true",
                    help="write --out even in --check-against mode")
    args = ap.parse_args(argv)

    print(f"whatif_bench: quick={args.quick} "
          f"backend={jax.default_backend()}")
    t0 = time.time()
    conv, fails_c = run_convergence(args.quick)
    print(f"  convergence: target={conv['target']['objective']:.4f} "
          f"({conv['target']['label']})")
    for k, d in conv["agents"].items():
        print(f"    {k:7s} best={d['best_objective']:.4f} "
              f"evals_to_target={d['evals_to_target']} "
              f"traces={d['traces']}")
    coal, fails_k = run_coalescing(args.quick)
    print(f"  coalescing: {coal['n_queries']} queries "
          f"bit_identical={coal['bit_identical']} "
          f"calls {coal['serial_calls']} -> {coal['coalesced_calls']} "
          f"wall {coal['wall_serial_s']}s -> {coal['wall_coalesced_s']}s")

    section = {
        "schema": 1,
        "quick": args.quick,
        "jax_backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "wall_s": round(time.time() - t0, 1),
        "convergence": conv,
        "coalescing": coal,
    }
    failures = fails_c + fails_k
    if args.check_against:
        failures += check_against(section, args.check_against,
                                  args.regress_margin)
    if args.write or not args.check_against:
        path = Path(args.out)
        doc = json.loads(path.read_text()) if path.exists() else {}
        doc["whatif"] = section
        path.write_text(json.dumps(doc, indent=1) + "\n")
        print(f"wrote {args.out} (whatif section)")
    if failures:
        print("WHATIF BENCH FAILURES:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        return 1
    print("whatif_bench: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
