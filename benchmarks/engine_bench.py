"""Engine perf trajectory: step-core kernel vs XLA lax path (ISSUE 6).

Benchmarks the simulator's per-step hot core at LUMI-scale pruned
geometries (16 -> 4096 nodes) and records the trajectory artifact
``BENCH_engine.json`` (ROADMAP item 2): per-cell wall-clock, steps/sec,
compile time, and the kernel-vs-lax step-time ratio, so later PRs can
prove (or catch regressions in) engine speedups.

Per scale it measures:

* ``cell`` — a real ``run_cell`` call on the production backend for this
  host (CPU container -> ref): wall-clock, executed steps, steps/sec and
  compile time. This is the number a characterization sweep pays per
  grid cell.
* ``step`` — a fixed-length jitted ``lax.scan`` of the step under each
  backend (``ref`` = XLA scatter path, ``pallas`` = fused kernel), best
  of ``--repeats``; ``kernel_vs_lax = ref_s / pallas_s`` (> 1 means the
  kernel wins). Off-TPU the kernel runs through the Pallas INTERPRETER,
  so the CPU ratio only tracks relative drift — the ``interpret`` flag
  is recorded so readers do not mistake it for TPU performance.
* ``parity`` — lock-step state comparison ref vs pallas (fp32-allclose,
  DESIGN.md §13); any mismatch fails the run (exit 1).

``--check-against BENCH_engine.json`` compares the hardware-normalized
``kernel_vs_lax`` ratio per scale against the committed artifact and
fails on > ``--regress-margin`` (default 10%) relative regression — the
CI smoke gate. Checking never rewrites the artifact; a plain run (or
``--write``) does.

Usage:
  PYTHONPATH=src python -m benchmarks.engine_bench            # full, writes
  PYTHONPATH=src python -m benchmarks.engine_bench --quick \
      --check-against BENCH_engine.json                       # CI smoke
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bench, congestion as cong
from repro.core.fabric import simulator as sim
from repro.core.fabric import systems

SCALES_FULL = (16, 64, 256, 1024, 4096)
SCALES_QUICK = (16, 64)
VECTOR_BYTES = 4 * 2 ** 20
# step-scan lengths tapered with scale: interpret-mode Pallas on CPU is
# emulation, the large scales only need enough steps for a stable ratio;
# the small (CI-gated) scales get long scans so the ratio is low-noise
N_STEPS = {16: 1024, 64: 512, 256: 64, 1024: 16, 4096: 8}
CELL_CHUNKS = {16: 12, 64: 12, 256: 8, 1024: 4, 4096: 2}
PARITY_STEPS = 8
FS_TOL = dict(rtol=2e-4, atol=1.0)


def _build(sysp, n_nodes):
    """LUMI allocation at ``n_nodes``; beyond the machine (4096 > 2978)
    a synthetic same-family fabric is built at the requested size."""
    machine = sysp.machine_nodes or n_nodes
    if n_nodes > machine:
        case = bench.build_case(sysp, n_nodes, "ring_allreduce", "incast",
                                topo=sysp.make_topology(n_nodes),
                                nodes=np.arange(n_nodes))
    else:
        case = bench.build_case(sysp, n_nodes, "ring_allreduce", "incast")
    dt = bench.choose_dt(case.topo, case.n_victims, VECTOR_BYTES,
                         case.lat(), case.max_phases)
    params = case.cell_params(VECTOR_BYTES, cong.steady(), dt)
    return case.geom, params, dt


def _time_best(fn, repeats):
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _step_scan(geom, params, backend, n_steps):
    @jax.jit
    def run(state):
        return jax.lax.scan(
            lambda s, _: sim.step(geom, params, s, backend=backend),
            state, None, length=n_steps)
    return run


def _measure_step(geom, params, backend, n_steps, repeats):
    run = _step_scan(geom, params, backend, n_steps)
    state = sim.init_state(geom, params)
    t0 = time.perf_counter()
    out = run(state)
    jax.block_until_ready(out)
    first = time.perf_counter() - t0
    steady = _time_best(
        lambda: jax.block_until_ready(run(state)), repeats)
    return {"total_s": round(steady, 6),
            "per_step_s": round(steady / n_steps, 9),
            "compile_s": round(max(first - steady, 0.0), 3),
            "n_steps": n_steps}


def _measure_cell(geom, params, n_nodes, repeats):
    kw = dict(chunk=256, max_chunks=CELL_CHUNKS[n_nodes], stride=8)
    n_iters = jnp.asarray(4, jnp.int32)

    def go():
        return jax.block_until_ready(
            sim.run_cell(geom, params, n_iters, **kw))
    t0 = time.perf_counter()
    out = go()
    first = time.perf_counter() - t0
    steady = _time_best(go, repeats)
    steps = int(np.asarray(out["chunks"])) * kw["chunk"]
    return {"wall_s": round(steady, 4),
            "compile_s": round(max(first - steady, 0.0), 3),
            "steps": steps,
            "steps_per_sec": round(steps / steady, 1)}


def _check_parity(geom, params):
    s_ref = jax.jit(lambda s: sim.step_debug(geom, params, s,
                                             backend="ref"))
    s_pal = jax.jit(lambda s: sim.step_debug(geom, params, s,
                                             backend="pallas"))
    state = sim.init_state(geom, params)
    for i in range(PARITY_STEPS):
        nr, gr, ar = s_ref(state)
        npal, gpal, apal = s_pal(state)
        for k in nr:
            if not np.allclose(np.asarray(npal[k]), np.asarray(nr[k]),
                               **FS_TOL):
                return f"MISMATCH state[{k}] step {i}"
        for k in ar:
            if not np.allclose(np.asarray(apal[k]), np.asarray(ar[k]),
                               **FS_TOL):
                return f"MISMATCH aux[{k}] step {i}"
        state = nr
    return "OK"


def run_scales(scales, repeats):
    sysp = systems.get_system("lumi")
    rows = []
    for n in scales:
        geom, params, dt = _build(sysp, n)
        dims = sim.geometry_dims(geom)
        n_steps = N_STEPS[n]
        parity = _check_parity(geom, params)
        cell = _measure_cell(geom, params, n, repeats)
        step_ref = _measure_step(geom, params, "ref", n_steps, repeats)
        step_pal = _measure_step(geom, params, "pallas", n_steps, repeats)
        ratio = step_ref["per_step_s"] / step_pal["per_step_s"]
        rows.append({
            "n_nodes": n, "dt_s": dt,
            "dims": {"n_flows": dims.n_flows, "n_links": dims.n_links,
                     "k_max": dims.k_max, "max_hops": dims.max_hops,
                     "n_sw": dims.n_sw, "n_src": dims.n_src},
            "cell": cell,
            "step": {"ref_per_step_s": step_ref["per_step_s"],
                     "pallas_per_step_s": step_pal["per_step_s"],
                     "ref_compile_s": step_ref["compile_s"],
                     "pallas_compile_s": step_pal["compile_s"],
                     "n_steps": n_steps,
                     "kernel_vs_lax": round(ratio, 4)},
            "parity": parity,
        })
        print(f"  n={n:5d}  F={dims.n_flows:5d} L={dims.n_links:6d} "
              f"cell={cell['wall_s']:.3f}s ({cell['steps_per_sec']:.0f} "
              f"steps/s)  step ref={step_ref['per_step_s']*1e3:.3f}ms "
              f"pallas={step_pal['per_step_s']*1e3:.3f}ms "
              f"ratio={ratio:.3f}  parity={parity}")
    return rows


def check_against(rows, committed_path, margin):
    """Compare the hardware-normalized kernel_vs_lax ratio per scale;
    absolute times are machine-dependent and never gated."""
    committed = json.loads(Path(committed_path).read_text())
    old = {r["n_nodes"]: r["step"]["kernel_vs_lax"]
           for r in committed["scales"]}
    failures = []
    for r in rows:
        n = r["n_nodes"]
        if n not in old:
            continue
        new = r["step"]["kernel_vs_lax"]
        if new < old[n] * (1.0 - margin):
            failures.append(f"n={n}: kernel_vs_lax {new:.3f} < committed "
                            f"{old[n]:.3f} - {margin:.0%}")
        else:
            print(f"  n={n}: kernel_vs_lax {new:.3f} vs committed "
                  f"{old[n]:.3f} — OK")
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--quick", action="store_true",
                    help="small scales only (CI smoke)")
    ap.add_argument("--repeats", type=int, default=5,
                    help="timing repeats (best-of)")
    ap.add_argument("--out", default="BENCH_engine.json")
    ap.add_argument("--check-against", default=None, metavar="JSON",
                    help="compare kernel_vs_lax per scale against a "
                    "committed artifact; fail on regression")
    ap.add_argument("--regress-margin", type=float, default=0.10,
                    help="allowed relative ratio regression (default 10%%)")
    ap.add_argument("--write", action="store_true",
                    help="write --out even in --check-against mode")
    args = ap.parse_args(argv)

    scales = SCALES_QUICK if args.quick else SCALES_FULL
    print(f"engine_bench: lumi scales={scales} "
          f"backend={jax.default_backend()} (pallas interpret="
          f"{jax.default_backend() != 'tpu'})")
    t0 = time.time()
    rows = run_scales(scales, args.repeats)
    result = {
        "schema": 1,
        "system": "lumi",
        "victim_coll": "ring_allreduce",
        "aggressor": "incast",
        "vector_bytes": VECTOR_BYTES,
        "jax_backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "pallas_interpret": jax.default_backend() != "tpu",
        "quick": args.quick,
        "wall_s": round(time.time() - t0, 1),
        "scales": rows,
    }

    bad_parity = [r["n_nodes"] for r in rows if r["parity"] != "OK"]
    failures = []
    if args.check_against:
        failures = check_against(rows, args.check_against,
                                 args.regress_margin)
    if args.write or not args.check_against:
        Path(args.out).write_text(json.dumps(result, indent=1) + "\n")
        print(f"wrote {args.out}")
    if bad_parity:
        print(f"PARITY MISMATCH at scales {bad_parity}", file=sys.stderr)
        return 1
    if failures:
        print("PERF REGRESSION:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        return 1
    print("engine_bench: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
