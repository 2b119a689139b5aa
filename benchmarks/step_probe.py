"""Per-step time of the simulator step on each step backend.

For each cell it jits a ``lax.scan`` of ``simulator.step`` vmapped over
the cell's lanes, compiles it ahead of time, runs it once, then times a
second call (``block_until_ready``) and reports milliseconds per step for
the fused kernel (``pallas``) and the lax oracle (``ref``). Cells:

* ``engine:<n>`` — the LUMI-family engine-bench geometry at ``n`` nodes
  (``benchmarks/engine_bench.py``): one lane, four at 256 nodes;
* ``grid:<system>:<aggressor>:<n>`` — one ``fig5_steady`` quick grid's
  four lanes (two sizes x baseline/steady), built as ``bench.run_grid``
  builds them.

Times are host-clock; the ``device`` record says where they ran (on a
CPU backend the kernel runs in the Pallas interpreter).

Usage:
  PYTHONPATH=src python -m benchmarks.step_probe
  PYTHONPATH=src python -m benchmarks.step_probe --cells engine:16 \\
      --steps 4 --out /tmp/probe.json
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from benchmarks import engine_bench
from repro.core import bench, scenarios
from repro.core.fabric import simulator as sim, systems

DEFAULT_CELLS = ("engine:64", "engine:256", "engine:4096",
                 "grid:cresco8:alltoall:256", "grid:leonardo:alltoall:256",
                 "grid:lumi:alltoall:256")
ENGINE_LANES = {256: 4}
# scan length: about 1e10 flow x link x hop x lane visits of the dense
# one-hot kernel per timed call, within [8, 256] steps
WORK_PER_CALL = 1e10


def _engine_cell(n):
    geom, params, _ = engine_bench._build(systems.get_system("lumi"), n)
    return geom, sim.stack_params([params] * ENGINE_LANES.get(n, 1))


def _grid_cell(system, aggressor, n):
    fig5 = scenarios.get("fig5_steady", quick=True)
    grid = next(g for g in fig5.grids if (g.system, g.aggressor, g.n_nodes)
                == (system, aggressor, n))
    case = bench.build_case(systems.get_system(system), n, grid.victim,
                            grid.aggressor)
    return case.geom, bench.grid_params(case, grid.sizes, grid.profiles)[1]


def build(cell: str):
    kind, *rest = cell.split(":")
    if kind == "engine":
        return _engine_cell(int(rest[0]))
    if kind == "grid":
        return _grid_cell(rest[0], rest[1], int(rest[2]))
    raise ValueError(f"unknown cell {cell!r}")


def measure(geom, params, backend: str, n_steps: int) -> dict:
    def one(p, s):
        return jax.lax.scan(
            lambda s, _: sim.step(geom, p, s, backend=backend),
            s, None, length=n_steps)

    run = jax.jit(jax.vmap(one))
    state = jax.vmap(lambda p: sim.init_state(geom, p))(params)
    t0 = time.perf_counter()
    compiled = run.lower(params, state).compile()
    t_compile = time.perf_counter() - t0
    jax.block_until_ready(compiled(params, state))
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(params, state))
    t_run = time.perf_counter() - t0
    return {"backend": backend, "steps": n_steps, "compile_s": t_compile,
            "step_ms": t_run / n_steps * 1e3}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cells", nargs="+", default=list(DEFAULT_CELLS))
    ap.add_argument("--steps", type=int, default=None,
                    help="scan length (default: sized from the cell's work)")
    ap.add_argument("--out", default=None, help="write the rows as JSON")
    args = ap.parse_args(argv)

    d = jax.devices()[0]
    device = {"jax_backend": jax.default_backend(),
              "device_kind": d.device_kind, "device_count": len(jax.devices())}
    print(json.dumps(device), flush=True)
    rows = []
    for cell in args.cells:
        geom, params = build(cell)
        dims = sim.geometry_dims(geom)
        lanes = int(np.shape(params.dt)[0])
        work = (dims.n_flows * (dims.n_links + 1) * dims.max_hops * lanes)
        n_steps = args.steps or int(np.clip(WORK_PER_CALL / work, 8, 256))
        for backend in ("pallas", "ref"):
            row = {"cell": cell, "F": dims.n_flows, "L": dims.n_links,
                   "H": dims.max_hops, "lanes": lanes,
                   **measure(geom, params, backend, n_steps)}
            print(json.dumps(row), flush=True)
            rows.append(row)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"device": device, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
