"""CPU oracle rows for the grid phase of ``chip_smoke.py``.

Runs every grid of the smoke's grid phase (``chip_smoke.grid_specs()``,
its shortened iteration protocol included) on the lax oracle
(``kernels/ref.py``) with XLA:CPU, and writes each row's uncongested and
congested iteration times at full precision to
``artifacts/chip_smoke_cpu_rows.csv``, which the smoke compares its chip
rows with. Rerun it whenever the engine's numerics or the smoke's grids
change (the 256-node CRESCO8 AllToAll grid dominates: minutes on the CPU):

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.smoke_cpu_rows
"""
from __future__ import annotations

import csv
import os
import sys
import time

import jax

import chip_smoke
from repro.core import scenarios
from repro.core.fabric import simulator as sim

FIELDS = ("system", "n_nodes", "aggressor", "vector_bytes", "profile",
          "t_uncongested_us", "t_congested_us")


def main() -> int:
    if jax.default_backend() != "cpu":
        print("run with JAX_PLATFORMS=cpu: these rows are the CPU oracle",
              file=sys.stderr)
        return 1
    sim.set_step_backend("ref")
    rows = []
    for scen, grid in chip_smoke.grid_specs():
        t0 = time.perf_counter()
        for r in scenarios.run_grid_spec(scen, grid):
            rows.append((r.system, r.n_nodes, r.aggressor, r.vector_bytes,
                         r.profile, repr(r.t_uncongested_s * 1e6),
                         repr(r.t_congested_s * 1e6)))
        print(f"{grid.system} n={grid.n_nodes} {grid.aggressor}: "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
    os.makedirs(os.path.dirname(chip_smoke.CPU_ROWS_PATH), exist_ok=True)
    with open(chip_smoke.CPU_ROWS_PATH, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(FIELDS)
        w.writerows(rows)
    print(f"wrote {len(rows)} rows to {chip_smoke.CPU_ROWS_PATH}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
