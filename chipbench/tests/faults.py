"""Drive a benchmark run on the CPU with the timed path broken underneath.

    JAX_PLATFORMS=cpu python -m chipbench.tests.faults

Skips the harness's look for a chip and runs set-up, window and check of
a small leonardo incast cell (16 and 32 nodes) once sound and once under
each fault the cells can have, printing ``{scenario: correct}`` as one
JSON line. The mesh scenarios need two devices; the module asks the CPU
for them before jax starts.
"""
from __future__ import annotations

import contextlib
import json
import sys

from repro.jax_compat import force_host_device_count

force_host_device_count(2)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import run, spec  # noqa: E402
from chipbench.meter import CompileMeter  # noqa: E402

SEED = 2 ** 31 + 77


def small_cell(nodes, mesh: bool) -> dict:
    cell = spec.cell("leonardo.incast-256")
    tr = dict(cell["traffic"], nodes=list(nodes), mesh=mesh)
    tr["check"] = dict(tr["check"], questions=2)
    return dict(cell, traffic=tr)


@contextlib.contextmanager
def patched(module, name, make):
    real = getattr(module, name)
    setattr(module, name, make(real))
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(module, name, real)
        jax.clear_caches()


def _unchanged_step(real):
    def step(geom, p, state, with_aux, backend="ref"):
        out = real(geom, p, state, with_aux, backend)
        return (state,) + tuple(out[1:])
    return step


def _half_batch(real):
    """Half of the lanes are computed; the other half are given the
    computed half's outputs."""
    def run_cells(geom, params, n_iters, **kw):
        n = int(np.asarray(params.dt).shape[0])
        keep = jax.tree_util.tree_map(lambda x: x[: n // 2], params)
        out = real(geom, keep, n_iters, **kw)
        return {k: np.concatenate([np.asarray(v)] * 2)[:n]
                for k, v in out.items()}
    return run_cells


def _altered_answer(real):
    def run_cells(geom, params, n_iters, **kw):
        out = dict(real(geom, params, n_iters, **kw))
        out["t_done"] = np.asarray(out["t_done"]) * np.float32(1.01)
        return out
    return run_cells


def _no_exchange(real):
    """Every device's shard is answered with the first device's outputs."""
    from repro.launch import sweep

    def dispatch(geoms, params, n_iters, **kw):
        out = real(geoms, params, n_iters, **kw)
        return sweep.ShardedOut([out._outs[0]] * len(out._outs), out._axis)
    return dispatch


def scenario(cell) -> bool:
    line = run.execute(cell, seed=SEED, seconds=0.5, traced=False,
                       devices=jax.devices()[:2 if cell["traffic"]["mesh"]
                                             else 1],
                       meter=CompileMeter())
    return bool(line["correct"])


def main() -> int:
    from repro.core import bench
    from repro.core.fabric import simulator
    from repro.launch import sweep

    one, two = small_cell([32], False), small_cell([16, 32], True)
    out = {"sound": scenario(one), "sound_mesh": scenario(two)}
    with patched(simulator, "_step_impl", _unchanged_step):
        out["state_unchanged"] = scenario(one)
    with patched(bench, "run_cells", _half_batch):
        out["half_batch"] = scenario(one)
    with patched(bench, "run_cells", _altered_answer):
        out["altered_answer"] = scenario(one)
    with patched(sweep, "dispatch_hetero", _no_exchange):
        out["no_exchange"] = scenario(two)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
