"""Pass-throughs around the calls the timed path makes into the program.

Installed only for a traced run. Each wraps a program function by its
module attribute, opens a profiler span named after the layer around the
call, and keeps the engine's outputs as they return to ``core/bench.py``
so that per-layer readers can count the steps each lane ran past its
answer. Nothing the program computes is changed.
"""
from __future__ import annotations

import contextlib
import functools

import numpy as np

TDONE_SLOTS = 96


class Tap:
    def __init__(self):
        self.engine_calls = []
        self._undo = []

    def _wrap(self, module, name: str, span: str, keep_output: bool = False):
        import jax

        fn = getattr(module, name)

        @functools.wraps(fn)
        def wrapper(*args, **kw):
            with jax.profiler.TraceAnnotation(span):
                out = fn(*args, **kw)
            if keep_output:
                self.engine_calls.append({"geom": args[0], "params": args[1],
                                          "n_iters": args[2],
                                          "chunk": kw["chunk"], "out": out})
            return out

        setattr(module, name, wrapper)
        self._undo.append((module, name, fn))

    @contextlib.contextmanager
    def installed(self):
        from repro.core import bench
        from repro.core.fabric import simulator

        self._wrap(bench, "build_case", "chipbench.build_case")
        self._wrap(bench, "grid_params", "chipbench.grid_params")
        self._wrap(bench, "run_cells", "chipbench.engine_launch", True)
        self._wrap(simulator, "run_cells_hetero", "chipbench.engine_launch",
                   True)
        self._wrap(bench, "_grid_results", "chipbench.marshal")
        try:
            yield self
        finally:
            for module, name, fn in reversed(self._undo):
                setattr(module, name, fn)
            self._undo.clear()


def lane_steps(call: dict):
    """(steps each lane needed to answer, steps the call executed per
    lane) of one engine call. The engine checks its early exit every
    ``chunk`` steps and a batch steps until its slowest lane exits, so
    every lane executes the batch's largest chunk count."""
    out, chunk = call["out"], int(call["chunk"])
    it = np.asarray(out["it"])[..., 0].ravel()
    t_done = np.asarray(out["t_done"])[..., 0, :].reshape(len(it), -1)
    dt = np.asarray(call["params"].dt).ravel()
    n_iters = int(np.asarray(call["n_iters"]))
    executed = int(np.asarray(out["chunks"]).max()) * chunk
    n_done = np.minimum(np.minimum(it, n_iters), TDONE_SLOTS)
    finish = np.where(
        n_done > 0,
        np.rint(t_done[np.arange(len(it)), np.maximum(n_done - 1, 0)] / dt),
        executed)
    return finish, executed


def real_dims(geom):
    """(flows, hops, links) of each topology cell an engine call ran,
    without the bucket padding: pad flows have a zero path length, pad
    links an infinite capacity, and pad hops no real link."""
    path_len = np.asarray(geom.path_len)
    caps = np.asarray(geom.caps_pad)
    paths = np.asarray(geom.paths)
    if path_len.ndim == 2:
        path_len, caps, paths = path_len[None], caps[None], paths[None]
    dims = []
    for pl, cp, pa in zip(path_len, caps, paths):
        real = pl[:, 0] > 0
        n_links = int(np.isfinite(cp).sum())
        used_hop = (pa[real] < len(cp) - 1).any(axis=(0, 1))
        dims.append((int(real.sum()), int(np.nonzero(used_hop)[0].max()) + 1,
                     n_links))
    return dims


def lane_step_totals(calls):
    """(lane-steps up to each lane's answer, lane-steps executed), summed
    over engine calls."""
    useful = executed = 0.0
    for call in calls:
        finish, steps = lane_steps(call)
        useful += float(finish.sum())
        executed += float(len(finish) * steps)
    return useful, executed
