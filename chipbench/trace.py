"""Reduce a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` the JAX profiler writes: the benchmark's
own host spans (``chipbench.*`` annotations), and per device the ops of
its op line as ``(short name, start, end)`` rows, where the short name is
the HLO instruction's name (``%fabric_step_core.8``), not its text.
``reduce`` turns them into the traced window's length, the device-busy
time (the union of op intervals, averaged over devices), the step core's
time, the host time of each question, and the breakdown: the device ops
with the most self time (a while loop's time less the ops inside it),
and the longest idle gaps named after the innermost harness span that
was open on the host. A device trace whose buffers overflowed marks the
lost interval (``Trace Buffers Dropped``); ``dropped_s`` reports it, and
readers of device time give nothing for such a window.

All times are seconds; inputs are nanoseconds as the trace holds them.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

import numpy as np

DEVICE_PLANE_PREFIX = "/device:"
DEVICE_OP_LINE = "XLA Ops"
DROPPED_EVENT = "Trace Buffers Dropped"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
QUESTION_SPAN = "chipbench.question"

Op = Tuple[int, str, float, float]        # device, name, start_ns, end_ns
Span = Tuple[str, float, float]           # name, start_ns, end_ns


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def short_name(hlo_text: str) -> str:
    return hlo_text.split(" = ", 1)[0]


def load(path: str) -> Tuple[List[Op], List[Span], List[Span]]:
    """(device ops, host spans, dropped intervals) of one trace file."""
    from jax.profiler import ProfileData

    ops: List[Op] = []
    spans: List[Span] = []
    dropped: List[Span] = []
    devices: Dict[str, int] = {}
    for plane in ProfileData.from_file(path).planes:
        is_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if is_device and line.name == DEVICE_OP_LINE:
                dev = devices.setdefault(plane.name, len(devices))
                ops.extend((dev, short_name(e.name), e.start_ns, e.end_ns)
                           for e in line.events)
            elif is_device:
                dropped.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events
                               if e.name == DROPPED_EVENT)
            else:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return ops, spans, dropped


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _covered(merged, lo, hi) -> float:
    return sum(e - s for s, e in _clip(merged, lo, hi))


def _innermost(spans: Sequence[Span], t: float) -> str:
    inside = [(e - s, name) for name, s, e in spans if s <= t <= e]
    return min(inside)[1] if inside else "no harness span"


def _self_times(rows) -> Dict[str, float]:
    """Self time per op name of one device's (name, start, end) rows: an
    op's duration less that of the ops nested inside it."""
    total: Dict[str, float] = collections.defaultdict(float)
    stack: List[list] = []                  # [name, end, child time]
    for name, s, e in sorted(rows, key=lambda r: (r[1], -r[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            total[top[0]] -= top[2]
        if stack:
            stack[-1][2] += e - s
        total[name] += e - s
        stack.append([name, e, 0.0])
    for top in stack:
        total[top[0]] -= top[2]
    return total


def reduce(ops: Sequence[Op], spans: Sequence[Span], kernel_pattern: str,
           dropped: Sequence[Span] = (), top: int = 10) -> dict:
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    lo, hi = windows[0]
    kernel = re.compile(kernel_pattern)
    n_dev = max((op[0] for op in ops), default=-1) + 1
    per_dev_rows = [[(n, max(s, lo), min(e, hi)) for d, n, s, e in ops
                     if d == dev and e > lo and s < hi]
                    for dev in range(n_dev)]
    per_dev = [union([(s, e) for _, s, e in rows]) for rows in per_dev_rows]
    busy = [sum(e - s for s, e in m) for m in per_dev]
    any_dev = union([iv for m in per_dev for iv in m])

    op_time: Dict[str, float] = collections.defaultdict(float)
    for rows in per_dev_rows:
        for name, t in _self_times(rows).items():
            op_time[name] += t
    step_core_ns = sum(t for name, t in op_time.items()
                       if kernel.search(name))

    gaps = []
    for m in per_dev:
        edges = np.array([lo] + [x for iv in m for x in iv] + [hi])
        starts, ends = edges[::2], edges[1::2]
        keep = ends > starts
        gaps.extend(zip(ends[keep] - starts[keep],
                        (starts[keep] + ends[keep]) / 2))
    gaps = sorted(gaps, reverse=True)[:top]
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]

    questions = [(s, e) for name, s, e in spans if name == QUESTION_SPAN]
    host_s = [((e - s) - _covered(any_dev, s, e)) / 1e9 for s, e in questions]
    return {
        "window_s": (hi - lo) / 1e9,
        "devices": n_dev,
        "busy_s": sum(busy) / 1e9 / max(n_dev, 1),
        "step_core_s": step_core_ns / 1e9,
        "dropped_s": _covered(union([(s, e) for _, s, e in dropped]),
                              lo, hi) / 1e9,
        "question_host_s": host_s,
        "device_ops": [[name, t / 1e9] for name, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_innermost(inner, float(mid)), float(g) / 1e9]
                      for g, mid in gaps],
    }


def complete(summary) -> bool:
    """A reduced trace whose device numbers can be read: some device ran
    ops in the window and no trace buffer was dropped there."""
    return bool(summary and summary["devices"] and summary["window_s"] > 0
                and summary["dropped_s"] == 0)
