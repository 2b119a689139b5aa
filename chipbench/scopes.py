"""Device time by the program's named scopes, host time by its spans.

The engine names the sections of its step with ``jax.named_scope``
(``core/fabric/simulator.py``: ``engine_loop``, ``fabric_step`` and the
sections below), and the grid path its host phases with ``fabric.*``
spans (``core/spans.py``). A TPU v5 lite trace names each op by its HLO
instruction alone (``%fusion.122 = ...``, no ``op_name``), so
``op_scopes`` reads every instruction's scope from the compiled text of
the engine that ran, lowered again with the arguments the grid path
passed it, and an op counts as the engine's only while an engine module
(``XLA Modules`` line) runs on its device: instruction names repeat
across modules. An engine op whose name that text lacks means the text
is not the module that ran, and the device readers then read nothing.

The harness's traced window keeps its ten longest ops and its own spans
(``chipbench/trace.py``), so ``sample(run)`` asks one more question
after the window under the profiler: the mix's warm-up question (the
smallest size in every lane, one victim iteration). Its steps have the
window's shapes, so the same device time per lane-step, and its
experiment is built as the window's are. A full collection of Python's
garbage collector runs before it, so its host phases read without one
(a window's questions take one every few questions; ``gc_s`` says what
ran inside the sample). Readers share one sample per run. A program
without the scopes or the spans gives no sample.

All times are seconds; the trace's are nanoseconds.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import gc
import re
import shutil
import sys
import tempfile
import traceback
import time
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace as trace_lib

ENGINE_SCOPE = "engine_loop"
SECTIONS = ("envelope", "route", "step_core", "signals", "cc", "progress",
            "queue_delay", "metrics_carry")
DOCUMENTED = (ENGINE_SCOPE,) + SECTIONS
ROUTE_SIGNAL = ("route", "signals", "queue_delay")
CC_PHASE = ("envelope", "cc", "progress")
SPAN_PREFIX = "fabric."
BUILD_SPAN = "fabric.build_case"
PARAMS_SPAN = "fabric.grid_params"
SHARD_SPAN = "fabric.shard"
SAMPLE_SPAN = "chipbench.scope_sample"
MODULE_LINE = "XLA Modules"
ENGINE_MODULE = re.compile(r"^jit__run_cells(_hetero)?_jit\(")
UNSCOPED = "(no scope)"
NOT_IN_TEXT = "(not in the engine's text)"
# the compiled entries of simulator.run_cells and run_cells_hetero
ENGINE_ENTRIES = ("_run_cells_jit", "_run_cells_hetero_jit")

Module = Tuple[int, float, float]                # device, start, end


def scope_of(op_name: str) -> Optional[str]:
    """The innermost documented scope on an ``op_name`` path; transform
    wrappers (``vmap(engine_loop)``) are taken off each component."""
    found = None
    for part in op_name.split("/"):
        while True:
            m = re.fullmatch(r"[\w.]+\((.*)\)", part)
            if not m:
                break
            part = m.group(1)
        if part in DOCUMENTED:
            found = part
    return found


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """Short instruction name -> its documented scope, or ``UNSCOPED``,
    for every instruction of a compiled module's text that can run as an
    op (instructions of fused computations and of scalar reducers run
    inside another one)."""
    scopes, runs = {}, True
    for line in hlo_text.splitlines():
        if line and not line.startswith(" ") and line.endswith("{"):
            runs = not (line.startswith("%fused_computation") or re.match(
                r"\S+ \((\S+: \w+\[\], )*\S+: \w+\[\]\) -> ", line))
            continue
        if not runs:
            continue
        m = re.match(r'\s+(?:ROOT )?(%\S+) = ', line)
        if m:
            meta = re.search(r'op_name="([^"]*)"', line)
            scopes[m.group(1)] = (meta and scope_of(meta.group(1))) \
                or UNSCOPED
    return scopes


def load(path: str):
    """(device ops, engine module intervals, host spans, dropped
    intervals, shard spans) of one trace file. Device numbers are the
    ordinals the plane names give (``/device:TPU:2`` -> 2), and for a
    shard span the ``device`` argument it was opened with (the JAX
    device id, the same number on one host)."""
    from jax.profiler import ProfileData

    ops: List[trace_lib.Op] = []
    modules: List[Module] = []
    spans: List[trace_lib.Span] = []
    dropped: List[trace_lib.Span] = []
    shards: List[Module] = []
    for plane in ProfileData.from_file(path).planes:
        ordinal = re.search(r":(\d+)$", plane.name)
        if plane.name.startswith(trace_lib.DEVICE_PLANE_PREFIX):
            if not ordinal:          # not a chip (``/device:CUSTOM:...``)
                continue
            dev = int(ordinal.group(1))
            for line in plane.lines:
                if line.name == trace_lib.DEVICE_OP_LINE:
                    ops.extend((dev, trace_lib.short_name(e.name),
                                e.start_ns, e.end_ns) for e in line.events)
                elif line.name == MODULE_LINE:
                    modules.extend((dev, e.start_ns, e.end_ns)
                                   for e in line.events
                                   if ENGINE_MODULE.match(e.name))
                else:
                    dropped.extend((e.name, e.start_ns, e.end_ns)
                                   for e in line.events
                                   if e.name == trace_lib.DROPPED_EVENT)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX) or e.name == SAMPLE_SPAN:
                    spans.append((e.name, e.start_ns, e.end_ns))
                device = dict(e.stats).get("device") \
                    if e.name == SHARD_SPAN else None
                if device is not None:
                    shards.append((int(device), e.start_ns, e.end_ns))
    return ops, modules, spans, dropped, shards


def _in_engine(modules: Sequence[Module], dev: int):
    """A test of whether a time lies inside an engine module on ``dev``."""
    iv = sorted((s, e) for d, s, e in modules if d == dev)
    starts = [s for s, _ in iv]

    def inside(t: float) -> bool:
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < iv[i][1]

    return inside


def reduce(ops: Sequence[trace_lib.Op], modules: Sequence[Module],
           spans: Sequence[trace_lib.Span], scopes: Dict[str, str],
           dropped: Sequence[trace_lib.Span] = (),
           shards: Sequence[Module] = ()) -> dict:
    """Self time per scope and idle time per span, inside the sample's
    question span (or the whole trace where there is none).

    ``scope_s`` maps each documented scope, ``UNSCOPED`` (engine ops the
    compiled text names no scope for), ``NOT_IN_TEXT`` (engine ops the
    text lacks; ``unknown_ops`` counts them) and ``"other modules"`` (ops
    of other programs) to device self time summed over devices;
    ``span_idle_s`` maps each span name to the time inside its spans in
    which no device ran an op; ``host_s`` is the question's time with no
    device busy, ``host_in_spans_s`` the part of it inside a
    ``fabric.*`` span. ``shard_idle_s`` gives each device's idle time
    and the part of it inside another device's ``fabric.shard`` span
    (time the device waits while the host dispatches another shard)."""
    frame = [(s, e) for n, s, e in spans if n == SAMPLE_SPAN]
    lo, hi = frame[0] if frame else (
        min((s for _, _, s, _ in ops), default=0.0),
        max((e for _, _, _, e in ops), default=0.0))
    devices = sorted({op[0] for op in ops})
    scope_ns: Dict[str, float] = collections.defaultdict(float)
    per_dev = {}
    unknown = 0
    for dev in devices:
        inside = _in_engine(modules, dev)
        rows = []
        for d, name, s, e in ops:
            if d != dev or e <= lo or s >= hi:
                continue
            tag = (scopes.get(name, NOT_IN_TEXT) if inside(s)
                   else "other modules")
            unknown += tag == NOT_IN_TEXT
            rows.append((tag, max(s, lo), min(e, hi)))
        for tag, t in trace_lib._self_times(rows).items():
            scope_ns[tag] += t
        per_dev[dev] = trace_lib.union([(s, e) for _, s, e in rows])
    busy_any = trace_lib.union([iv for m in per_dev.values() for iv in m])

    def idle(s, e, busy):
        s, e = max(s, lo), min(e, hi)
        return max(e - s, 0.0) - trace_lib._covered(busy, s, e)

    span_idle: Dict[str, float] = collections.defaultdict(float)
    fabric = [sp for sp in spans if sp[0].startswith(SPAN_PREFIX)]
    for name, s, e in fabric:
        if name != "fabric.shard":   # shards lie inside the dispatch span
            span_idle[name] += idle(s, e, busy_any)
    covered = trace_lib.union([(s, e) for _, s, e in fabric])
    host_in_spans = sum(idle(s, e, busy_any) for s, e in covered)
    shard_idle = {}
    for dev in devices:
        others = trace_lib.union([(s, e) for d, s, e in shards if d != dev])
        shard_idle[dev] = [idle(lo, hi, per_dev[dev]) / 1e9,
                           sum(idle(s, e, per_dev[dev])
                               for s, e in others) / 1e9]
    return {
        "window_s": (hi - lo) / 1e9,
        "devices": len(devices),
        "unknown_ops": unknown,
        "scope_s": {k: v / 1e9 for k, v in scope_ns.items()},
        "span_idle_s": {k: v / 1e9 for k, v in span_idle.items()},
        "host_s": idle(lo, hi, busy_any) / 1e9,
        "host_in_spans_s": host_in_spans / 1e9,
        "shard_idle_s": shard_idle,
        "dropped_s": trace_lib._covered(
            trace_lib.union([(s, e) for _, s, e in dropped]), lo, hi) / 1e9,
    }


def scoped_share(summary: dict) -> Optional[float]:
    """Share of the engine's device self time under a documented scope,
    or None where no op of the engine was found or the engine's text
    lacks some op's name."""
    t = summary["scope_s"]
    engine = sum(v for k, v in t.items() if k != "other modules")
    if not engine or summary["unknown_ops"]:
        return None
    return (engine - t.get(UNSCOPED, 0.0)) / engine


def _program_names_scopes() -> bool:
    from repro.core.fabric import simulator

    try:
        import repro.core.spans  # noqa: F401
    except ImportError:
        return False
    return getattr(simulator, "ENGINE_SCOPE", None) == ENGINE_SCOPE


@contextlib.contextmanager
def engine_calls():
    """Keep every call of the engine's compiled entries made inside the
    block as ``(entry, args, kwargs, out)``; nothing is changed."""
    from repro.core.fabric import simulator

    calls, undo = [], []
    for name in ENGINE_ENTRIES:
        entry = getattr(simulator, name)

        def call(*args, _entry=entry, **kw):
            out = _entry(*args, **kw)
            calls.append((_entry, args, kw, out))
            return out

        setattr(simulator, name, call)
        undo.append((name, entry))
    try:
        yield calls
    finally:
        for name, entry in undo:
            setattr(simulator, name, entry)


def engine_text(call) -> str:
    """The compiled text of the engine one kept call ran: the entry
    lowered again with that call's own arguments (a compile-cache hit)."""
    entry, args, kw, _ = call
    return entry.lower(*args, **kw).compile().as_text()


def engine_scopes(calls) -> Optional[Dict[str, str]]:
    """``op_scopes`` of the engine the kept calls ran, or None where
    they ran more than one compiled text (the shards of one grid share
    one)."""
    import jax

    texts = {}
    for call in calls:
        entry, args, kw, _ = call
        key = (entry, tuple((x.shape, str(x.dtype))
                            for x in jax.tree_util.tree_leaves(args)),
               tuple(sorted(kw.items())))
        if key not in texts:
            texts[key] = engine_text(call)
    if len(set(texts.values())) != 1:
        return None
    return op_scopes(next(iter(texts.values())))


def _lane_step_calls(calls):
    """The kept calls in the form ``tap.lane_steps`` reads."""
    return [{"geom": args[0], "params": args[1], "n_iters": args[2],
             "chunk": kw["chunk"], "out": out}
            for _, args, kw, out in calls]


def sample(run) -> Optional[dict]:
    """The reduced trace of one warm-up question asked after the window,
    with the lane-steps it executed (``lane_steps``); None where the
    program names no scopes or the trace lost buffers. Taken once per
    run and kept on it for the other readers."""
    if not hasattr(run, "scope_sample"):
        run.scope_sample = None
        if _program_names_scopes():
            try:
                run.scope_sample = _take_sample(run)
            except Exception:        # a reader must not end the run
                traceback.print_exc(file=sys.stderr)
    return run.scope_sample


def _take_sample(run) -> Optional[dict]:
    import jax
    import numpy as np

    from chipbench.questions import Questions
    from chipbench.run import ask, devices_for
    from chipbench.tap import lane_step_totals

    cell = run.cell
    mesh = None
    if cell["traffic"].get("mesh"):
        devices = devices_for(int(cell["workload"]["chips"]))
        mesh = jax.sharding.Mesh(np.array(devices), ("cell",))
    log_dir = tempfile.mkdtemp(prefix="chipbench_scopes_")
    paused, started = [], []

    def collector(phase, _info):
        if phase == "start":
            started.append(time.perf_counter())
        elif started:
            paused.append(time.perf_counter() - started.pop())

    gc.collect()
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
        try:
            with engine_calls() as calls, \
                    jax.profiler.TraceAnnotation(SAMPLE_SPAN):
                gc.callbacks.append(collector)
                try:
                    ask(cell, Questions(cell["traffic"], 0).warmup_sizes(),
                        n_iters=1, warmup=0, mesh=mesh)
                finally:
                    gc.callbacks.remove(collector)
        finally:
            jax.profiler.stop_trace()
        ops, modules, spans, dropped, shards = load(
            trace_lib.find_xplane(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    scopes = engine_scopes(calls)
    summary = reduce(ops, modules, spans, scopes or {}, dropped, shards)
    if scopes is None:
        summary["unknown_ops"] = max(summary["unknown_ops"], 1)
    _, summary["lane_steps"] = lane_step_totals(_lane_step_calls(calls))
    summary["questions"] = 1
    summary["gc_s"] = sum(paused)
    print(f"scope_sample {_describe(summary)}", file=sys.stderr)
    return summary if summary["dropped_s"] == 0 else None


def _describe(s: dict) -> str:
    share = scoped_share(s)
    host = s["host_in_spans_s"] / s["host_s"] if s["host_s"] else None
    scope_us = {k: round(1e6 * v / s["lane_steps"], 3) if s["lane_steps"]
                else None for k, v in sorted(s["scope_s"].items())}
    return (f"window_s {s['window_s']!r} lane_steps {s['lane_steps']!r} "
            f"scoped_share {share!r} unknown_ops {s['unknown_ops']!r} "
            f"host_s {s['host_s']!r} gc_s {s['gc_s']!r} "
            f"host_in_spans_share {host!r} "
            f"us_per_lane_step {scope_us} "
            f"span_idle_s {s['span_idle_s']} "
            f"shard_idle_s {s['shard_idle_s']} dropped_s {s['dropped_s']!r}")


def per_lane_step_us(run, scopes: Sequence[str]) -> Optional[float]:
    """Device self time under ``scopes`` per lane-step the sample's
    engine executed, in microseconds."""
    s = sample(run)
    if not s or not s["lane_steps"] or s["unknown_ops"]:
        return None
    if not any(k in DOCUMENTED for k in s["scope_s"]):
        return None
    return 1e6 * sum(s["scope_s"].get(k, 0.0) for k in scopes) \
        / s["lane_steps"]


def idle_ms_per_answer(run, span: str) -> Optional[float]:
    """Device-idle time inside ``span`` per answered question, in
    milliseconds."""
    s = sample(run)
    if not s or span not in s["span_idle_s"]:
        return None
    return 1e3 * s["span_idle_s"][span] / s["questions"]
