"""Run one benchmark cell on the machine this process starts on.

    python -m chipbench.run --workload leonardo.incast-256 --seed 7 \\
        --seconds 30 --trace 0

Set-up (process start to the first timed question) loads the program,
warms the cell's shapes with a one-iteration question and fills the
program's topology cache as a user's first grid does. The window then
asks the traffic mix's questions back to back, one closed-loop client,
until ``--seconds`` have passed; the question in flight runs to its
end. With ``--trace 1`` the window runs under the profiler with the
benchmark's spans and pass-throughs installed, closes after
``TRACE_SECONDS``, and the line carries the per-layer metrics instead of
the end-to-end ones. After the window a
seed-drawn sample of the answered questions is compared with the plain
reference (``chipbench/reference.py``).

The last line of standard output is one JSON object; the compared
numbers and their limits close standard error. With no TPU, or fewer
chips than the cell asks for, the run exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from chipbench import check, questions, roofline, spec  # noqa: E402
from chipbench import trace as trace_lib  # noqa: E402

# the engine protocol of a grid as scenarios.run_grid_spec runs it
# (bench.run_grid's defaults): the early exit is checked every CHUNK steps
CHUNK, MAX_STEPS = 2048, 200_000
# A traced run's window closes with the first answer past this many
# seconds: on a TPU v5 lite a second of device trace holds 0.1-1 million
# op events, and the device drops trace buffers past about six million.
TRACE_SECONDS = 3.0


class NoChip(RuntimeError):
    pass


def devices_for(chips: int):
    """The first ``chips`` TPU devices; no fallback to another platform."""
    import jax

    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax found {devs[0].platform if devs else 'no'}"
                     " devices")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, jax found {len(devs)}")
    return devs[:chips]


@dataclasses.dataclass
class Run:
    """What a run saw; per-layer readers take their numbers from it."""
    cell: dict
    answers: list
    setup_compile: dict
    device_kind: str
    trace: dict = None
    tap: object = None


def ask(cell: dict, sizes, *, n_iters: int, warmup: int, mesh=None):
    """One question: the traffic's grid at ``sizes``, driven through
    ``bench.run_grid`` as ``scenarios.run_grid_spec`` calls it."""
    from repro.core import bench
    from repro.core import congestion as cong
    from repro.core.fabric import systems

    tr, cfg = cell["traffic"], cell["config"]
    program = spec.profile(tr["profile"], cell["base"]).PROGRAM
    profiles = (getattr(cong, program)(**tr.get("profile_args", {})),)
    if len(tr["nodes"]) == 1 and mesh is None:
        system = systems.get_system(cfg["preset"])
        return bench.run_grid(system, tr["nodes"][0], tr["victim"],
                              tr["aggressor"], sizes, profiles,
                              n_iters=n_iters, warmup=warmup)
    cells = [(cfg["preset"], n) for n in tr["nodes"]]
    return bench.run_grid(cells, 0, tr["victim"], tr["aggressor"], sizes,
                          profiles, n_iters=n_iters, warmup=warmup, mesh=mesh)


def window(cell: dict, qs: questions.Questions, seconds: float, mesh):
    import jax

    cfg = cell["config"]
    answers = []
    with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
        t0 = time.perf_counter()
        while not answers or answers[-1].answered - t0 < seconds:
            k = len(answers)
            sizes = qs.sizes(k)
            with jax.profiler.TraceAnnotation(trace_lib.QUESTION_SPAN):
                sub = time.perf_counter()
                res = ask(cell, sizes, n_iters=cfg["n_iters"],
                          warmup=cfg["warmup"], mesh=mesh)
                answers.append(questions.Answer(k, sizes, sub,
                                                time.perf_counter(), res))
    return answers


def reference_check(cell: dict, answers, seed: int, devices) -> dict:
    """Compare a seed-drawn sample of the answered questions with the
    plain reference; returns the worst reading of each number."""
    from chipbench import reference

    tr, cfg = cell["traffic"], cell["config"]
    readings = []
    for i in questions.check_sample(seed, len(answers),
                                    int(tr["check"]["questions"])):
        rows = reference.answer(cfg, tr["nodes"], tr["aggressor"],
                                answers[i].sizes, n_iters=cfg["n_iters"],
                                warmup=cfg["warmup"], chunk=CHUNK,
                                max_steps=MAX_STEPS, profile=tr["profile"],
                                profile_args=tr.get("profile_args"),
                                devices=list(devices), base=cell["base"])
        readings.append(check.compare(answers[i].results, rows))
    return check.worst(readings)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def traced_window(cell: dict, qs, seconds: float, mesh, run: Run) -> None:
    """The window under the profiler, with the pass-throughs installed;
    fills ``run.answers``, ``run.tap`` and the reduced ``run.trace``."""
    import jax

    from chipbench.tap import Tap

    run.tap = Tap()
    log_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
        try:
            with run.tap.installed():
                run.answers = window(cell, qs, seconds, mesh)
        finally:
            jax.profiler.stop_trace()
        ops, spans, dropped = trace_lib.load(trace_lib.find_xplane(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    run.trace = trace_lib.reduce(ops, spans,
                                 roofline.step_core_event_pattern(), dropped)
    print(f"trace_dropped_s {run.trace['dropped_s']!r}", file=sys.stderr)


def execute(cell: dict, *, seed: int, seconds: float, traced: bool,
            devices, meter) -> dict:
    """Set-up, window and check of one run; returns the result line."""
    import jax
    import numpy as np

    from repro.core.fabric import simulator

    simulator.ensure_compile_cache()
    qs = questions.Questions(cell["traffic"], seed)
    mesh = None
    if cell["traffic"].get("mesh"):
        mesh = jax.sharding.Mesh(np.array(devices), ("cell",))
    ask(cell, qs.warmup_sizes(), n_iters=1, warmup=0, mesh=mesh)
    setup_s = time.perf_counter() - _T_START
    run = Run(cell=cell, answers=[], setup_compile=meter.snapshot(),
              device_kind=devices[0].device_kind)
    if traced:
        traced_window(cell, qs, min(seconds, TRACE_SECONDS), mesh, run)
    else:
        run.answers = window(cell, qs, seconds, mesh)
    window_compiles = meter.snapshot()["count"] - run.setup_compile["count"]
    print(f"window_compiles {window_compiles}", file=sys.stderr, flush=True)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak(devices)}
    line = {"correct": False, "attempted": len(run.answers),
            "failed": sum(questions.failed(a) for a in run.answers)}
    if traced:
        device.update(busy_s=run.trace["busy_s"],
                      window_s=run.trace["window_s"])
        metrics = {}
        for m in cell["per_layer"]:
            value = spec.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        ends = {"sim_us_per_s": questions.sim_us_per_s(run.answers),
                "setup_s": setup_s}
        metrics = {m["name"]: {"value": ends[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}

    limits = cell["traffic"]["check"]["limits"]
    readings = reference_check(cell, run.answers, seed, devices)
    line.update(correct=check.verdict(readings, limits), metrics=metrics,
                device=device)
    if traced:
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    line["checks"] = check.as_json(readings, limits)
    for text in check.lines(readings, limits):
        print(text, file=sys.stderr)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the program and its compile cache live beside the benchmark: the
    # cache at one fixed path inside the checkout, so only a checkout's
    # first run of a cell compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(spec.ROOT,
                                                           ".jax_cache")
    # the TPU runtime's logs go under the run's own temporary directory,
    # not to a fixed path shared with other runs
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(),
                                                      "tpu_logs"))
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    cell = spec.cell(args.workload)
    try:
        devices = devices_for(int(cell["workload"]["chips"]))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    from chipbench.meter import CompileMeter

    meter = CompileMeter()
    line = execute(cell, seed=args.seed, seconds=args.seconds,
                   traced=bool(args.trace), devices=devices, meter=meter)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
