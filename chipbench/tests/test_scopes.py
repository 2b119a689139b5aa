"""Attribution of device time to the engine's named scopes and of host
time to the program's spans (chipbench/scopes.py): on synthetic events,
on a recorded chip trace, and through a warm-up sample on the CPU."""
from __future__ import annotations

import gzip
import json
import os

import pytest

from chipbench import scopes, spec

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "leonardo_scopes_trace.json.gz")
MS = 1e6  # ns
ENGINE_TEXT = """HloModule jit__run_cells_jit

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %neg.9 = f32[4]{0} negate(f32[4]{0} %param_0), metadata={op_name="jit(_run_cells_jit)/vmap(engine_loop)/while/body/while/body/closed_call/fabric_step/cc/neg"}
}

%region_1.5 (reduce_sum.1: f32[], reduce_sum.2: f32[]) -> f32[] {
  ROOT %add.3 = f32[] add(f32[] %reduce_sum.1, f32[] %reduce_sum.2), metadata={op_name="fabric_step/route/reduce_sum"}
}

%body (p: (f32[4])) -> (f32[4]) {
  %fusion.122 = pred[8]{0} fusion(f32[4]{0} %x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(_run_cells_jit)/vmap(engine_loop)/while/body/while/body/closed_call/fabric_step/signals/gather"}
  %fabric_step_core.8 = (f32[4]{0}) custom-call(f32[4]{0} %x), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(_run_cells_jit)/vmap(engine_loop)/while/body/while/body/closed_call/fabric_step/step_core/jit(fabric_step_core)/pallas_call"}
  %copy.5 = f32[4]{0} copy(f32[4]{0} %x)
  ROOT %while.2 = (f32[4]) while((f32[4]) %t), condition=%c, body=%b, metadata={op_name="jit(_run_cells_jit)/vmap(engine_loop)/while"}
}
"""


def test_scope_of_takes_the_innermost_documented_scope():
    assert scopes.scope_of(
        "jit(f)/vmap(vmap(engine_loop))/while/body/closed_call/fabric_step/"
        "route/jit(take_along_axis)/gather") == "route"
    assert scopes.scope_of("jit(f)/vmap(engine_loop)/while") == "engine_loop"
    assert scopes.scope_of("jit(f)/fabric_step/step_core/jit(fabric_step_"
                           "core)/pallas_call") == "step_core"
    assert scopes.scope_of("jit(convert_element_type)/convert") is None


def test_op_scopes_reads_the_ops_that_run():
    got = scopes.op_scopes(ENGINE_TEXT)
    # fused and reducer computations run inside another op; an op without
    # metadata has no scope
    assert got == {"%fusion.122": "signals",
                   "%fabric_step_core.8": "step_core",
                   "%copy.5": scopes.UNSCOPED,
                   "%while.2": "engine_loop"}


def _synthetic():
    """Two devices, each running the engine launched in its own shard
    span, and the host spans of one question."""
    spans = [(scopes.SAMPLE_SPAN, 0, 100 * MS),
             ("fabric.build_case", 0, 10 * MS),
             ("fabric.grid_params", 10 * MS, 20 * MS),
             ("fabric.dispatch", 20 * MS, 40 * MS),
             ("fabric.shard", 20 * MS, 30 * MS),
             ("fabric.shard", 30 * MS, 40 * MS),
             ("fabric.marshal", 40 * MS, 95 * MS)]
    modules = [(0, 25 * MS, 60 * MS), (1, 35 * MS, 90 * MS)]
    ops = [(0, "%convert.1", 12 * MS, 14 * MS),         # another module
           (0, "%while.2", 25 * MS, 60 * MS),
           (0, "%fusion.122", 30 * MS, 40 * MS),        # nested in the loop
           (0, "%fabric_step_core.8", 40 * MS, 50 * MS),
           (0, "%copy.5", 50 * MS, 55 * MS),            # no scope
           (1, "%while.2", 35 * MS, 90 * MS),
           (1, "%fabric_step_core.8", 40 * MS, 80 * MS)]
    return ops, modules, spans, scopes.op_scopes(ENGINE_TEXT)


def test_reduce_attributes_ops_and_idle_time():
    got = scopes.reduce(*_synthetic())
    assert got["window_s"] == pytest.approx(0.1)
    assert got["devices"] == 2
    assert got["unknown_ops"] == 0
    t = got["scope_s"]
    assert t["other modules"] == pytest.approx(0.002)
    assert t["signals"] == pytest.approx(0.01)
    assert t["step_core"] == pytest.approx(0.01 + 0.04)
    assert t[scopes.UNSCOPED] == pytest.approx(0.005)
    # each loop's self time: device 0's 35 ms less 25 ms inside, device
    # 1's 55 ms less 40 ms
    assert t["engine_loop"] == pytest.approx(0.01 + 0.015)
    assert scopes.scoped_share(got) == pytest.approx(0.085 / 0.09)
    # busy on some device: 12-14 and 25-90 ms
    idle = got["span_idle_s"]
    assert idle["fabric.build_case"] == pytest.approx(0.010)
    assert idle["fabric.grid_params"] == pytest.approx(0.008)
    assert idle["fabric.dispatch"] == pytest.approx(0.005)
    assert idle["fabric.marshal"] == pytest.approx(0.005)
    assert "fabric.shard" not in idle
    assert got["host_s"] == pytest.approx(0.1 - 0.002 - 0.065)
    assert got["host_in_spans_s"] == pytest.approx(got["host_s"] - 0.005)


def test_reduce_splits_each_chips_idle_time_by_other_shards():
    """Device 1 waits through device 0's shard span (dispatched first);
    device 0 is busy through device 1's."""
    ops, modules, spans, scope_map = _synthetic()
    shards = [(0, 20 * MS, 30 * MS), (1, 30 * MS, 40 * MS)]
    got = scopes.reduce(ops, modules, spans, scope_map, (), shards)
    (idle0, in_other0), (idle1, in_other1) = (got["shard_idle_s"][0],
                                              got["shard_idle_s"][1])
    # device 0 busy 12-14 and 25-60 ms, device 1 35-90 ms
    assert (idle0, in_other0) == (pytest.approx(0.063), 0.0)
    assert (idle1, in_other1) == (pytest.approx(0.045), pytest.approx(0.01))
    assert scopes.reduce(ops, modules, spans, scope_map)["shard_idle_s"][1] \
        == [pytest.approx(0.045), 0.0]


def test_reduce_without_scopes_names_no_scope():
    ops, modules, spans, _ = _synthetic()
    got = scopes.reduce(ops, modules, spans, {})
    assert set(got["scope_s"]) == {"other modules", scopes.NOT_IN_TEXT}
    assert got["unknown_ops"] == 6
    assert scopes.scoped_share(got) is None


def test_an_op_the_text_lacks_silences_the_device_readers():
    """A text that is not the module that ran (here one instruction
    short) gives no device number; the host spans still read."""
    ops, modules, spans, scope_map = _synthetic()
    del scope_map["%copy.5"]
    run = _Run()
    run.scope_sample = dict(scopes.reduce(ops, modules, spans, scope_map),
                            lane_steps=1000.0, questions=1)
    assert run.scope_sample["unknown_ops"] == 1
    assert scopes.scoped_share(run.scope_sample) is None
    assert spec.metric_reader("route_signal_us_per_lane_step")(run) is None
    assert spec.metric_reader("cc_phase_us_per_lane_step")(run) is None
    assert spec.metric_reader("build_ms_per_answer")(run) == \
        pytest.approx(10.0)


class _Run:
    pass


@pytest.mark.parametrize("name", ["route_signal_us_per_lane_step",
                                  "cc_phase_us_per_lane_step",
                                  "build_ms_per_answer",
                                  "params_ms_per_answer"])
def test_readers(monkeypatch, name):
    read = spec.metric_reader(name)
    ops, modules, spans, scope_map = _synthetic()
    sample = dict(scopes.reduce(ops, modules, spans, scope_map),
                  lane_steps=1000.0, questions=1)
    want = {"route_signal_us_per_lane_step": 1e6 * 0.01 / 1000,
            "cc_phase_us_per_lane_step": 0.0,
            "build_ms_per_answer": 10.0,
            "params_ms_per_answer": 8.0}[name]
    run = _Run()
    run.scope_sample = sample
    assert read(run) == pytest.approx(want)
    # a trace whose engine ops carry no scope, and a program without
    # scopes or spans, read nothing
    bare = dict(scopes.reduce(ops, modules, [], {}), lane_steps=1000.0,
                questions=1)
    run.scope_sample = bare
    assert read(run) is None
    other = _Run()
    monkeypatch.setattr(scopes, "_program_names_scopes", lambda: False)
    assert read(other) is None
    assert other.scope_sample is None


def test_reduce_recorded_chip_trace():
    with gzip.open(DATA, "rt") as f:
        rec = json.load(f)
    ops = [tuple(o) for o in rec["ops"]]
    spans = [tuple(s) for s in rec["spans"]]
    modules = [tuple(m) for m in rec["modules"]]
    got = scopes.reduce(ops, modules, spans, rec["scopes"])
    got = json.loads(json.dumps(got))
    want = rec["expected"]
    assert set(got) == set(want)
    for key, value in want.items():
        assert got[key] == pytest.approx(value, rel=1e-9), key
    t = got["scope_s"]
    # every op the engine module ran is an instruction of its text
    assert got["unknown_ops"] == 0
    # the step core is the kernel; the link-state gathers are scoped
    assert max(t, key=t.get) == "step_core"
    assert t["signals"] > t["route"] > t["cc"]
    assert scopes.scoped_share(got) > 0.99
    assert got["host_in_spans_s"] / got["host_s"] > 0.99


def test_sample_on_the_cpu_reads_spans_and_the_engine_text():
    """The warm-up sample end to end at a small size on the CPU: the
    engine's calls are kept and the program's spans land in the trace;
    a CPU trace has no chip ops, so the device readers read nothing."""
    run = _Run()
    run.cell = {"config": spec.config("cresco8"),
                "traffic": dict(spec.traffic("incast-256"), nodes=[16]),
                "workload": {"chips": 1}, "base": spec.HERE}
    s = scopes._take_sample(run)
    assert s["lane_steps"] > 0
    assert {"fabric.build_case", "fabric.grid_params", "fabric.dispatch",
            "fabric.marshal"} <= set(s["span_idle_s"])
    assert s["devices"] == 0 and s["scope_s"] == {}
    assert s["unknown_ops"] == 0 and s["gc_s"] >= 0.0
    run.scope_sample = s
    assert scopes.per_lane_step_us(run, scopes.ROUTE_SIGNAL) is None


def test_engine_text_is_lowered_with_the_arguments_the_engine_ran_with():
    """The kept call carries the grid path's own static arguments (here
    a goodput stride and a step budget other than the defaults), and the
    text lowered from it is the module those arguments compile to."""
    from repro.core import bench
    from repro.core import congestion as cong
    from repro.core.fabric import simulator, systems

    before = [getattr(simulator, n) for n in scopes.ENGINE_ENTRIES]
    with scopes.engine_calls() as calls:
        bench.run_grid(systems.get_system("cresco8"), 16, "ring_allgather",
                       "incast", [32768.0], (cong.steady(),), n_iters=1,
                       warmup=0, max_steps=4096, trace_stride=4)
    assert [getattr(simulator, n) for n in scopes.ENGINE_ENTRIES] == before
    (entry, args, kw, _), = calls
    assert entry is simulator._run_cells_jit
    assert (kw["stride"], kw["max_chunks"]) == (4, 2)
    text = scopes.engine_text(calls[0])
    assert text == entry.lower(*args, **kw).compile().as_text()
    assert text != entry.lower(*args, **dict(kw, stride=8)).compile() \
        .as_text()
    got = scopes.engine_scopes(calls)
    assert set(got.values()) <= set(scopes.DOCUMENTED) | {scopes.UNSCOPED}
    assert "step_core" in got.values()


def test_stalls_report_splits_the_slowest_question():
    from chipbench import stalls
    from chipbench.questions import Answer

    answers = [Answer(0, (), 1.0, 2.0, []), Answer(1, (), 2.0, 5.5, []),
               Answer(2, (), 5.5, 6.5, [])]
    recorded = [("fabric.build_case", 2.0e9, 2.5e9),
                ("fabric.marshal", 2.6e9, 5.4e9),
                ("fabric.marshal", 1.1e9, 1.9e9)]     # another question's
    text = stalls.report(answers, recorded)
    assert text.startswith("slowest_question index 1 seconds 3.5 median 1.0")
    assert stalls.split(answers[1], recorded) == {
        "fabric.build_case": pytest.approx(0.5),
        "fabric.marshal": pytest.approx(2.8)}
