"""The trace reduction, on synthetic events and on a small recorded trace
of a traced ``leonardo.incast-256`` run on one TPU v5 lite."""
from __future__ import annotations

import gzip
import json
import os
import re

import pytest

from chipbench import roofline, trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "leonardo_incast_trace.json.gz")
MS = 1e6  # ns


def test_reduce_synthetic():
    spans = [("chipbench.window", 0, 100 * MS),
             ("chipbench.question", 0, 60 * MS),
             ("chipbench.marshal", 50 * MS, 60 * MS),
             ("chipbench.question", 60 * MS, 100 * MS)]
    ops = [(0, "%while.1", 10 * MS, 40 * MS),
           (0, "%my_kernel.2", 20 * MS, 30 * MS),   # nested in the loop
           (0, "%fusion.2", 70 * MS, 90 * MS),
           (1, "%my_kernel.2", 0, 100 * MS)]
    got = trace.reduce(ops, spans, "^%my_kernel")
    assert got["window_s"] == pytest.approx(0.1)
    assert got["devices"] == 2
    assert got["dropped_s"] == 0
    # device 0 busy 10..40 and 70..90 (50 ms), device 1 all 100 ms
    assert got["busy_s"] == pytest.approx((0.05 + 0.1) / 2)
    assert got["step_core_s"] == pytest.approx(0.01 + 0.1)
    # host time: the device-busy union over both devices covers everything
    assert got["question_host_s"] == pytest.approx([0.0, 0.0])
    # self time: the loop's 30 ms less the kernel's 10 ms inside it
    assert dict((n, t) for n, t in got["device_ops"]) == pytest.approx(
        {"%my_kernel.2": 0.11, "%while.1": 0.02, "%fusion.2": 0.02})
    # device 0's gaps: 0..10 (first question), 40..70 (its middle lies in
    # the marshal span), 90..100 (second question)
    assert got["idle_gaps"][0] == ["chipbench.marshal", pytest.approx(0.03)]
    assert [g for g, _ in got["idle_gaps"]] == [
        "chipbench.marshal", "chipbench.question", "chipbench.question"]


def test_reduce_single_device_host_time_and_drops():
    spans = [("chipbench.window", 0, 10 * MS),
             ("chipbench.question", 0, 10 * MS)]
    ops = [(0, "%while.3", 2 * MS, 6 * MS), (0, "%k.1", 3 * MS, 5 * MS)]
    got = trace.reduce(ops, spans, r"^%k(\.[0-9]+)?$")
    assert got["question_host_s"] == pytest.approx([0.006])
    assert got["busy_s"] == pytest.approx(0.004)
    assert got["step_core_s"] == pytest.approx(0.002)
    assert trace.complete(got)
    dropped = [(trace.DROPPED_EVENT, 8 * MS, 12 * MS)]
    got = trace.reduce(ops, spans, r"^%k(\.[0-9]+)?$", dropped)
    assert got["dropped_s"] == pytest.approx(0.002)
    assert not trace.complete(got)


def test_short_names_match_the_step_core_pattern():
    pattern = re.compile(roofline.step_core_event_pattern())
    text = ("%fabric_step_core.8 = (f32[4,1,256]{2,1,0:T(1,128)}) "
            "custom-call(s32[4,4,256]{2,1,0} %pad.163), "
            'custom_call_target="tpu_custom_call"')
    assert trace.short_name(text) == "%fabric_step_core.8"
    assert pattern.search(trace.short_name(text))
    assert not pattern.search("%fusion.125")


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        trace.reduce([], [("chipbench.question", 0, 1)], "k")


def test_reduce_recorded_chip_trace():
    with gzip.open(DATA, "rt") as f:
        rec = json.load(f)
    ops = [tuple(o) for o in rec["ops"]]
    spans = [tuple(s) for s in rec["spans"]]
    got = trace.reduce(ops, spans, roofline.step_core_event_pattern())
    assert _close(got, rec["expected"])
    lo, hi = [(s, e) for n, s, e in spans if n == trace.WINDOW_SPAN][0]
    clipped = [(n, max(s, lo), min(e, hi)) for _, n, s, e in ops
               if e > lo and s < hi]
    # the step core is a leaf: its time is the plain sum of its events
    assert got["step_core_s"] == pytest.approx(sum(
        e - s for n, s, e in clipped if n.startswith("%fabric_step_core"))
        / 1e9)
    # ops on the line nest, so their self times add up to the busy union
    assert sum(trace._self_times(clipped).values()) / 1e9 == pytest.approx(
        got["busy_s"], rel=1e-9)
    assert 0 < got["step_core_s"] < got["busy_s"] < got["window_s"]
    assert got["device_ops"][0][0] == "%fabric_step_core.8"


def _close(got, want) -> bool:
    if isinstance(want, dict):
        return set(got) == set(want) and all(_close(got[k], want[k])
                                             for k in want)
    if isinstance(want, (list, tuple)):
        return len(got) == len(want) and all(_close(a, b)
                                             for a, b in zip(got, want))
    if isinstance(want, float):
        return got == pytest.approx(want, rel=1e-9, abs=1e-12)
    return got == want
