"""Peaks and the step core's bytes, kept with the benchmark."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "chipbench/peaks.json")
    return table[device_kind]


def step_core_event_pattern() -> str:
    with open(os.path.join(HERE, "step_core.json")) as f:
        return json.load(f)["event_pattern"]


def step_core_bytes(n_flows: int, max_hops: int, n_links: int) -> int:
    """HBM bytes one lane-step of the step core must move at least, from
    the problem's dims: in, the (hops x flows) path table, three flow
    vectors (injection, source id, NIC cap), five link vectors (queue,
    occupancy, capacity, feeding and fed switch) and five scalars; out,
    two flow vectors (scaled injection, achieved rate) and three link
    vectors (arrival, new queue, effective capacity). All 4-byte words;
    the link vectors hold the sink row too."""
    F, H, L1 = int(n_flows), int(max_hops), int(n_links) + 1
    return 4 * (F * H + 5 * F + 8 * L1 + 5)
