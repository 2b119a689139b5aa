"""Decide ``correct``: the program's answers against the plain reference.

Every compared lane of an answered question is held against the
reference's answer to the same question:

* ``time_gap_steps`` — the widest gap of a reported time (the baseline
  and the congested mean iteration time, and the victim job's own mean)
  in simulation steps of that lane;
* ``ratio_gap`` — the widest relative gap of the slowdown ratio;
* ``count_mismatch`` — lanes whose number of iterations done differs, or
  that finished on one side and not on the other (an exact comparison).

The limits live in the traffic file, beside the mix they were measured
on (PERF.md gives the readings each was set from).
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

NUMBERS = ("time_gap_steps", "ratio_gap", "count_mismatch")


def _gap(a: float, b: float) -> float:
    if math.isnan(a) and math.isnan(b):
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b)


def compare(results: Sequence, reference_rows: Sequence[dict]) -> Dict[str, float]:
    """The three numbers over one question's rows (program BenchResults
    and reference rows in the same order)."""
    if len(results) != len(reference_rows):
        return {"time_gap_steps": math.inf, "ratio_gap": math.inf,
                "count_mismatch": float(abs(len(results) - len(reference_rows))
                                        or 1)}
    t_gap = r_gap = 0.0
    mismatch = 0
    for got, want in zip(results, reference_rows):
        dt = want["dt"]
        victim = [t for name, t, _ in got.job_times if name == "victim"]
        victim_n = [n for name, _, n in got.job_times if name == "victim"]
        times = [(got.t_uncongested_s, want["t_uncongested_s"]),
                 (got.t_congested_s, want["t_congested_s"]),
                 (victim[0] if victim else math.nan, want["victim_mean_s"])]
        t_gap = max([t_gap] + [_gap(a, b) / dt for a, b in times])
        if math.isfinite(want["ratio"]) and math.isfinite(got.ratio):
            r_gap = max(r_gap, abs(got.ratio / want["ratio"] - 1.0))
        elif not (math.isnan(want["ratio"]) and math.isnan(got.ratio)):
            r_gap = math.inf
        n_u, n_c = want["n_iters"]
        mismatch += int(tuple(got.n_iters) != (n_u, n_c))
        mismatch += int((victim_n[0] if victim_n else 0) != n_c)
    return {"time_gap_steps": t_gap, "ratio_gap": r_gap,
            "count_mismatch": float(mismatch)}


def worst(readings: List[Dict[str, float]]) -> Dict[str, float]:
    return {k: max([r[k] for r in readings] + [0.0]) for k in NUMBERS}


def verdict(readings: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(limits[k] is not None and math.isfinite(readings[k])
               and readings[k] <= limits[k] for k in NUMBERS)


def lines(readings: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    return [f"check {k} {readings[k]!r} limit {limits[k]!r}" for k in NUMBERS]


def as_json(readings: Dict[str, float], limits: Dict[str, float]) -> dict:
    return {k: {"value": readings[k] if math.isfinite(readings[k]) else None,
                "limit": limits[k]} for k in NUMBERS}
