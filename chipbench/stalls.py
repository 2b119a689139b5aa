"""Run one cell as ``chipbench.run`` does, with the program's span
recorder installed over the window, and print the window's slowest
question split by ``fabric.*`` span to standard error: a question that
stalls shows which host phase (or the device, inside ``fabric.marshal``)
held it.

    python -m chipbench.stalls --workload leonardo.incast-256 --seed 7 \\
        --seconds 30 --trace 0

Arguments and the result line are those of ``chipbench.run``; the
recorder keeps host clock readings only and adds nothing the window
waits on.
"""
from __future__ import annotations

import collections
import statistics
import sys

from chipbench import run as run_mod


def split(answer, recorded) -> dict:
    """Seconds of each span name inside one question's interval."""
    lo, hi = answer.submitted * 1e9, answer.answered * 1e9
    out = collections.defaultdict(float)
    for name, s, e in recorded:
        if s >= lo and e <= hi:
            out[name] += (e - s) / 1e9
    return dict(out)


def report(answers, recorded) -> str:
    took = [a.answered - a.submitted for a in answers]
    worst = max(range(len(answers)), key=took.__getitem__)
    return (f"slowest_question index {worst} seconds {took[worst]!r} "
            f"median {statistics.median(took)!r} "
            f"spans {split(answers[worst], recorded)}")


def main(argv=None) -> int:
    window = run_mod.window
    found = {}

    def recorded_window(*args, **kw):
        try:
            from repro.core import spans
        except ImportError:          # a program without spans
            return window(*args, **kw)
        with spans.recording() as rec:
            answers = window(*args, **kw)
        found["answers"], found["spans"] = answers, list(rec)
        return answers

    run_mod.window = recorded_window
    try:
        code = run_mod.main(argv)
    finally:
        run_mod.window = window
    if found.get("answers"):
        print(report(found["answers"], found["spans"]), file=sys.stderr,
              flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
