"""The closed-loop client: which grids it asks, and what the answers are
worth.

A traffic file names one grid shape (fabric cells, victim, aggressor,
profile) and the vector sizes each question asks it at. The seed draws
the order in which each question lists the sizes (and so the order of
its lanes), never the sizes themselves: every seed asks the same work,
so runs with different seeds differ no more than two runs of one seed.
(With sizes drawn per question from log-uniform bands, one question
more or less of a size in a 30 s window moved the Leonardo incast rate
by 3% between seeds, against 0.1% between runs of one seed, on one TPU
v5 lite.)
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Sequence

import numpy as np


class Questions:
    """The seed's sequence of questions: ``sizes(k)`` is the k-th."""

    def __init__(self, traffic: dict, seed: int):
        self.base = tuple(float(v) for v in traffic["sizes"])
        self.seed = int(seed)

    def sizes(self, k: int) -> tuple:
        rng = np.random.default_rng([self.seed, 0, k])
        return tuple(self.base[i] for i in rng.permutation(len(self.base)))

    def warmup_sizes(self) -> tuple:
        """The smallest size in every slot: the window's shapes at the
        least simulated work."""
        return (min(self.base),) * len(self.base)


def check_sample(seed: int, n_answered: int, n_check: int) -> List[int]:
    """Which answered questions the check compares, drawn from the seed
    apart from the sizes' stream."""
    rng = np.random.default_rng([int(seed), 1])
    k = min(n_check, n_answered)
    return sorted(int(i) for i in rng.choice(n_answered, size=k,
                                             replace=False))


@dataclasses.dataclass
class Answer:
    """One question as the client saw it."""
    index: int
    sizes: tuple
    submitted: float                 # host clock, seconds
    answered: float
    results: list                    # the program's BenchResults


def simulated_seconds(results: Sequence) -> float:
    """Simulated time an answer states: per lane, the victim iterations
    done times the lane's reported mean iteration time. A lane that did
    not finish states nothing."""
    total = 0.0
    for r in results:
        for n, t in zip(r.n_iters, (r.t_uncongested_s, r.t_congested_s)):
            if n > 0 and math.isfinite(t):
                total += n * t
    return total


def sim_us_per_s(answers: Sequence[Answer]) -> float:
    """Simulated microseconds answered per wall second, over the window
    from the first question's submission to the last answer."""
    window = answers[-1].answered - answers[0].submitted
    sim = sum(simulated_seconds(a.results) for a in answers)
    return sim * 1e6 / window


def failed(answer: Answer) -> bool:
    return any(r.dnf for r in answer.results)
