"""What every topology family file under ``chipbench/families/`` builds
on: directed capacitated links and structured candidate paths.

A family file ``families/<family>.py`` exports ``build(n, **params)``,
which returns a :class:`Topology` of ``n`` nodes. Hosts are
``host(i)``; a switch is any other hashable tuple whose first item is
not ``"h"``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np


@dataclasses.dataclass
class Topology:
    caps: np.ndarray                 # (L,) bytes/s
    links: List[Tuple]               # (a, b) endpoints per link
    paths: object                    # (src, dst) -> list of link-id lists


class Links:
    def __init__(self):
        self.links, self.caps, self.index = [], [], {}

    def add(self, a, b, gbit):
        if (a, b) not in self.index:
            self.index[(a, b)] = len(self.links)
            self.links.append((a, b))
            self.caps.append(gbit * 1e9 / 8.0)

    def __getitem__(self, key):
        return self.index[key]


def host(i):
    return ("h", i)
