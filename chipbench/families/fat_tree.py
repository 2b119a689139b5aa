"""Two-level fat-tree (CRESCO8): leaves under a tapered spine layer, one
candidate path through each spine."""
from __future__ import annotations

import numpy as np

from chipbench.topology import Links, Topology, host


def build(n, nodes_per_leaf, taper, host_gbit):
    b = Links()
    n_leaf = -(-n // nodes_per_leaf)
    n_spine = max(1, round(nodes_per_leaf / taper))
    for i in range(n):
        lf = ("leaf", i // nodes_per_leaf)
        b.add(host(i), lf, host_gbit)
        b.add(lf, host(i), host_gbit)
    for lf in range(n_leaf):
        for s in range(n_spine):
            b.add(("leaf", lf), ("spine", s), host_gbit)
            b.add(("spine", s), ("leaf", lf), host_gbit)

    def paths(src, dst):
        ls, ld = ("leaf", src // nodes_per_leaf), ("leaf", dst // nodes_per_leaf)
        inj, ej = b[(host(src), ls)], b[(ld, host(dst))]
        if ls == ld:
            return [[inj, ej]]
        return [[inj, b[(ls, ("spine", s))], b[(("spine", s), ld)], ej]
                for s in range(n_spine)]

    return Topology(np.asarray(b.caps), b.links, paths)
