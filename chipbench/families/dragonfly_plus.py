"""Dragonfly+ (Leonardo): groups of leaves under spines, all-to-all global
links between spines of one index, minimal paths first, then non-minimal
paths through transit groups spread over the machine."""
from __future__ import annotations

import numpy as np

from chipbench.topology import Links, Topology, host


def build(n, leaves_per_group, spines_per_group, nodes_per_leaf,
          host_gbit, global_gbit, intra_factor, n_valiant):
    b = Links()
    per_group = leaves_per_group * nodes_per_leaf
    n_groups = -(-n // per_group)
    up_gbit = (host_gbit * nodes_per_leaf / spines_per_group
               if intra_factor <= 0 else host_gbit * intra_factor)

    def leaf(i):
        return ("lf", i // per_group, (i % per_group) // nodes_per_leaf)

    for i in range(n):
        b.add(host(i), leaf(i), host_gbit)
        b.add(leaf(i), host(i), host_gbit)
    for g in range(n_groups):
        for lf in range(leaves_per_group):
            for s in range(spines_per_group):
                b.add(("lf", g, lf), ("sp", g, s), up_gbit)
                b.add(("sp", g, s), ("lf", g, lf), up_gbit)
    for g1 in range(n_groups):
        for g2 in range(n_groups):
            if g1 != g2:
                s = (g1 + g2) % spines_per_group
                b.add(("sp", g1, s), ("sp", g2, s), global_gbit)

    def paths(src, dst):
        ls, ld = leaf(src), leaf(dst)
        gs, gd = ls[1], ld[1]
        inj, ej = b[(host(src), ls)], b[(ld, host(dst))]
        if ls == ld:
            return [[inj, ej]]
        if gs == gd:
            return [[inj, b[(ls, ("sp", gs, s))], b[(("sp", gs, s), ld)], ej]
                    for s in range(spines_per_group)]
        s = (gs + gd) % spines_per_group
        out = [[inj, b[(ls, ("sp", gs, s))], b[(("sp", gs, s), ("sp", gd, s))],
                b[(("sp", gd, s), ld)], ej]]
        # non-minimal paths through transit groups spread over the machine
        stride = max(1, n_groups // (n_valiant + 1))
        seen = {gs, gd}
        for j in range(n_groups):
            gi = (min(gs, gd) + 1 + j * stride) % n_groups
            if gi in seen or len(out) >= 1 + n_valiant:
                continue
            seen.add(gi)
            s1, s2 = (gs + gi) % spines_per_group, (gi + gd) % spines_per_group
            p = [inj, b[(ls, ("sp", gs, s1))], b[(("sp", gs, s1), ("sp", gi, s1))]]
            if s1 != s2:
                p += [b[(("sp", gi, s1), ("lf", gi, 0))],
                      b[(("lf", gi, 0), ("sp", gi, s2))]]
            p += [b[(("sp", gi, s2), ("sp", gd, s2))], b[(("sp", gd, s2), ld)], ej]
            out.append(p)
        return out

    return Topology(np.asarray(b.caps), b.links, paths)
