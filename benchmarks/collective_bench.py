"""§III-B microbenchmark: wall-clock cost of the custom collective
schedules (ring AllGather, bidir ring, linear/pairwise AlltoAll, ring
AllReduce, incast) on an 8-device host mesh.

This is a rehearsal on CPU virtual devices, not a device measurement:
jax pins the device count at first init, so the timing runs in one
subprocess with ``JAX_PLATFORMS=cpu`` and
``--xla_force_host_platform_device_count=8``, exactly like the
multi-device tests. The child never contends for an accelerator.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import cached_sweep, size_label

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys, time
import jax, jax.numpy as jnp
import numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.core import collectives as C
from repro.launch.mesh import compat_make_mesh

mesh = compat_make_mesh((8,), ("x",))
n = 8
out = []

def timeit(fn, x, iters=30):
    y = fn(x); jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(iters):
        y = fn(x)
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / iters * 1e6

for size in json.loads(sys.argv[1]):
    d = max(size // 4 // n, 8)
    x = jnp.zeros((n * d,), jnp.float32)
    xa = jnp.zeros((n, d), jnp.float32)
    sm = lambda f, in_s, out_s: jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=in_s, out_specs=out_s, check_vma=False))
    cases = {
        "ring_all_gather": sm(lambda v: C.ring_all_gather(v, "x", n),
                              P("x"), P(None)),
        "bidir_ring_all_gather": sm(
            lambda v: C.ring_all_gather(v, "x", n, bidirectional=True),
            P("x"), P(None)),
        "xla_all_gather": sm(lambda v: jax.lax.all_gather(v, "x"),
                             P("x"), P(None)),
    }
    for name, fn in cases.items():
        out.append({"collective": name, "size": size,
                    "us_per_call": timeit(fn, x)})
    cases2 = {
        "ring_all_reduce": sm(lambda v: C.ring_all_reduce(v[0], "x", n),
                              P("x"), P(None)),
        "xla_all_reduce": sm(lambda v: jax.lax.psum(v[0], "x"),
                             P("x"), P(None)),
        "linear_all_to_all": sm(lambda v: C.linear_all_to_all(v[0], "x", n),
                                P("x"), P("x")),
        "pairwise_all_to_all": sm(
            lambda v: C.pairwise_all_to_all(v[0], "x", n), P("x"), P("x")),
        "incast_gather": sm(lambda v: C.incast_gather(v[0], "x", n),
                            P("x"), P("x")),
    }
    xb = jnp.zeros((n, n, max(d // n, 1)), jnp.float32)
    for name, fn in cases2.items():
        out.append({"collective": name, "size": size,
                    "us_per_call": timeit(fn, xb)})
print("REPORT" + json.dumps(out))
"""


def run_all(sizes) -> list:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _SCRIPT, json.dumps(sizes)],
                       env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("REPORT")][-1]
    return json.loads(line[len("REPORT"):])


def main(force: bool = False):
    from repro.core import scenarios

    sizes = list(scenarios.get("collective_microbench").microbench_sizes)
    cache_points = [(s,) for s in sizes]

    def run_size(size):
        rows = run_all([size])
        return {r["collective"]: round(r["us_per_call"], 1) for r in rows}

    rows = cached_sweep("collective_bench", ["size"], cache_points, run_size,
                        force=force)
    print("\n# §III-B — custom collective schedules, 8 cpu virtual devices "
          "(us/call)")
    colls = [k for k in rows[0] if k != "size"]
    print(f"{'size':>8} " + " ".join(f"{c:>22}" for c in colls))
    for r in rows:
        print(f"{size_label(r['size']):>8} "
              + " ".join(f"{float(r[c]):>22.1f}" for c in colls))
    return rows


if __name__ == "__main__":
    main()
