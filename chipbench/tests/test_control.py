"""``correct`` fails where it must, on the CPU at a small size.

The control is the plain reference computed in bfloat16, put in the
program's place: it has to fail the limits of the traffic mix it stands
in. And a run driven with the timed path broken underneath (a step that
leaves its state unchanged, half of the lanes left out, the exchange
between devices left out, an answer altered where it is produced) has
to come out not correct, while the same run unbroken comes out correct.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import jax.numpy as jnp
import pytest

from chipbench import check, reference, spec

MiB = float(1 << 20)


def as_results(rows):
    """Reference rows in the shape of the program's BenchResults."""
    return [types.SimpleNamespace(
        t_uncongested_s=r["t_uncongested_s"], t_congested_s=r["t_congested_s"],
        ratio=r["ratio"], n_iters=r["n_iters"],
        job_times=(("victim", r["victim_mean_s"], r["n_iters"][1]),))
        for r in rows]


@pytest.mark.parametrize("config,traffic,nodes", [
    ("leonardo", "incast-256", 64), ("cresco8", "incast-256", 32)])
def test_bfloat16_control_fails_the_limits(config, traffic, nodes):
    cfg, tr = spec.config(config), spec.traffic(traffic)
    kw = dict(n_iters=cfg["n_iters"], warmup=cfg["warmup"], chunk=2048,
              max_steps=200_000)
    sizes = (40e3, 1.5 * MiB)
    f32 = reference.answer(cfg, [nodes], tr["aggressor"], sizes, **kw)
    bf16 = reference.answer(cfg, [nodes], tr["aggressor"], sizes,
                            dtype=jnp.bfloat16, **kw)
    readings = check.compare(as_results(bf16), f32)
    assert not check.verdict(readings, tr["check"]["limits"]), readings
    assert check.verdict(check.compare(as_results(f32), f32),
                         tr["check"]["limits"])


def test_broken_timed_path_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(spec.ROOT, "src"), spec.ROOT,
         env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-m", "chipbench.tests.faults"],
                       cwd=spec.ROOT, env=env, capture_output=True,
                       text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "sound_mesh": True,
                   "state_unchanged": False, "half_batch": False,
                   "altered_answer": False, "no_exchange": False}
