"""Custom collective schedules vs XLA one-shot natives on an 8-device mesh.

jax locks the device count at first backend init, and conftest must NOT
force a multi-device view (smoke tests see 1 device). These tests
therefore run subprocesses on 8 CPU virtual devices
(``JAX_PLATFORMS=cpu``,
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) that execute the
checks and report JSON; the pytest cases assert on the parsed report.
The paper-§III-B schedule checks and the MoE dispatch check run in
separate subprocesses, so a failure of the model scaffold cannot take the
schedule checks down with it.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

_PRELUDE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from repro.launch.mesh import compat_make_mesh
report = {}
"""

_SCHEDULE_SCRIPT = _PRELUDE + r"""
from functools import partial
from jax.sharding import PartitionSpec as P

from repro.core import collectives as C

mesh = compat_make_mesh((8,), ("x",))
n = 8

x = jax.random.normal(jax.random.PRNGKey(0), (n, 4, 16), jnp.float32)

def run(fn, inp, in_spec=P("x"), out_spec=P(None)):
    return np.asarray(C.run_on_mesh(mesh, "x", fn, inp, in_spec, out_spec))

# ring all-gather == the full buffer in rank order, replicated
full = np.asarray(x)
ag = run(partial(C.ring_all_gather, axis_name="x", n=n),
         x.reshape(n * 4, 16), in_spec=P("x"), out_spec=P(None))
report["ring_ag"] = float(np.abs(ag.reshape(n, 4, 16) - full).max())

bag = run(lambda v: C.ring_all_gather(v, "x", n, bidirectional=True),
          x.reshape(n * 4, 16), in_spec=P("x"), out_spec=P(None))
report["bidir_ring_ag"] = float(np.abs(bag.reshape(n, 4, 16) - full).max())

# ring reduce-scatter: rank r gets sum over ranks of chunk r
# per-rank payload under P("x") keeps the rank: (1, n, 3) -> v[0] is (n, 3)
y = jax.random.normal(jax.random.PRNGKey(1), (n, n, 3), jnp.float32)
rs = run(lambda v: C.ring_reduce_scatter(v[0], "x", n),
         y, in_spec=P("x"), out_spec=P("x"))
want_rs = np.asarray(y).sum(axis=0)  # (n, 3): chunk r summed over ranks
report["ring_rs"] = float(np.abs(rs.reshape(n, 3) - want_rs).max())

# ring all-reduce == everyone holds the full sum (replicated output)
ar = run(lambda v: C.ring_all_reduce(v[0], "x", n),
         y, in_spec=P("x"), out_spec=P(None))
report["ring_ar"] = float(np.abs(np.asarray(ar) - want_rs).max())

# all-to-all schedules vs the native one-shot
z = jnp.arange(n * n * 2, dtype=jnp.float32).reshape(n, n, 2)
native = run(lambda v: jax.lax.all_to_all(v[0], "x", 0, 0, tiled=True),
             z, in_spec=P("x"), out_spec=P("x"))
linear = run(lambda v: C.linear_all_to_all(v[0], "x", n),
             z, in_spec=P("x"), out_spec=P("x"))
pair = run(lambda v: C.pairwise_all_to_all(v[0], "x", n),
           z, in_spec=P("x"), out_spec=P("x"))
report["a2a_linear"] = float(np.abs(linear - native).max())
report["a2a_pairwise"] = float(np.abs(pair - native).max())

# incast: root 0 collects everyone's buffer. Output differs per rank
# (zeros off-root), so gather all ranks' views and check the root's.
w = jax.random.normal(jax.random.PRNGKey(2), (n, 5), jnp.float32)
inc = run(lambda v: C.incast_gather(v[0], "x", n, root=0),
          w, in_spec=P("x"), out_spec=P("x"))
inc = inc.reshape(n, n, 5)  # rank-major stacking
report["incast"] = float(np.abs(inc[0] - np.asarray(w)).max())

# analyzer correction: a bf16-primal psum must be counted at 2 B/elem even
# though the CPU backend float-normalizes the wire to f32 (A1), and the
# CPU tuple-form scaffolding must not inflate HBM bytes (A2)
from repro.launch.hlo_stats import analyze
def psum_bf16(v):
    return jax.lax.psum(v.astype(jnp.bfloat16), "x").astype(jnp.float32)
fn = jax.jit(jax.shard_map(psum_bf16, mesh=mesh, in_specs=P(),
                           out_specs=P(), check_vma=False))
text = fn.lower(jnp.ones((1024,), jnp.float32)).compile().as_text()
st = analyze(text, 8)
elems = 1024
bf16_ar_wire = 2 * (7 / 8) * elems * 2  # ring all-reduce, 2-byte elements
report["bf16_psum_wire"] = st["collectives"]["total"]["wire_bytes"]
report["bf16_psum_wire_expected"] = bf16_ar_wire

print("REPORT" + json.dumps(report))
"""

_MOE_SCRIPT = _PRELUDE + r"""
# MoE EP dispatch path on a real 8-way mesh (the paper's AlltoAll pattern)
import dataclasses
from repro.configs import get_config
from repro.models.api import build_model
from repro.launch.mesh import rules_for
mesh2 = compat_make_mesh((8, 1), ("data", "model"))
cfg = dataclasses.replace(get_config("kimi-k2-1t-a32b").reduced(),
                          n_experts=16, top_k=2, capacity_factor=8.0)
rules = rules_for(cfg, mesh2)
model = build_model(cfg, rules, mesh2)
params = model.init(jax.random.PRNGKey(0))
tok = jax.random.randint(jax.random.PRNGKey(1), (16, 8), 0, cfg.vocab_size)
with jax.set_mesh(mesh2):
    loss, metrics = model.loss(params, {"tokens": tok, "labels": tok})
report["moe_ep8_loss_finite"] = bool(jnp.isfinite(loss))
# same loss on a single-device run (EP must not change the math)
mesh1 = compat_make_mesh((1, 1), ("data", "model"))
rules1 = rules_for(cfg, mesh1)
model1 = build_model(cfg, rules1, mesh1)
with jax.set_mesh(mesh1):
    loss1, _ = model1.loss(params, {"tokens": tok, "labels": tok})
report["moe_ep_vs_single"] = abs(float(loss) - float(loss1))

print("REPORT" + json.dumps(report))
"""


def _run_report(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("REPORT")][-1]
    return json.loads(line[len("REPORT"):])


@pytest.fixture(scope="module")
def report():
    return _run_report(_SCHEDULE_SCRIPT)


@pytest.fixture(scope="module")
def moe_report():
    return _run_report(_MOE_SCRIPT)


def test_ring_all_gather(report):
    assert report["ring_ag"] < 1e-6
    assert report["bidir_ring_ag"] < 1e-6


def test_ring_reduce_scatter(report):
    assert report["ring_rs"] < 1e-5


def test_ring_all_reduce(report):
    assert report["ring_ar"] < 1e-5


def test_all_to_all_schedules(report):
    assert report["a2a_linear"] < 1e-6
    assert report["a2a_pairwise"] < 1e-6


def test_incast(report):
    assert report["incast"] < 1e-6


def test_moe_ep_dispatch(moe_report):
    assert moe_report["moe_ep8_loss_finite"]
    assert moe_report["moe_ep_vs_single"] < 5e-3


def test_bf16_wire_correction(report):
    """hlo_stats must count bf16-primal collectives at 2 B/element despite
    the CPU backend's f32 float-normalization (EXPERIMENTS.md §Perf A1)."""
    got = report["bf16_psum_wire"]
    want = report["bf16_psum_wire_expected"]
    assert got <= want * 1.10, (got, want)  # not counted as f32 (2x)
    assert got >= want * 0.5, (got, want)   # and not dropped entirely


# ---------------------------------------------------------------------------
# analytic wire-byte model invariants (pure python — no devices needed)
# ---------------------------------------------------------------------------

def test_wire_bytes_model():
    from repro.core.collectives import wire_bytes_model as wbm

    v = 1024.0
    for n in (2, 4, 16):
        ag = wbm("ring_all_gather", n, v)
        ar = wbm("ring_all_reduce", n, v)
        a2a = wbm("linear_all_to_all", n, v)
        inc = wbm("incast", n, v)
        assert np.isclose(ar["bytes"], 2 * ag["bytes"])  # RS+AG
        assert ag["steps"] == n - 1 and ar["steps"] == 2 * (n - 1)
        assert np.isclose(a2a["bytes"], (n - 1) / n * v)
        assert inc["bytes"] == v
        # bidirectional halves the serialized step count
        bi = wbm("bidir_ring_all_gather", n, v)
        assert bi["steps"] == (n - 1 + 1) // 2
        assert np.isclose(bi["bytes"], ag["bytes"])
    assert wbm("ring_all_gather", 1, v) == {"bytes": 0.0, "steps": 0}
