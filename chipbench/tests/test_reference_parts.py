"""The reference's fabric-specific parts, found by name in files of their
own, on the CPU (no chip).

A topology family (``families/``), a CC rule (``cc/``) and an envelope
profile (``profiles/``) each enter as one new file. Moving today's parts
there changed no bit of the reference's lanes: ``data/reference_lanes.json``
holds ``launch_lane``'s raw outputs as the reference gave them before
the parts were files (at sizes 40e3 and 1.5 MiB, the baseline and the
congested lane). A part that is missing fails when the cell loads, and a
time-varying envelope written as a new file answers as the program's.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import check, reference, spec

MiB = float(1 << 20)
SIZES = (40e3, 1.5 * MiB)
PARTS = ("families", "cc", "profiles")
KW = dict(chunk=2048, max_steps=200_000)

with open(os.path.join(spec.HERE, "tests", "data",
                       "reference_lanes.json")) as _f:
    GOLDEN = json.load(_f)


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


def _lanes(cfg, nodes, aggressor, profile="steady", base=spec.HERE):
    """launch_lane's raw outputs, in the order the golden file lists them."""
    case = reference.build_case(cfg, nodes, aggressor, base)
    out = []
    for v in SIZES:
        dt = reference.choose_dt(case, v)
        for prof in (spec.BASELINE_PROFILE, profile):
            o = reference.launch_lane(
                case, cfg["cc"], v, prof, dt, n_iters=cfg["n_iters"],
                chunk=KW["chunk"],
                max_chunks=-(-KW["max_steps"] // KW["chunk"]), base=base)
            out.append((v, dt, {k: np.asarray(x) for k, x in o.items()}))
    return out


def _assert_golden(lanes, cfg_name, nodes, aggressor):
    want = [g for g in GOLDEN if (g["config"], g["nodes"], g["aggressor"])
            == (cfg_name, nodes, aggressor)]
    assert len(want) == len(lanes) == 4
    for (v, dt, got), w in zip(lanes, want):
        assert (v, dt) == (w["vector_bytes"], w["dt"])
        assert int(got["it"]) == w["it"]
        assert int(got["chunks"]) == w["chunks"]
        for key in ("t", "qd_acc", "t_done"):
            np.testing.assert_array_equal(_bits(got[key]), _bits(w[key]),
                                          err_msg=key)


@pytest.mark.parametrize("name,nodes,aggressor", [
    ("leonardo", 32, "incast"), ("cresco8", 32, "incast"),
    ("leonardo", 16, "alltoall")])
def test_lanes_bit_identical_to_before_the_move(name, nodes, aggressor):
    _assert_golden(_lanes(spec.config(name), nodes, aggressor), name, nodes,
                   aggressor)


def _copy_parts(base):
    for kind in PARTS:
        shutil.copytree(os.path.join(spec.HERE, kind), os.path.join(base, kind),
                        ignore=shutil.ignore_patterns("__pycache__"))


def _bench(config: str, traffic: str) -> dict:
    return {"workloads": [{"name": "c", "config": config, "traffic": traffic,
                           "chips": 1}],
            "end_to_end": [], "per_layer": []}


def _write(base, kind, name, obj):
    os.makedirs(os.path.join(base, kind), exist_ok=True)
    with open(os.path.join(base, kind, name + ".json"), "w") as f:
        json.dump(obj, f)


def test_parts_found_from_new_files_alone(tmp_path):
    """A configuration and mix that name copies of the shipped parts under
    other names give the shipped lanes, bit for bit."""
    base = str(tmp_path)
    _copy_parts(base)
    for kind, old, new in (("families", "dragonfly_plus", "df_copy"),
                           ("cc", "ib", "ib_copy"),
                           ("profiles", "steady", "steady_copy")):
        shutil.copy(os.path.join(spec.HERE, kind, old + ".py"),
                    os.path.join(base, kind, new + ".py"))
    cfg = spec.config("leonardo")
    cfg = dict(cfg, name="leonardo_copy",
               topology=dict(cfg["topology"], family="df_copy"),
               cc=dict(cfg["cc"], kind="ib_copy"))
    _write(base, "configs", "leonardo_copy", cfg)
    _write(base, "traffic", "incast-copy",
           dict(spec.traffic("incast-256"), profile="steady_copy"))
    cell = spec.cell("c", _bench("leonardo_copy", "incast-copy"), base=base)
    assert cell["base"] == base
    assert spec.family("df_copy", base) is not spec.family("dragonfly_plus")
    lanes = _lanes(cell["config"], 32, "incast", "steady_copy", base)
    _assert_golden(lanes, "leonardo", 32, "incast")


@pytest.mark.parametrize("kind,missing", [
    ("families", "dragonfly_x"), ("cc", "slingshot_x"),
    ("profiles", "bursty_x")])
def test_missing_part_fails_when_the_cell_loads(tmp_path, kind, missing):
    base = str(tmp_path)
    _copy_parts(base)
    cfg, tr = spec.config("leonardo"), spec.traffic("incast-256")
    if kind == "families":
        cfg = dict(cfg, topology=dict(cfg["topology"], family=missing))
    elif kind == "cc":
        cfg = dict(cfg, cc=dict(cfg["cc"], kind=missing))
    else:
        tr = dict(tr, profile=missing)
    _write(base, "configs", "leonardo", cfg)
    _write(base, "traffic", "incast-256", tr)
    path = os.path.join(base, kind, missing + ".py")
    with pytest.raises(FileNotFoundError, match=re.escape(path)):
        spec.cell("c", _bench("leonardo", "incast-256"), base=base)


def test_shipped_profiles_are_the_programs():
    import jax
    import jax.numpy as jnp

    from repro.core import congestion, envelopes

    ts = jnp.linspace(0.0, 2e-2, 257, dtype=jnp.float32)
    for name in ("steady", "off"):
        mod = spec.profile(name)
        env = getattr(congestion, mod.PROGRAM)().params()
        want = jax.vmap(lambda t: envelopes.envelope_at(env, t))(ts)  # noqa: B023
        got = jax.vmap(mod.envelope)(ts)
        np.testing.assert_array_equal(got, want)
        assert set(np.unique(got)) == {1.0 if name == "steady" else 0.0}


def test_reference_imports_nothing_of_the_program():
    code = """
import os, sys
from chipbench import reference, spec
for kind, get in (("families", spec.family), ("cc", spec.cc_rule),
                  ("profiles", spec.profile)):
    for f in sorted(os.listdir(os.path.join(spec.HERE, kind))):
        if f.endswith(".py"):
            get(f[:-3])
for name in ("leonardo", "cresco8"):
    reference.build_case(spec.config(name), 16, "incast")
print(sorted(m for m in sys.modules if m.split(".")[0] == "repro"))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-4000:]
    assert r.stdout.strip().splitlines()[-1] == "[]"


SQUARE_WAVE = '''"""The aggressor on for ``burst_s``, then off for ``pause_s``."""
import jax.numpy as jnp

PROGRAM = "bursty"


def envelope(t, burst_s, pause_s):
    return (t % (burst_s + pause_s) < burst_s).astype(jnp.float32)
'''


def test_time_varying_profile_from_a_new_file(tmp_path):
    """A square wave written as a new profile file (40 us on, 20 us off)
    answers as the program's ``bursty`` profile, under ``incast-256``'s
    limits."""
    from chipbench import run

    base = str(tmp_path)
    _copy_parts(base)
    (tmp_path / "profiles" / "square.py").write_text(SQUARE_WAVE)
    tr = dict(spec.traffic("incast-256"), profile="square", nodes=[32],
              profile_args={"burst_s": 4e-5, "pause_s": 2e-5})
    _write(base, "configs", "leonardo", spec.config("leonardo"))
    _write(base, "traffic", "square-32", tr)
    cell = spec.cell("c", _bench("leonardo", "square-32"), base=base)
    cfg = cell["config"]
    got = run.ask(cell, SIZES, n_iters=cfg["n_iters"], warmup=cfg["warmup"])
    want = reference.answer(cfg, [32], "incast", SIZES, n_iters=cfg["n_iters"],
                            warmup=cfg["warmup"], profile="square",
                            profile_args=tr["profile_args"], base=base, **KW)
    readings = check.compare(got, want)
    assert check.verdict(readings, tr["check"]["limits"]), readings
    # the envelope is live: under a steady aggressor the answer differs
    steady = reference.answer(cfg, [32], "incast", SIZES,
                              n_iters=cfg["n_iters"], warmup=cfg["warmup"],
                              base=base, **KW)
    assert [r["t_congested_s"] for r in steady] != [
        r["t_congested_s"] for r in want]
