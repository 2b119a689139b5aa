"""The benchmark's own files, checked on the CPU (no chip).

Every configuration, traffic mix, metric reader and part of the
reference (topology family, CC rule, envelope profile) loads; BENCHMARK.json
keeps to its character rules; a new traffic file is found by its name
alone; the step core's bytes match the kernel's operands; the reference
answers as the program does at a small size; and a run that finds no
TPU exits non-zero without printing a result.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from chipbench import questions, reference, roofline, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MiB = float(1 << 20)


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_every_file_loads(bench):
    for name in sorted(os.listdir(os.path.join(spec.HERE, "configs"))):
        cfg = spec.config(name[:-len(".json")])
        assert {"preset", "machine_nodes", "topology", "cc", "routing",
                "n_iters", "warmup"} <= set(cfg)
    for name in sorted(os.listdir(os.path.join(spec.HERE, "traffic"))):
        tr = spec.traffic(name[:-len(".json")])
        assert questions.Questions(tr, 1).sizes(0)
        assert set(tr["check"]["limits"]) == {"time_gap_steps", "ratio_gap",
                                              "count_mismatch"}
    for kind, load in (("metrics", spec.metric_reader),
                       ("families", spec.family), ("cc", spec.cc_rule),
                       ("profiles", lambda n: spec.profile(n).envelope)):
        for name in sorted(os.listdir(os.path.join(spec.HERE, kind))):
            if name.endswith(".py"):
                assert callable(load(name[:-len(".py")]))
    from repro.core import congestion

    for name in sorted(os.listdir(os.path.join(spec.HERE, "profiles"))):
        if name.endswith(".py"):
            program = spec.profile(name[:-len(".py")]).PROGRAM
            assert callable(getattr(congestion, program))
    for w in bench["workloads"]:
        cell = spec.cell(w["name"], bench)
        assert cell["end_to_end"] and cell["per_layer"]


def test_benchmark_names_and_units(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = ([m["name"] for m in metrics]
             + [c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [w["config"] for w in bench["workloads"]]
             + [w["traffic"] for w in bench["workloads"]]
             + [k for c in bench["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for key in ("end_to_end", "per_layer", "configs", "workloads"):
        entries = [e["name"] for e in bench[key]]
        assert len(entries) == len(set(entries)), key
    assert {m["name"] for m in bench["per_layer"]} <= {
        n[:-len(".py")] for n in os.listdir(os.path.join(spec.HERE,
                                                          "metrics"))}
    assert bench["paths"] == ["chipbench"]


def test_new_traffic_file_found_by_name(tmp_path):
    (tmp_path / "traffic").mkdir()
    mix = {"victim": "ring_allgather", "aggressor": "incast",
           "profile": "steady", "nodes": [32], "sizes": [16384, 65536, 4096],
           "check": {"questions": 1, "limits": {}}}
    (tmp_path / "traffic" / "incast-32.json").write_text(json.dumps(mix))
    assert spec.traffic("incast-32", base=str(tmp_path)) == mix
    seen = set()
    for seed in (1, 2 ** 31 + 5):
        qs = questions.Questions(mix, seed=seed)
        for k in range(8):
            assert sorted(qs.sizes(k)) == [4096, 16384, 65536]
            seen.add(qs.sizes(k))
        assert qs.sizes(3) == questions.Questions(mix, seed=seed).sizes(3)
        assert qs.warmup_sizes() == (4096.0,) * 3
    assert len(seen) > 1
    with pytest.raises(FileNotFoundError):
        spec.traffic("incast-32")


def test_step_core_bytes_match_kernel_operands():
    """At leonardo.incast-256's dims, the formula equals the bytes of the
    ``pallas_call``'s operands and results, counted at the padded dims
    the kernel is handed (the metric itself uses the real dims)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import fabric_step

    cfg = spec.config("leonardo")
    case = reference.build_case(cfg, 256, "incast")
    F, K, H = case.paths.shape
    L = len(case.caps) - 1
    assert (F, H, L) == (255, 8, 3907)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    args = (i32(F, H), f32(F), i32(F), f32(F), f32(L + 1), f32(L + 1),
            f32(L + 1), i32(L + 1), i32(L + 1), f32(), f32(), f32(), f32(),
            f32())
    jaxpr = jax.make_jaxpr(lambda *a: fabric_step.fabric_step_core(
        *a, n_src=128, n_sw=64, interpret=True))(*args)
    calls = [e for e in jaxpr.eqns[0].params["jaxpr"].eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    moved = sum(int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                for v in list(calls[0].invars) + list(calls[0].outvars))
    Fp = calls[0].invars[0].aval.shape[1]
    Lp = calls[0].invars[4].aval.shape[1]
    assert moved == roofline.step_core_bytes(Fp, H, Lp - 1)
    assert roofline.step_core_bytes(F, H, L) == 4 * (F * H + 5 * F
                                                     + 8 * (L + 1) + 5)


def test_peaks_refuse_unknown_device():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


@pytest.mark.parametrize("name,nodes,aggressor", [
    ("leonardo", 32, "incast"), ("cresco8", 32, "incast"),
    ("leonardo", 16, "alltoall")])
def test_reference_answers_as_the_program(name, nodes, aggressor):
    from chipbench import check
    from repro.core import bench as program
    from repro.core import congestion as cong
    from repro.core.fabric import systems

    cfg = spec.config(name)
    sizes = (40e3, 1.5 * MiB)
    got = program.run_grid(systems.get_system(cfg["preset"]), nodes,
                           "ring_allgather", aggressor, sizes,
                           (cong.steady(),), n_iters=cfg["n_iters"],
                           warmup=cfg["warmup"])
    want = reference.answer(cfg, [nodes], aggressor, sizes,
                            n_iters=cfg["n_iters"], warmup=cfg["warmup"],
                            chunk=2048, max_steps=200_000)
    got_n = check.compare(got, want)
    assert got_n["count_mismatch"] == 0
    assert got_n["time_gap_steps"] < 1e-3
    assert got_n["ratio_gap"] < 1e-4


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-m", "chipbench.run", "--workload",
                        "cresco8.incast-256", "--seed", str(2 ** 31 + 9),
                        "--seconds", "1", "--trace", "0"],
                       cwd=spec.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr
