"""Named scopes of the engine and host spans of the grid path (DESIGN.md
§18): the scopes are metadata only, every op of a compiled engine entry
carries one, and the grid path's spans open in order without changing a
result."""
import contextlib
import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bench, congestion as cong, spans
from repro.core.fabric import simulator as sim, systems

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
GRID = ("ring_allgather", "incast", [1 << 20], [cong.steady()])
GRID_KW = dict(n_iters=6, warmup=2)
ENGINE_KW = dict(chunk=32, max_chunks=3, stride=8, backend="ref")


def _engine_args():
    case = bench.build_case(systems.get_system("cresco8"), 8,
                            "ring_allgather", "incast")
    _, params = bench.grid_params(case, [32768.0, 1 << 20],
                                  [cong.steady()])
    return case.geom, params, jnp.asarray(2, jnp.int32)


def _op_names(hlo_text: str):
    """(instruction, op_name) of every instruction that carries one and
    runs as an op: fused computations and scalar reducers run inside
    another instruction, and parameters are no ops."""
    found, runs = [], True
    for line in hlo_text.splitlines():
        if line and not line.startswith(" ") and line.endswith("{"):
            runs = not (line.startswith("%fused_computation") or re.match(
                r"\S+ \((\S+: \w+\[\], )*\S+: \w+\[\]\) -> ", line))
            continue
        m = re.match(r'\s+(?:ROOT )?(%\S+) = \S+ ([\w-]+)\(.*'
                     r'op_name="([^"]*)"', line)
        if runs and m and m.group(2) != "parameter":
            found.append((m.group(1), m.group(3)))
    return found


def _components(op_name: str):
    """The path of an op_name with transform wrappers (``vmap(x)``)
    taken off each component."""
    out = []
    for part in op_name.split("/"):
        while re.fullmatch(r"\w+\((.*)\)", part):
            part = re.fullmatch(r"\w+\((.*)\)", part).group(1)
        out.append(part)
    return out


def _strip(hlo_text: str) -> str:
    """A compiled module's text without its debug tables and per-op
    metadata."""
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(.+\n)*", "\n", hlo_text)
    return re.sub(r",? metadata=\{[^}]*\}", "", text)


@contextlib.contextmanager
def _no_scopes(monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    yield
    monkeypatch.undo()


def _fresh_engine():
    """The batched engine entry, traced anew: a new function object has
    no entry in jax's trace caches."""
    def engine(*args, **kw):
        return sim._run_cells_jit.__wrapped__(*args, **kw)

    return jax.jit(engine, static_argnames=tuple(ENGINE_KW)
                   + ("metrics", "with_trace"))


@pytest.fixture
def no_persistent_cache():
    """Compile anew: the persistent cache's key leaves out the scopes, so
    a scoped and an unscoped engine share their entry."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.mark.parametrize("metrics", [False, True])
def test_every_engine_op_carries_a_documented_scope(metrics,
                                                   no_persistent_cache):
    text = _fresh_engine().lower(*_engine_args(), metrics=metrics,
                                 **ENGINE_KW).compile().as_text()
    named = _op_names(text)
    assert named
    seen = set()
    for inst, op_name in named:
        path = _components(op_name)
        assert sim.ENGINE_SCOPE in path, (inst, op_name)
        if sim.STEP_SCOPE in path:
            section = path[path.index(sim.STEP_SCOPE) + 1]
            assert section in sim.STEP_SECTIONS, (inst, op_name)
            seen.add(section)
    want = set(sim.STEP_SECTIONS) - ({"metrics_carry"} if not metrics
                                     else set())
    assert seen == want


def test_scopes_leave_the_compiled_engine_and_its_results_unchanged(
        monkeypatch, no_persistent_cache):
    args = _engine_args()
    scoped = _fresh_engine().lower(*args, **ENGINE_KW).compile()
    with _no_scopes(monkeypatch):
        plain = _fresh_engine().lower(*args, **ENGINE_KW).compile()
    assert "engine_loop" in scoped.as_text()
    assert "engine_loop" not in plain.as_text()
    assert _strip(scoped.as_text()) == _strip(plain.as_text())
    a, b = scoped(*args), plain(*args)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


def test_recorder_is_off_unless_installed_and_bounded(monkeypatch):
    monkeypatch.setattr(spans, "_LIMIT", 3)
    with spans.span(spans.MARSHAL):
        pass
    assert spans._recorder is None
    with spans.recording() as rec:
        for _ in range(5):
            with spans.span(spans.DISPATCH):
                pass
        with spans.recording() as inner:
            with spans.span(spans.MARSHAL):
                pass
        assert [n for n, _, _ in inner] == [spans.MARSHAL]
        assert spans._recorder is rec
    assert spans._recorder is None
    assert [n for n, _, _ in rec] == [spans.DISPATCH] * 3
    assert all(0 < s <= e for _, s, e in rec)


def _order(rec):
    return [n for n, _, _ in sorted(rec, key=lambda r: r[1])]


def _rows(results):
    return [(r.system, r.n_nodes, r.vector_bytes, r.t_uncongested_s,
             r.t_congested_s, r.ratio, r.n_iters) for r in results]


def test_run_grid_spans_in_order_and_results_unchanged_under_profiler(
        tmp_path):
    system = systems.get_system("cresco8")
    plain = bench.run_grid(system, 8, *GRID, **GRID_KW)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.recording() as rec:
            traced = bench.run_grid(system, 8, *GRID, **GRID_KW)
    finally:
        jax.profiler.stop_trace()
    assert _rows(traced) == _rows(plain)          # bit-identical floats
    assert _order(rec) == [spans.BUILD_CASE, spans.GRID_PARAMS,
                           spans.DISPATCH, spans.MARSHAL]
    from jax.profiler import ProfileData

    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    names = [e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("fabric.")]
    assert names == [spans.BUILD_CASE, spans.GRID_PARAMS, spans.DISPATCH,
                     spans.MARSHAL]


CHILD = r"""
import json, sys
import jax
from repro.core import bench, congestion as cong, spans
from repro.launch.mesh import make_sweep_mesh
cells = [("cresco8", 8), ("cresco8", 12)]
grid = ("ring_allgather", "incast", [1 << 20], [cong.steady()])
rows = lambda rs: [[r.t_uncongested_s, r.t_congested_s, r.ratio,
                    list(r.n_iters)] for r in rs]
plain = bench.run_scale_grid(cells, *grid, n_iters=6, warmup=2)
with spans.recording() as rec:
    sharded = bench.run_scale_grid(cells, *grid, n_iters=6, warmup=2,
                                   mesh=make_sweep_mesh())
print(json.dumps({"devices": len(jax.devices()),
                  "order": [n for n, _, _ in sorted(rec,
                                                    key=lambda r: r[1])],
                  "same": rows(plain) == rows(sharded)}))
"""


def test_scale_grid_spans_per_shard_on_a_two_device_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run([sys.executable, "-c", CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["devices"] == 2
    assert got["same"]
    assert got["order"] == [spans.BUILD_CASE, spans.GRID_PARAMS,
                            spans.DISPATCH, spans.SHARD, spans.SHARD,
                            spans.MARSHAL]
