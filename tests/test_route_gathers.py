"""The step's routing and signal gathers index only the shared candidate
table ``geom.paths``; each lane's chosen path is a mask over K
(``simulator._chosen``, DESIGN.md §13). The reference below is the
earlier formula: the candidate scores gathered at the (F, K, H) table,
the chosen path gathered per lane with ``take_along_axis`` and the link
state gathered at it. Both must give the same bits on every state leaf,
observer and engine output (the padded engines' goodput sum aside,
below)."""
import dataclasses
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.core import bench, congestion as cong
from repro.core.fabric import simulator as sim, systems
from repro.core.fabric.routing import N_POLICIES

SYSTEM = "nanjing_ecmp"          # 1 and 4 candidate paths among its flows
SIZES = (32768.0, float(1 << 20), float(1 << 19))
# one lane per routing policy, then adaptive again
POLICIES = tuple(range(N_POLICIES)) + (N_POLICIES - 2,)
ENGINE_KW = dict(chunk=32, max_chunks=6, stride=8)
GATHER_SCOPES = ("route", "signals", "queue_delay")


def _reference_chosen(table, choice):
    idx = choice.reshape(choice.shape + (1,) * (table.ndim - 1))
    return jnp.take_along_axis(table, idx, axis=1)[:, 0]


def _reference_hop_max(link_vals, geom, pad=None):
    assert pad is None               # the earlier step masked at plinks
    return jnp.max(link_vals[geom.paths], axis=2)


def _reference_path_max(link_vals, geom, choice):
    plinks = _reference_chosen(geom.paths, choice)
    valid = plinks < geom.L
    if link_vals.dtype == jnp.bool_:
        return jnp.any(link_vals[plinks] & valid, axis=1)
    return jnp.max(jnp.where(valid, link_vals[plinks], 0.0), axis=1)


@pytest.fixture
def use_reference(monkeypatch):
    def install():
        monkeypatch.setattr(sim, "_chosen", _reference_chosen)
        monkeypatch.setattr(sim, "_path_max", _reference_path_max)
        monkeypatch.setattr(sim, "_hop_max", _reference_hop_max)
    return install


def _case(n_nodes):
    return bench.build_case(systems.get_system(SYSTEM), n_nodes,
                            "ring_allgather", "incast", policy_tables=True)


def _lanes(case, n_flows=None):
    """Baseline and steady-aggressor lanes at each size, one routing
    policy and one CC kind per lane."""
    cells = [(v, prof) for v in SIZES
             for prof in (cong.no_congestion(), cong.steady())]
    params = sim.stack_params([case.cell_params(v, prof, 2e-6,
                                                n_flows=n_flows)
                               for v, prof in cells])
    n = len(cells)
    return dataclasses.replace(
        params, policy=jnp.asarray(POLICIES, jnp.int32),
        kind=jnp.arange(n, dtype=jnp.int32) % 4,
        flowlet_gap_s=jnp.full((n,), 20e-6, jnp.float32))


def _fresh(entry, static):
    """``entry``'s jitted body traced anew: a new function object has no
    entry in jax's trace caches, so a patched helper is seen."""
    def engine(*args, **kw):
        return entry.__wrapped__(*args, **kw)

    return jax.jit(engine, static_argnames=static)


def _engine_args():
    case = _case(8)
    return case.geom, _lanes(case), jnp.asarray(2, jnp.int32)


def _hetero_args(nodes=(8, 16)):
    cases = [_case(n) for n in nodes]
    dims, geoms = bench.bucket_stack([c.geom for c in cases])
    params = sim.stack_params([_lanes(c, dims.n_flows) for c in cases])
    return geoms, params, jnp.asarray(2, jnp.int32)


def _one_cell_args():
    """One topology cell, as each device of the sharded sweep runs it."""
    return _hetero_args((16,))


STATIC = ("chunk", "max_chunks", "stride", "backend", "metrics",
          "with_trace")


def _run_steps(backend):
    geom, params, _ = _engine_args()
    one = jax.vmap(lambda p, s: sim._step_impl(geom, p, s, True, backend))
    state = jax.vmap(lambda p: sim.init_state(geom, p, metrics=True))(params)
    steps, wrapped = [], False
    run = jax.jit(one)
    for _ in range(40):
        state, gp, aux = run(params, state)
        wrapped |= bool(np.any(np.asarray(aux["wrap"])[:, 0]))
        steps.append(jax.tree_util.tree_map(np.asarray, (state, gp, aux)))
    return steps, wrapped


def _assert_same(a, b):
    la, ta = jax.tree_util.tree_flatten_with_path(a)
    lb, tb = jax.tree_util.tree_flatten_with_path(b)
    assert ta == tb
    for (path, x), (_, y) in zip(la, lb):
        np.testing.assert_array_equal(x, y, err_msg=str(path))


def test_lanes_cover_short_path_sets_and_iteration_drain():
    geom, params, _ = _engine_args()
    n_paths = np.asarray(geom.n_paths)
    assert n_paths.min() < geom.paths.shape[1] == n_paths.max()
    assert set(np.asarray(params.policy)) == set(range(N_POLICIES))
    assert np.all(np.asarray(params.iter_drain) < 1.0)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_steps_bit_identical_to_per_lane_gathers(backend, use_reference):
    new, wrapped = _run_steps(backend)
    assert wrapped                 # the iteration drain ran
    use_reference()
    old, _ = _run_steps(backend)
    for i, (a, b) in enumerate(zip(new, old)):
        try:
            _assert_same(a, b)
        except AssertionError as e:
            raise AssertionError(f"step {i}: {e}") from None


# The engine sums the victim goodput (``trace``) over flows in an order
# XLA:CPU picks per fusion: on the padded geometries below, the earlier
# formula's own step and engine already differ there by one ulp
# (1.8749999e11 against 1.875e11 on lane (1, 3) of the two-cell grid), so
# that one sum is held to one ulp there.
@pytest.mark.parametrize("entry,args,trace_ulp", [
    ("_run_cells_jit", _engine_args, 0),
    ("_run_cells_hetero_jit", _hetero_args, 1),
    ("_run_cells_hetero_jit", _one_cell_args, 1),
], ids=["run_cells", "run_cells_hetero", "run_cells_hetero_one_cell"])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_engine_outputs_bit_identical_to_per_lane_gathers(
        entry, args, trace_ulp, backend, use_reference):
    a = args()
    kw = dict(ENGINE_KW, backend=backend, metrics=True)
    new = _fresh(getattr(sim, entry), STATIC)(*a, **kw)
    use_reference()
    old = _fresh(getattr(sim, entry), STATIC)(*a, **kw)
    assert np.all(np.asarray(new["it"])[..., 0] >= 1)
    np.testing.assert_array_max_ulp(np.asarray(new.pop("trace")),
                                    np.asarray(old.pop("trace")), trace_ulp)
    _assert_same(new, old)


def _gathers(jaxpr, stack=""):
    """(name stack, dimension numbers) of every gather in ``jaxpr`` and
    the jaxprs nested in it (loop bodies, jitted helpers)."""
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "gather":
            yield here, eqn.params["dimension_numbers"]
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    yield from _gathers(sub, here)


def _per_lane_gathers(entry, args):
    """Scopes of the routing and signal gathers whose indices carry the
    lane axis, in the batched engine as traced."""
    engine = partial(getattr(sim, entry).__wrapped__, **ENGINE_KW,
                     backend="ref")
    found = []
    for stack, dn in _gathers(jax.make_jaxpr(engine)(*args()).jaxpr):
        scope = set(re.split(r"[/()]", stack)) & set(GATHER_SCOPES)
        if scope and dn.start_indices_batching_dims:
            found.extend(scope)
    return found


@pytest.mark.parametrize("entry,args", [
    ("_run_cells_jit", _engine_args),
    ("_run_cells_hetero_jit", _one_cell_args),
], ids=["run_cells", "run_cells_hetero_one_cell"])
def test_step_gathers_take_no_per_lane_indices(entry, args, use_reference):
    assert _per_lane_gathers(entry, args) == []
    use_reference()                  # the check sees the earlier formula's
    assert set(_per_lane_gathers(entry, args)) == set(GATHER_SCOPES)
