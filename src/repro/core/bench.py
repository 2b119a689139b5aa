"""The paper's measurement protocol (§III): run the victim collective for a
fixed number of iterations under a congestion profile, discard warmup,
report mean iteration time and the uncongested/congested ratio.

The paper uses 1000 iterations / 100 warmup on real fabrics; the fluid
simulator converges much faster (no per-packet noise), so the default here
is 60/10 — scaled, and noted in EXPERIMENTS.md.

Two entry points:

* :func:`run_point` — one heatmap cell (baseline + congested, batched as a
  2-cell grid internally).
* :func:`run_grid` — a whole (vector size x profile x baseline/congested)
  grid on ONE flow set, executed by a single ``jit(vmap(...))`` call
  (simulator.run_cells). This is the fast path for the paper's Figs. 5-8
  sweeps: one compile, all cells advance in lockstep.
"""
from __future__ import annotations

import copy
import dataclasses
from typing import List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro.core import congestion as cong
from repro.core import spans
from repro.core import traffic
from repro.core.fabric.simulator import (TDONE_SLOTS, FabricGeometry,
                                         SimParams, _drop_warmup,
                                         bucket_dims, check_iter_budget,
                                         make_geometry, make_params,
                                         pad_geometry, run_cell, run_cells,
                                         run_cells_hetero, stack_geometries,
                                         stack_params, summarize)
from repro.core.fabric.routing import splitmix64
from repro.core.fabric.systems import (SystemPreset, default_policy,
                                       get_system)

# One (system, n_nodes) cell of a scale-batched sweep; systems may be
# preset objects or registry names.
ScaleCell = Tuple[Union[str, SystemPreset], int]


@dataclasses.dataclass
class BenchResult:
    system: str
    n_nodes: int
    victim: str
    aggressor: str
    profile: str
    vector_bytes: float
    t_uncongested_s: float
    t_congested_s: float
    ratio: float  # uncongested / congested (paper Fig. 5-8; higher = better)
    victim_goodput_gbps: float
    n_iters: tuple
    # per-job mean iteration times of the congested cell, for multi-job
    # mixes: ((job_name, t_mean_s, n_done), ...) over jobs that closed
    # at least one program iteration
    job_times: tuple = ()
    # False when either lane finished inside its warmup window: the
    # reported means are then last-iteration estimates, not steady state
    warmup_ok: bool = True
    # did-not-finish: a lane completed ZERO iterations within the step
    # budget — times/ratio are NaN and the cell must not be scored
    dnf: bool = False


def victim_label(victim_coll: str, phased: bool) -> str:
    """The reported/cached victim column: the collective kind plus a
    '+phased' marker when the primary job runs its step schedule. The
    single source of truth for result rows AND scenario cache keys."""
    return victim_coll + ("+phased" if phased else "")


def resolve_victim_label(victim_coll: str, phased: bool, jobs=None) -> str:
    """Victim label as build_case resolves it for a (victim, phased,
    jobs) request — scenario cache keys (benchmarks.common) call this so
    the key and the cached row cannot drift apart."""
    if jobs:
        return victim_label(victim_coll or jobs[0].collective,
                            bool(jobs[0].phased))
    return victim_label(victim_coll, phased)


def mean_iter_time(res, lat: float) -> float:
    """Reported per-iteration time of one summarized run: mean simulated
    iteration + analytic per-step latency + mean queueing delay (shared
    by the grid runners and mitigation.search). A run that completed ZERO
    iterations is NaN — an explicit did-not-finish the callers must flag
    (BenchResult.dnf / CellRun.dnf), never a silent ``inf`` that poisons
    downstream ratios and Pareto scores."""
    if len(res.iter_times) == 0:
        return float("nan")
    return float(np.mean(res.iter_times)) + lat + res.mean_qdelay_s


_TOPO_CACHE: dict = {}


def _fn_fingerprint(fn) -> tuple:
    """Identity-relevant fingerprint of a topology builder: bytecode,
    constants (nested code objects repr to a stable per-object string),
    closure values and defaults — so a SystemPreset re-registered under
    the same name with a different builder cannot hit a stale entry."""
    code = getattr(fn, "__code__", None)
    if code is None:
        return (repr(fn),)
    consts = tuple(
        c if isinstance(c, (int, float, str, bytes, bool, type(None)))
        else repr(c) for c in code.co_consts)
    closure = tuple(repr(c.cell_contents)
                    for c in (getattr(fn, "__closure__", None) or ()))
    return (code.co_code, consts, closure, repr(fn.__defaults__))


def _topo_cache_key(system: SystemPreset, n: int) -> tuple:
    return (system.name, system.fabric, system.machine_nodes,
            system.k_max, system.static_routing,
            _fn_fingerprint(system.make_topology), n)


def clear_topology_cache() -> None:
    """Drop every cached machine topology (tests that mutate presets)."""
    _TOPO_CACHE.clear()


def machine_topology(system: SystemPreset, n_nodes: int = 0):
    """Full-machine topology (cached — reused across heatmap cells).
    Testbed systems (``machine_nodes == 0``) are built at the allocation
    size instead, so scale sweeps over them actually scale the fabric.
    The cache keys on the preset's identity-relevant fields plus a
    fingerprint of the builder itself, NOT just the name: two presets
    sharing a name but differing in fabric/size/builder get distinct
    entries."""
    n = system.machine_nodes or (n_nodes or 8)
    key = _topo_cache_key(system, n)
    if key not in _TOPO_CACHE:
        _TOPO_CACHE[key] = system.make_topology(n)
    return _TOPO_CACHE[key]


def allocate(system: SystemPreset, n_nodes: int, seed: int = 7) -> np.ndarray:
    """Model a production batch-scheduler allocation: a scattered sample of
    the machine (the paper: 'we cannot fully control job allocations' —
    busy TOP500 systems hand out fragmented node sets). The interleaved
    victim/aggressor split then alternates within and across switches —
    the paper's maximal-sharing design (§III-A).

    ``seed`` and ``n_nodes`` mix through the pinned splitmix64, so
    distinct (seed, n_nodes) pairs draw unrelated allocations — the old
    additive ``seed + n_nodes`` seeding made (7, 8) and (8, 7) identical
    draws (and neighboring scales near-copies of each other)."""
    machine = system.machine_nodes or n_nodes
    if n_nodes >= machine:
        return np.arange(machine)
    mixed = splitmix64((np.uint64(seed) << np.uint64(32))
                       | np.uint64(np.uint32(n_nodes)))
    rng = np.random.RandomState(int(mixed & np.uint64(0xFFFFFFFF)))
    return np.sort(rng.choice(machine, size=n_nodes, replace=False))


# --------------------------------------------------------------------------
# dt selection
# --------------------------------------------------------------------------

# power-of-two microsecond ladder: neighboring grid cells snap to shared dt
# values, so batched cells stay numerically comparable and JIT caches hit
# across sweeps even when dt were a compile-time constant.
DT_LADDER_S = tuple(2.0 ** k * 1e-6 for k in range(8))  # 1us .. 128us


def quantize_dt(dt_raw: float) -> float:
    """Snap down to the nearest ladder step (finer dt = more accurate)."""
    for dt in reversed(DT_LADDER_S):
        if dt <= dt_raw:
            return dt
    return DT_LADDER_S[0]


def choose_dt(topo, n_victims: int, vector_bytes: float, lat: float,
              n_phases: int = 1) -> float:
    """dt sized so one uncongested iteration spans ~100 steps — and, for
    phased programs, so each of the ``n_phases`` barrier-gated phases
    spans at least ~8 steps (phase advance is quantized to dt, so a
    too-coarse dt would inflate every phase by up to one step)."""
    per_flow = vector_bytes / max(n_victims, 1)
    t_est = max(per_flow / (topo.caps.max()), 2e-6) * 2 + lat
    steps = max(100, 8 * int(n_phases))
    return quantize_dt(float(np.clip(t_est / steps, 1e-6, 200e-6)))


# --------------------------------------------------------------------------
# Case construction: one flow set, reused across a grid of cells
# --------------------------------------------------------------------------


@dataclasses.dataclass
class GridCase:
    """One (system, allocation, traffic program) experiment; the
    unit-vector flow program to be scaled per cell (sweeping jobs' bytes
    are linear in the swept vector size; background jobs keep their own
    fixed volume)."""

    system: SystemPreset
    n_nodes: int
    victim_coll: str
    aggr_coll: str
    topo: object
    geom: FabricGeometry
    unit_bytes: np.ndarray  # (F,) per-flow bytes at vector_bytes == 1.0
    is_victim: np.ndarray  # (F,)
    host_caps: np.ndarray  # (F,)
    n_victims: int
    sweep_mask: np.ndarray = None  # (F,) flows whose bytes sweep
    job_names: List[str] = None
    max_phases: int = 1
    primary_phased: bool = False  # job 0 runs a phased step schedule
    # traced routing-policy id for this case's cells (the system default;
    # mitigation/search overrides it per candidate)
    policy: int = 0

    def __post_init__(self):
        if self.sweep_mask is None:
            self.sweep_mask = np.asarray(self.is_victim, bool)
        if self.job_names is None:
            self.job_names = ["victim", "aggressor"]

    def cell_params(self, vector_bytes: float, profile: cong.Profile,
                    dt: float, n_flows: Optional[int] = None,
                    with_fault_table: bool = False) -> SimParams:
        """Per-cell traced params; ``n_flows`` pads the flow axis to a
        geometry-bucket width (pad flows: 0 bytes — never alive — and a
        positive dummy host cap so no divide ever sees 0).

        ``with_fault_table=True`` forces the inert all-``none`` fault
        table onto lanes whose profile carries no events — stacked lanes
        of one grid must share a pytree structure, and the inert table is
        bit-identical to running without one (DESIGN.md §16)."""
        bpi = np.where(self.sweep_mask, self.unit_bytes * vector_bytes,
                       self.unit_bytes)
        host_caps = self.host_caps
        if n_flows is not None and n_flows > len(bpi):
            bpi = traffic.pad_rows(bpi, n_flows, 0.0)
            host_caps = traffic.pad_rows(host_caps, n_flows, 1.0)
        fault = profile.fault_params()
        if fault is None and with_fault_table:
            fault = cong.no_fault_table()
        # intra-node stage capacity: a fraction of the fastest NIC on the
        # case (inf = stage inert; the geometry flag gates the trace)
        node_cap = np.inf if profile.node_cap_frac <= 0 else \
            float(profile.node_cap_frac) * float(np.max(self.host_caps))
        return make_params(self.system.cc, dt=dt, bytes_per_iter=bpi,
                           host_caps=host_caps, env=profile.params(),
                           policy=self.policy, fault=fault,
                           node_cap=node_cap)

    def lat(self) -> float:
        return cong.latency_model(self.victim_coll, self.n_victims)


def build_case(system: SystemPreset, n_nodes: int, victim_coll: str,
               aggr_coll: str, topo=None,
               nodes: Optional[np.ndarray] = None, *,
               phased: bool = False,
               jobs: Optional[Sequence[traffic.JobSpec]] = None,
               policy_tables: bool = False,
               intra_node: bool = False,
               seed: int = 7) -> GridCase:
    """Build the flow program + geometry once for a whole grid of cells.

    Default: the paper's two-job victim/aggressor split. ``phased=True``
    lowers the victim's step schedule instead of flattening it.
    ``jobs`` replaces the split with an explicit multi-job program — jobs
    without nodes get an interleaved share of the allocation, and jobs
    with ``sweep_bytes`` are compiled at unit vector size and scaled per
    cell. ``policy_tables=True`` additionally computes the ECMP/NSLB
    static tables so traced policies can cross-select them (the
    mitigation search needs this; plain sweeps only dispatch the policy
    matching ``fixed_choice`` and skip the host-side assignment cost).
    """
    if topo is None:
        topo = machine_topology(system, n_nodes)
    if nodes is None:
        nodes = allocate(system, n_nodes, seed=seed)
    if jobs is not None:
        jobs = traffic.split_nodes(nodes, list(jobs))
        jobs = [dataclasses.replace(j, vector_bytes=1.0)
                if j.sweep_bytes and not j.endless else j for j in jobs]
        flows = cong.build_program_flowset(
            topo, jobs, routing_mode=system.static_routing,
            k_max=system.k_max, policy_tables=policy_tables)
        # caller-provided labels win (scenario cache keys); fall back to
        # the program's own names
        victim_coll = victim_coll or jobs[0].collective
        aggr_coll = aggr_coll or "+".join(j.name for j in jobs[1:])
        n_victims = len(jobs[0].nodes)
    else:
        # the paper's §III-A interleaved split (applied even with no
        # aggressor collective, so baseline and congested cells share
        # the victim set)
        vidx, aidx = cong.interleaved_split(n_nodes)
        victims, aggressors = nodes[vidx], nodes[aidx]
        flows = cong.build_flowset(topo, victims, aggressors, victim_coll,
                                   aggr_coll, 1.0,
                                   routing_mode=system.static_routing,
                                   k_max=system.k_max, phased=phased,
                                   policy_tables=policy_tables)
        n_victims = len(victims)
    geom = make_geometry(topo, flows, intra_node=intra_node)
    return GridCase(system=system, n_nodes=n_nodes, victim_coll=victim_coll,
                    aggr_coll=aggr_coll, topo=topo, geom=geom,
                    unit_bytes=flows.bytes_per_iter.copy(),
                    is_victim=flows.is_victim, host_caps=flows.host_caps,
                    n_victims=n_victims,
                    sweep_mask=np.asarray(flows.sweep_mask, bool),
                    job_names=list(flows.job_names),
                    max_phases=int(np.max(flows.n_phases)),
                    primary_phased=bool(jobs[0].phased) if jobs is not None
                    else phased,
                    policy=default_policy(system))


# --------------------------------------------------------------------------
# Batched grid runner (the vmap hot path)
# --------------------------------------------------------------------------


def _job_times(out, case: GridCase, *, n_iters, warmup, cell) -> tuple:
    """Per-job mean iteration times of one cell (jobs that closed at
    least one program iteration; endless aggressors never do). Reads
    only the tiny it/t_done outputs — no trace-buffer transfer."""
    it = np.asarray(out["it"])
    td = np.asarray(out["t_done"])
    if cell is not None:
        it, td = it[cell], td[cell]
    rows = []
    for ji, name in enumerate(case.job_names):
        n_done = min(int(it[ji]), n_iters, TDONE_SLOTS)
        if n_done <= 0:
            continue
        times = np.diff(np.concatenate([[0.0], td[ji][:n_done]]))
        times, _ = _drop_warmup(times, n_done, warmup)
        if len(times):
            rows.append((name, float(np.mean(times)), n_done))
    return tuple(rows)


def _cell_dts(case: GridCase, sizes: Sequence[float], n_profiles: int,
              dt: Optional[float], lat: float) -> List[float]:
    """One dt per sub-cell (size-major, baseline + profiles per size),
    chosen per cell on the shared power-of-two ladder."""
    dts: List[float] = []
    for v in sizes:
        cell_dt = dt if dt is not None else choose_dt(
            case.topo, case.n_victims, float(v), lat,
            n_phases=case.max_phases)
        dts.extend([cell_dt] * (1 + n_profiles))
    return dts


def grid_params(case: GridCase, sizes: Sequence[float],
                profiles: Sequence[cong.Profile], dt: Optional[float] = None,
                ) -> Tuple[List[float], SimParams]:
    """One grid's lanes: per size a baseline (aggressors/background jobs
    off) then one lane per profile, stacked into one SimParams. Returns
    the per-lane dts and the stack. Any faulted lane forces the inert
    fault table on its siblings (one pytree structure per stack)."""
    with_ft = cong.needs_fault_table(profiles)
    dts = _cell_dts(case, sizes, len(profiles), dt, case.lat())
    cells = [(float(v), prof) for v in sizes
             for prof in [cong.no_congestion()] + list(profiles)]
    return dts, stack_params([case.cell_params(v, prof, d,
                                               with_fault_table=with_ft)
                              for (v, prof), d in zip(cells, dts)])


def _grid_results(case: GridCase, out: dict, sizes: Sequence[float],
                  profiles: Sequence[cong.Profile], dts: Sequence[float], *,
                  n_iters: int, warmup: int, chunk: int, stride: int,
                  cell_prefix: tuple = ()) -> List[BenchResult]:
    """Marshal one case's (size x baseline/profile) sub-cells out of a
    batched run. ``cell_prefix`` indexes the leading batch axes in front
    of the sub-cell axis (run_cells_hetero adds a topology-cell axis)."""
    lat = case.lat()
    per_prof = 1 + len(profiles)
    results = []
    for si, v in enumerate(sizes):
        base_i = si * per_prof
        base = summarize(out, n_iters=n_iters, warmup=warmup, dt=dts[base_i],
                         chunk=chunk, stride=stride,
                         cell=cell_prefix + (base_i,))
        t_u = mean_iter_time(base, lat)
        for pi, prof in enumerate(profiles):
            ci = base_i + 1 + pi
            res = summarize(out, n_iters=n_iters, warmup=warmup, dt=dts[ci],
                            chunk=chunk, stride=stride,
                            cell=cell_prefix + (ci,))
            t_c = mean_iter_time(res, lat)
            dnf = base.n_done == 0 or res.n_done == 0
            results.append(BenchResult(
                system=case.system.name, n_nodes=case.n_nodes,
                victim=victim_label(case.victim_coll, case.primary_phased),
                aggressor=case.aggr_coll or "none", profile=prof.label(),
                vector_bytes=float(v), t_uncongested_s=t_u,
                t_congested_s=t_c,
                ratio=float("nan") if dnf
                else (t_u / t_c if t_c > 0 else 0.0),
                victim_goodput_gbps=float(
                    np.mean(res.victim_rate_trace[-200:]) * 8 / 1e9)
                if len(res.victim_rate_trace) else 0.0,
                n_iters=(base.n_done, res.n_done),
                job_times=_job_times(out, case, n_iters=n_iters,
                                     warmup=warmup,
                                     cell=cell_prefix + (ci,)),
                warmup_ok=base.warmup_ok and res.warmup_ok,
                dnf=dnf,
            ))
    return results


def _resolve_launcher(mesh, launcher, shard_axis: str = "cell"):
    """Launcher resolution shared by the grid runners and the mitigation
    search: an explicit ``launcher`` callable wins; a ``mesh`` alone gets
    launch.sweep's per-device dispatcher over ``shard_axis`` (imported
    lazily — core never depends on the launch layer at import time)."""
    if launcher is not None or mesh is None:
        return launcher
    from repro.launch.sweep import device_launcher
    return device_launcher(mesh, shard_axis=shard_axis)


def run_grid(system: Union[SystemPreset, Sequence[ScaleCell]], n_nodes: int,
             victim_coll: str, aggr_coll: str, sizes: Sequence[float],
             profiles: Sequence[cong.Profile], *, n_iters: int = 60,
             warmup: int = 10, dt: Optional[float] = None,
             max_steps: int = 200_000, chunk: int = 2048,
             trace_stride: int = 8, phased: bool = False,
             jobs: Optional[Sequence[traffic.JobSpec]] = None,
             mesh=None, launcher=None,
             ) -> List[BenchResult]:
    """All (vector size x profile) cells of one experiment in a single
    batched call: a per-size baseline (aggressors/background jobs off)
    plus one congested cell per profile, sharing one FlowSet/geometry and
    one compile. ``phased``/``jobs`` select the traffic program (see
    build_case); per-job iteration times ride along in each result.

    ``system`` may also be a list of ``(system, n_nodes)`` cells —
    heterogeneous topologies and scales. Those route through the
    scale-batched engine (:func:`run_scale_grid`): geometries are padded
    to bucket shapes and stacked, so the whole cross-scale sweep costs
    one compile per bucket instead of one per scale. ``n_nodes`` is
    ignored in that mode.

    ``mesh`` (or an explicit ``launcher``) shards the batched call
    across devices via the sharded sweep launcher (launch/sweep.py);
    single-system grids reroute through the scale-batched path, whose
    bucket padding is provably inert, so sharded and plain runs stay
    bit-identical."""
    if not isinstance(system, SystemPreset) or mesh is not None \
            or launcher is not None:
        cells = system if not isinstance(system, SystemPreset) \
            else [(system, n_nodes)]
        return run_scale_grid(cells, victim_coll, aggr_coll, sizes,
                              profiles, n_iters=n_iters, warmup=warmup,
                              dt=dt, max_steps=max_steps, chunk=chunk,
                              trace_stride=trace_stride, phased=phased,
                              jobs=jobs, mesh=mesh, launcher=launcher)
    check_iter_budget(n_iters)
    # any node-capped lane arms the intra-node stage for the whole case
    # (inert at inf)
    with spans.span(spans.BUILD_CASE):
        case = build_case(system, n_nodes, victim_coll, aggr_coll,
                          phased=phased, jobs=jobs,
                          intra_node=any(p.node_cap_frac > 0
                                         for p in profiles))
    with spans.span(spans.GRID_PARAMS):
        dts, params = grid_params(case, sizes, profiles, dt)
    max_chunks = -(-max_steps // chunk)
    with spans.span(spans.DISPATCH):
        out = run_cells(case.geom, params, jnp.asarray(n_iters, jnp.int32),
                        chunk=chunk, max_chunks=max_chunks,
                        stride=trace_stride)
    with spans.span(spans.MARSHAL):
        return _grid_results(case, out, sizes, profiles, dts,
                             n_iters=n_iters, warmup=warmup, chunk=chunk,
                             stride=trace_stride)


# --------------------------------------------------------------------------
# Scale-batched grids: heterogeneous (system, n_nodes) cells in one vmap
# --------------------------------------------------------------------------


def _round_pow2(x: int) -> int:
    """Bucket-size policy: round every geometry dim up to a power of two
    so different cell sets resolve to the same padded shape and the JIT
    cache hits across sweeps (DESIGN.md §11)."""
    return 1 << max(0, int(x) - 1).bit_length()


def bucket_stack(geoms: Sequence[FabricGeometry]):
    """Pad geometries to their shared power-of-two GeometryDims bucket
    and stack them for run_cells_hetero — THE bucket policy, shared by
    run_scale_grid and mitigation.search.run_candidates (one place, so
    the two paths cannot diverge on which compiles they reuse). Returns
    ``(dims, stacked)``."""
    dims = bucket_dims(geoms, round_up=_round_pow2)
    return dims, stack_geometries([pad_geometry(g, dims) for g in geoms])


@dataclasses.dataclass
class PendingGrid:
    """A dispatched (but not yet marshalled) scale grid. ``launch_scale_
    grid`` returns immediately after the async device dispatch; calling
    :meth:`results` blocks on the outputs and marshals them — so several
    grids can be launched back-to-back and their host-side result
    assembly overlaps the device compute of the grids still in flight
    (the sweep launcher's async pipeline)."""

    cases: List[GridCase]
    out: object  # dict-like of batched run outputs (possibly lazy)
    sizes: tuple
    profiles: tuple
    all_dts: List[List[float]]
    n_iters: int
    warmup: int
    chunk: int
    stride: int

    def results(self) -> List[BenchResult]:
        with spans.span(spans.MARSHAL):
            return [r for k, case in enumerate(self.cases)
                    for r in _grid_results(case, self.out, self.sizes,
                                           self.profiles, self.all_dts[k],
                                           n_iters=self.n_iters,
                                           warmup=self.warmup,
                                           chunk=self.chunk,
                                           stride=self.stride,
                                           cell_prefix=(k,))]


def launch_scale_grid(cells: Sequence[ScaleCell], victim_coll: str,
                      aggr_coll: str, sizes: Sequence[float],
                      profiles: Sequence[cong.Profile], *, n_iters: int = 60,
                      warmup: int = 10, dt: Optional[float] = None,
                      max_steps: int = 200_000, chunk: int = 2048,
                      trace_stride: int = 8, phased: bool = False,
                      jobs: Optional[Sequence[traffic.JobSpec]] = None,
                      mesh=None, launcher=None) -> PendingGrid:
    """Build + DISPATCH a cross-scale grid and return a
    :class:`PendingGrid` without blocking on device compute (jax
    dispatch is async; the sharded launcher additionally fans the cell
    axis out across devices). ``results()`` marshals."""
    check_iter_budget(n_iters)
    launcher = _resolve_launcher(mesh, launcher)
    with_ft = cong.needs_fault_table(profiles)
    intra = any(p.node_cap_frac > 0 for p in profiles)
    with spans.span(spans.BUILD_CASE):
        cases = []
        for sysname, n in cells:
            sysp = get_system(sysname) if isinstance(sysname, str) \
                else sysname
            cases.append(build_case(sysp, int(n), victim_coll, aggr_coll,
                                    phased=phased, jobs=jobs,
                                    intra_node=intra))
        if cases:
            dims, stacked = bucket_stack([case.geom for case in cases])
    sizes, profiles = tuple(sizes), tuple(profiles)
    if not cases:
        return PendingGrid([], {}, sizes, profiles, [], n_iters, warmup,
                           chunk, trace_stride)

    with spans.span(spans.GRID_PARAMS):
        all_dts = [_cell_dts(case, sizes, len(profiles), dt, case.lat())
                   for case in cases]
        sub_cells = [(float(v), prof) for v in sizes
                     for prof in [cong.no_congestion()] + list(profiles)]
        params = stack_params([
            stack_params([case.cell_params(v, prof, d,
                                           n_flows=dims.n_flows,
                                           with_fault_table=with_ft)
                          for (v, prof), d in zip(sub_cells, all_dts[k])])
            for k, case in enumerate(cases)])
    run = launcher if launcher is not None else run_cells_hetero
    with spans.span(spans.DISPATCH):
        out = run(stacked, params, jnp.asarray(n_iters, jnp.int32),
                  chunk=chunk, max_chunks=-(-max_steps // chunk),
                  stride=trace_stride)
    return PendingGrid(cases, out, sizes, profiles, all_dts, n_iters,
                       warmup, chunk, trace_stride)


def run_scale_grid(cells: Sequence[ScaleCell], victim_coll: str,
                   aggr_coll: str, sizes: Sequence[float],
                   profiles: Sequence[cong.Profile], *, n_iters: int = 60,
                   warmup: int = 10, dt: Optional[float] = None,
                   max_steps: int = 200_000, chunk: int = 2048,
                   trace_stride: int = 8, phased: bool = False,
                   jobs: Optional[Sequence[traffic.JobSpec]] = None,
                   mesh=None, launcher=None) -> List[BenchResult]:
    """A whole cross-scale experiment — heterogeneous ``(system,
    n_nodes)`` cells x (vector size x profile) — in one batched call per
    geometry *bucket*.

    Routing is traced data (SimParams.policy) since the mitigation lab,
    so mixed-routing cell lists no longer split into per-mode buckets:
    ALL cells pad to one power-of-two GeometryDims bucket (masks keep
    the padding provably inert — a padded run is bit-identical to its
    unpadded equivalent) and stack under a nested ``jit(vmap(vmap(...)))``
    — an EDR/HDR/NDR/Slingshot x {16..512} nodes x collective sweep
    compiles the simulator ONCE per GeometryDims bucket (asserted via
    simulator.TRACE_COUNTS in tests/test_grid.py). Results come back in
    input order: cells major, then sizes, then baseline/profiles
    (matching a sequential per-cell run_grid concatenation).

    ``mesh``/``launcher`` shard the dispatch across devices
    (launch/sweep.py); the default per-device dispatcher is bit-identical
    to the single-device path (asserted in tests and the CI smoke).
    Launch/collect are split in :func:`launch_scale_grid` for callers
    that overlap several grids."""
    return launch_scale_grid(cells, victim_coll, aggr_coll, sizes, profiles,
                             n_iters=n_iters, warmup=warmup, dt=dt,
                             max_steps=max_steps, chunk=chunk,
                             trace_stride=trace_stride, phased=phased,
                             jobs=jobs, mesh=mesh,
                             launcher=launcher).results()


def run_point(system: SystemPreset, n_nodes: int, victim_coll: str,
              aggr_coll: str, vector_bytes: float,
              profile: cong.Profile, *, n_iters: int = 60, warmup: int = 10,
              dt: Optional[float] = None, max_steps: int = 200_000,
              return_traces: bool = False, phased: bool = False,
              jobs: Optional[Sequence[traffic.JobSpec]] = None,
              seed: int = 7):
    """One heatmap cell: baseline (aggressors off) vs congested run.

    Implemented as a 2-cell grid (baseline + congested batched in one
    call). ``seed`` picks the allocation draw (collapse depth under
    incast is placement-dependent; see allocate()).
    """
    check_iter_budget(n_iters)
    with_ft = cong.needs_fault_table([profile])
    case = build_case(system, n_nodes, victim_coll, aggr_coll,
                      phased=phased, jobs=jobs, seed=seed,
                      intra_node=profile.node_cap_frac > 0)
    lat = case.lat()
    if dt is None:
        dt = choose_dt(case.topo, case.n_victims, vector_bytes, lat,
                       n_phases=case.max_phases)
    chunk, stride = 2048, 8
    max_chunks = -(-max_steps // chunk)
    params = stack_params([
        case.cell_params(vector_bytes, cong.no_congestion(), dt,
                         with_fault_table=with_ft),
        case.cell_params(vector_bytes, profile, dt,
                         with_fault_table=with_ft)])
    out = run_cells(case.geom, params, jnp.asarray(n_iters, jnp.int32),
                    chunk=chunk, max_chunks=max_chunks, stride=stride)
    base = summarize(out, n_iters=n_iters, warmup=warmup, dt=dt, chunk=chunk,
                     stride=stride, cell=0)
    cong_res = summarize(out, n_iters=n_iters, warmup=warmup, dt=dt,
                         chunk=chunk, stride=stride, cell=1)
    t_u = mean_iter_time(base, lat)
    t_c = mean_iter_time(cong_res, lat)
    dnf = base.n_done == 0 or cong_res.n_done == 0
    res = BenchResult(
        system=system.name, n_nodes=n_nodes,
        victim=victim_label(case.victim_coll, case.primary_phased),
        aggressor=case.aggr_coll or "none", profile=profile.kind,
        vector_bytes=vector_bytes, t_uncongested_s=t_u, t_congested_s=t_c,
        ratio=float("nan") if dnf else (t_u / t_c if t_c > 0 else 0.0),
        victim_goodput_gbps=float(np.mean(cong_res.victim_rate_trace[-200:])
                                  * 8 / 1e9)
        if len(cong_res.victim_rate_trace) else 0.0,
        n_iters=(base.n_done, cong_res.n_done),
        job_times=_job_times(out, case, n_iters=n_iters, warmup=warmup,
                             cell=1),
        warmup_ok=base.warmup_ok and cong_res.warmup_ok,
        dnf=dnf,
    )
    if return_traces:
        return res, base, cong_res
    return res


# --------------------------------------------------------------------------
# Single-trace helpers
# --------------------------------------------------------------------------


def _run_uncongested(system: SystemPreset, topo, nodes, coll: str,
                     vector_bytes: float, *, dt: float, n_iters: int,
                     warmup: int, max_steps: int = 200_000):
    """One aggressor-free run on an explicit topology/allocation — the
    shared helper behind goodput_trace and straggler_impact."""
    check_iter_budget(n_iters)
    flows = cong.build_flowset(topo, nodes, [], coll, "", vector_bytes,
                               routing_mode=system.static_routing,
                               k_max=system.k_max)
    geom = make_geometry(topo, flows)
    params = make_params(system.cc, dt=dt,
                         bytes_per_iter=flows.bytes_per_iter,
                         host_caps=flows.host_caps,
                         env=cong.no_congestion().params(),
                         policy=default_policy(system))
    chunk, stride = 2048, 8
    out = run_cell(geom, params, jnp.asarray(n_iters, jnp.int32),
                   chunk=chunk, max_chunks=-(-max_steps // chunk),
                   stride=stride)
    return summarize(out, n_iters=n_iters, warmup=warmup, dt=dt, chunk=chunk,
                     stride=stride)


def goodput_trace(system: SystemPreset, n_nodes: int, coll: str,
                  vector_bytes: float, *, n_iters: int = 40,
                  dt: float = 20e-6, max_steps: int = 200_000):
    """Self-congestion run (no aggressors) — Fig. 3 sawtooth experiments."""
    topo = machine_topology(system) if system.machine_nodes \
        else system.make_topology(n_nodes)
    nodes = allocate(system, n_nodes)
    return _run_uncongested(system, topo, nodes, coll, vector_bytes, dt=dt,
                            n_iters=n_iters, warmup=5, max_steps=max_steps)


def straggler_impact(system: SystemPreset, n_nodes: int, coll: str,
                     vector_bytes: float, *, slow_factor: float = 0.1,
                     n_iters: int = 25,
                     straggler: Optional[int] = None) -> dict:
    """Model a straggler as a degraded injection link (DESIGN.md §7):
    one node's NIC runs at ``slow_factor`` of line rate; a synchronous
    collective is gated by its slowest member, so the iteration time
    stretches toward 1/slow_factor. Runtime policy (fault.StepMonitor +
    elastic_plan) uses this as the model for when eviction pays.

    ``straggler`` indexes into the allocation (default: its middle node).
    """
    topo = machine_topology(system) if system.machine_nodes \
        else system.make_topology(n_nodes)
    nodes = allocate(system, n_nodes)
    base = _run_uncongested(system, topo, nodes, coll, vector_bytes,
                            dt=5e-6, n_iters=n_iters, warmup=5)

    if straggler is None:
        straggler = len(nodes) // 2
    victim_node = int(nodes[straggler])
    topo_slow = copy.copy(topo)
    caps = topo.caps.copy()
    for li, (a, b) in enumerate(topo.link_names):
        if a == ("h", victim_node) or b == ("h", victim_node):
            caps[li] = caps[li] * slow_factor
    topo_slow.caps = caps
    slow = _run_uncongested(system, topo_slow, nodes, coll, vector_bytes,
                            dt=5e-6, n_iters=n_iters, warmup=5)
    t_base = float(np.mean(base.iter_times)) if len(base.iter_times) else 0.0
    t_slow = float(np.mean(slow.iter_times)) if len(slow.iter_times) \
        else float("inf")
    return {"t_base_s": t_base, "t_straggler_s": t_slow,
            "slowdown": t_slow / t_base if t_base else float("inf")}
