"""JAX fluid flow-level fabric simulator — pure-functional core.

Multi-job flow *programs* (traffic.py) traverse a :class:`Topology` under
a congestion-control model (cc.py) and a routing policy. The inner loop
is a ``jax.lax.scan`` over fixed-dt timesteps:

  1. injection demand from per-flow CC rate limits, gated by phase
     membership (a flow transmits only while its job is in its phase),
  2. per-flow path choice by the cell's *traced* routing policy — a
     ``lax.switch`` over SimParams.policy (fixed / ECMP / NSLB tables,
     adaptive min-queue, flowlet re-pathing), so cells with different
     routing policies batch in one compile (mitigation lab),
  3. staged feed-forward propagation (FIFO fluid sharing per hop),
  4. queue integration (offered load vs capacity) + ECN/credit signals,
  5. CC rate update per fabric model + optional backpressure spreading,
  6. per-job phase advance — barrier-gated on the slowest member flow
     (DESIGN.md §7 straggler semantics) plus an optional compute gap —
     and program-completion bookkeeping (a job wrapping its last phase
     is one iteration of the paper's 1000-iteration protocol, scaled:
     see bench.py).

The engine is split into two pytrees:

* :class:`FabricGeometry` — the static structure of one experiment (packed
  paths, link capacities, switch adjacency). Constant across a parameter
  sweep; its array shapes key the JIT cache.
* :class:`SimParams` — everything a sweep varies: CC scalars, ``dt``,
  per-flow bytes targets, and the congestion-envelope parameters. All
  leaves are traced, so a grid of cells batches under ``jax.vmap`` with a
  single compile (bench.run_grid).

CC kind and the congestion envelope are *data*: the per-kind update is a
``lax.switch`` over branch functions and the aggressor envelope is a
traceable function of sim time (congestion.envelope_at), so cells with
different fabrics and different burst/pause duty cycles coexist in one
batched call. Approximations are documented in DESIGN.md; the validation
targets are the paper's observed *behaviors* (sawtooth, NSLB flat-line,
incast collapse, duty-cycle sensitivity), which emerge from the mechanisms,
not from fitting.
"""
from __future__ import annotations

import collections
import dataclasses
import os
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fabric.cc import (CCParams, KIND_AI_ECN, KIND_DCQCN, KIND_IB,
                                  KIND_SLINGSHOT, ROUTE_ADAPTIVE, ROUTE_FIXED)
from repro.core.fabric.routing import (POLICY_ADAPTIVE, POLICY_ECMP,
                                       POLICY_FIXED, POLICY_FLOWLET,
                                       POLICY_NSLB)
from repro.core.fabric.topology import Topology
from repro.core.envelopes import (ENV_COMPONENTS, GROUP_EDGE_DOWN,
                                  GROUP_EDGE_UP, GROUP_FABRIC, GROUP_HOT,
                                  GROUP_SWITCH, envelope_at, fault_scale_at,
                                  no_congestion)
from repro.core.traffic import pad_rows
from repro.kernels import ops as kernel_ops
from repro.kernels import ref as kernel_ref

# Fixed iteration-time buffer: n_iters is traced (no recompile across
# protocols); completed iterations beyond the buffer fold into the last slot.
TDONE_SLOTS = 96
_TDONE_ARANGE = np.arange(TDONE_SLOTS)  # hoisted iteration-slot ids

# ---------------------------------------------------------------------------
# Step-core backend: the memory-bound scatter core of each step (NIC limit,
# backpressure segment-sums, H-hop propagation, queue update) is extracted
# into repro.kernels — ``ref`` is the pure-jnp oracle (the original lax
# code, the default off-TPU), ``pallas`` the fused kernel
# (kernels/fabric_step.py, DESIGN.md §13). Resolution order: explicit
# ``backend=`` argument > set_step_backend() > $REPRO_FABRIC_KERNEL > auto
# (pallas on TPU, ref elsewhere). The public entries resolve EAGERLY in a
# thin Python wrapper and pass the resolved name as a static jit argument,
# so switching backends never serves stale compiles.
STEP_BACKENDS = ("auto", "ref", "pallas")
_step_backend_override: Optional[str] = None


def set_step_backend(backend: Optional[str]) -> None:
    """Process-wide step-core backend override ('auto' | 'ref' |
    'pallas'); None restores env-var/auto resolution."""
    global _step_backend_override
    if backend is not None and backend not in STEP_BACKENDS:
        raise ValueError(f"unknown step backend {backend!r}; "
                         f"expected one of {STEP_BACKENDS}")
    _step_backend_override = backend


def resolve_step_backend(backend: Optional[str] = None) -> str:
    """Resolve to a concrete backend name ('ref' or 'pallas')."""
    b = backend or _step_backend_override \
        or os.environ.get("REPRO_FABRIC_KERNEL", "auto")
    if b not in STEP_BACKENDS:
        raise ValueError(f"unknown step backend {b!r}; "
                         f"expected one of {STEP_BACKENDS}")
    if b == "auto":
        b = "pallas" if jax.default_backend() == "tpu" else "ref"
    return b

# How often each jitted engine entry has been TRACED (== compiled) since
# import. Python side effects run only while tracing, so the increments
# below fire once per compile; tests assert a whole scale sweep costs at
# most one compile per geometry bucket (DESIGN.md §11).
TRACE_COUNTS: collections.Counter = collections.Counter()


# Named scopes of the engine (DESIGN.md §18). Every op of a compiled
# engine entry lies under ENGINE_SCOPE, and every op of a step under
# STEP_SCOPE and one of its sections, so a device trace attributes its
# time by section through each op's ``op_name``. Scopes are metadata: the
# compiled program and its results are those of an unscoped build. New
# code in the step goes under an existing section or a new listed one.
ENGINE_SCOPE = "engine_loop"
STEP_SCOPE = "fabric_step"
STEP_SECTIONS = ("envelope", "route", "step_core", "signals", "cc",
                 "progress", "queue_delay", "metrics_carry")


def trace_count(entry: str = None) -> int:
    """Total traces of one engine entry (or all entries)."""
    if entry is None:
        return sum(TRACE_COUNTS.values())
    return TRACE_COUNTS[entry]


def check_iter_budget(n_iters: int) -> None:
    if n_iters > TDONE_SLOTS:
        raise ValueError(
            f"n_iters={n_iters} exceeds the {TDONE_SLOTS}-slot iteration "
            "buffer (raise TDONE_SLOTS or lower n_iters)")


# ---------------------------------------------------------------------------
# Persistent compilation cache: reruns of the engine skip XLA compilation
# entirely (the jaxpr trace still runs, but it is milliseconds next to the
# multi-second XLA compile of the chunked while_loop). Every public engine
# entry arms it lazily. The directory is placed from outside only:
# $JAX_COMPILATION_CACHE_DIR when set, else one fixed path inside the
# checkout (a cache directory is part of the entries' key, so it must not
# move between runs).
# ---------------------------------------------------------------------------

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_COMPILE_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "..", ".jax_cache"))
_COMPILE_CACHE_DIR: Optional[str] = None


def ensure_compile_cache(*, min_compile_secs: float = 0.0) -> str:
    """Arm XLA's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    or, when that is unset, at ``DEFAULT_COMPILE_CACHE_DIR``. Idempotent
    and cheap once configured; returns the active cache dir.
    ``min_entry_size_bytes=-1`` caches every entry regardless of size —
    on CPU the engine executables are small but cost seconds to build."""
    global _COMPILE_CACHE_DIR
    if _COMPILE_CACHE_DIR is not None:
        # first activation wins: a process-wide cache must not silently
        # re-point mid-run (half the entries would land elsewhere)
        return _COMPILE_CACHE_DIR
    cache_dir = os.environ.get(COMPILE_CACHE_ENV) or DEFAULT_COMPILE_CACHE_DIR
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _COMPILE_CACHE_DIR = cache_dir
    return cache_dir


@dataclasses.dataclass
class FlowSet:
    """Static flow structure for one experiment (a packed traffic
    program: every flow belongs to one phase of one job)."""

    paths: np.ndarray  # (F, K, H) link ids, pad = L (sink)
    n_paths: np.ndarray  # (F,)
    path_len: np.ndarray  # (F, K) hop counts (for minimal-path bias)
    is_victim: np.ndarray  # (F,) bool — flow of a non-envelope-gated job
    bytes_per_iter: np.ndarray  # (F,) bytes per phase visit; endless ~inf
    fixed_choice: np.ndarray  # (F,) host-side static assignment
    host_caps: np.ndarray  # (F,) injection-link capacity per flow
    src_id: np.ndarray  # (F,) source node (NIC injection limiting)
    # --- traced-policy static tables (POLICY_ECMP / POLICY_NSLB read
    # these regardless of which mode built fixed_choice; default to the
    # fixed assignment so legacy flow sets stay policy-invariant) ---
    ecmp_choice: Optional[np.ndarray] = None  # (F,)
    nslb_choice: Optional[np.ndarray] = None  # (F,)
    # --- traffic-program tables (defaulted for legacy flat flow sets) ---
    flow_job: Optional[np.ndarray] = None  # (F,) owning job id
    flow_phase: Optional[np.ndarray] = None  # (F,) phase within the job
    n_phases: Optional[np.ndarray] = None  # (J,) program length per job
    phase_gap: Optional[np.ndarray] = None  # (J, P) compute gap per phase
    sweep_mask: Optional[np.ndarray] = None  # (F,) bytes scale with sweep
    job_names: Optional[List[str]] = None

    def __post_init__(self):
        if self.ecmp_choice is None:
            self.ecmp_choice = np.asarray(self.fixed_choice, np.int32)
        if self.nslb_choice is None:
            self.nslb_choice = np.asarray(self.fixed_choice, np.int32)
        # Legacy construction (no program tables): victims are job 0
        # phase 0, aggressors job 1 phase 0, both single-phase loops.
        if self.flow_job is None:
            self.flow_job = np.where(self.is_victim, 0, 1).astype(np.int32)
        if self.flow_phase is None:
            self.flow_phase = np.zeros(len(self.is_victim), np.int32)
        if self.n_phases is None:
            n_jobs = int(self.flow_job.max()) + 1 if len(self.flow_job) \
                else 1
            self.n_phases = np.ones((n_jobs,), np.int32)
        if self.phase_gap is None:
            self.phase_gap = np.zeros((len(self.n_phases), 1), np.float32)
        if self.sweep_mask is None:
            self.sweep_mask = np.asarray(self.is_victim, bool)
        if self.job_names is None:
            self.job_names = [f"job{j}" for j in range(len(self.n_phases))]

    @property
    def n_flows(self) -> int:
        return len(self.is_victim)

    @property
    def n_jobs(self) -> int:
        return len(self.n_phases)


def pack_paths(paths_per_flow: List[List[List[int]]], sink: int, k_max: int = 4):
    F = len(paths_per_flow)
    H = max((len(p) for ps in paths_per_flow for p in ps), default=1)
    out = np.full((F, k_max, H), sink, np.int32)
    n_paths = np.zeros((F,), np.int32)
    plen = np.zeros((F, k_max), np.int32)
    for f, ps in enumerate(paths_per_flow):
        ps = ps[:k_max] if ps else [[]]
        n_paths[f] = len(ps)
        for k, p in enumerate(ps):
            out[f, k, : len(p)] = p
            plen[f, k] = len(p)
    return out, n_paths, plen


# --------------------------------------------------------------------------
# Static geometry pytree
# --------------------------------------------------------------------------


@partial(jax.tree_util.register_dataclass,
         data_fields=["caps_pad", "caps_finite", "dst_sw", "src_sw", "paths",
                      "n_paths", "spray_choice", "path_len", "is_victim",
                      "fixed_choice", "ecmp_choice", "nslb_choice", "src_id",
                      "flow_job", "flow_phase", "n_phases", "phase_gap",
                      "link_group", "link_sw_group"],
         meta_fields=["L", "n_sw", "n_src", "n_jobs", "intra_node"])
@dataclasses.dataclass(frozen=True)
class FabricGeometry:
    """Everything structural: link capacities, switch adjacency, packed
    flow paths, and the traffic-program tables (which job/phase each flow
    belongs to, program lengths, compute gaps). Built once per
    (topology, flow program); shared by every cell of a parameter sweep.

    Routing policy is NOT part of the geometry: it is traced per-cell
    data (``SimParams.policy``), so geometries differing only in routing
    stack into one bucket. The geometry carries every *static* choice
    table a traced policy may read (fixed / ecmp / nslb)."""

    caps_pad: jnp.ndarray  # (L+1,) with inf sink
    caps_finite: jnp.ndarray  # (L+1,) with 1.0 sink
    dst_sw: jnp.ndarray  # (L+1,) switch fed by each link (0 = host)
    src_sw: jnp.ndarray  # (L+1,) switch feeding each link (0 = host)
    paths: jnp.ndarray  # (F, K, H)
    n_paths: jnp.ndarray  # (F,)
    spray_choice: jnp.ndarray  # (F,) deterministic sprayed home path
    path_len: jnp.ndarray  # (F, K) float
    is_victim: jnp.ndarray  # (F,) bool
    fixed_choice: jnp.ndarray  # (F,) host-side static assignment
    ecmp_choice: jnp.ndarray  # (F,) POLICY_ECMP table
    nslb_choice: jnp.ndarray  # (F,) POLICY_NSLB table
    src_id: jnp.ndarray  # (F,)
    flow_job: jnp.ndarray  # (F,) owning job per flow
    flow_phase: jnp.ndarray  # (F,) phase membership per flow
    n_phases: jnp.ndarray  # (J,) program length per job
    phase_gap: jnp.ndarray  # (J, P) compute gap after each phase
    # structural fault-targeting groups per link (envelopes.GROUP_*);
    # 0 on the sink and padding so event rows can never touch them
    link_group: jnp.ndarray  # (L+1,) int32
    # second structural channel: GROUP_SWITCH on every link incident to
    # the busiest switch (a whole switch failing as one unit), 0
    # elsewhere. Separate from link_group so the promotion can never
    # re-label the ids existing event rows target (bit-identity when no
    # row uses GROUP_SWITCH — envelopes.fault_scale_at).
    link_sw_group: jnp.ndarray  # (L+1,) int32
    L: int
    n_sw: int
    n_src: int
    n_jobs: int
    # static flag arming the intra-node (NVLink/PCIe) stage ahead of the
    # NIC limit; 0 keeps the legacy trace free of the extra scatter
    intra_node: int = 0

    @property
    def n_flows(self) -> int:
        return self.is_victim.shape[0]


def make_geometry(topo: Topology, flows: FlowSet, prune: bool = True,
                  intra_node: bool = False) -> FabricGeometry:
    """Bind a flow set to a topology.

    ``prune=True`` (default) restricts the per-link state arrays to the
    links actually referenced by some flow path, remapping link ids
    densely (and likewise switch/source ids). An allocation of tens of
    nodes on a multi-thousand-node machine touches a few hundred links,
    so this shrinks every per-step scatter from machine size to
    allocation size. Untouched links can never interact with a flow
    (their queues stay 0 and no path reads them), so pruning leaves all
    flow-visible outputs bit-identical — tests/test_grid.py asserts it.
    """
    L_full = len(topo.caps)
    paths_np = np.asarray(flows.paths)
    if prune:
        used = np.unique(paths_np[paths_np < L_full]).astype(np.int64)
    else:
        used = np.arange(L_full, dtype=np.int64)
    L = len(used)
    remap = np.full((L_full + 1,), L, np.int32)
    remap[used] = np.arange(L, dtype=np.int32)
    paths_np = remap[paths_np]  # old sink (== L_full) -> new sink (== L)
    caps = np.asarray(topo.caps, np.float64)[used]
    caps_pad = jnp.asarray(np.concatenate([caps, [np.inf]]), jnp.float32)
    caps_finite = jnp.asarray(np.concatenate([caps, [1.0]]), jnp.float32)
    # link <-> switch adjacency for backpressure spreading
    sw_ids: dict = {}
    dst_sw = np.zeros(L + 1, np.int32)
    src_sw = np.zeros(L + 1, np.int32)
    for li, gi in enumerate(used):
        a, b = topo.link_names[int(gi)]
        if not (isinstance(b, tuple) and b[0] == "h"):
            dst_sw[li] = 1 + sw_ids.setdefault(b, len(sw_ids))
        if not (isinstance(a, tuple) and a[0] == "h"):
            src_sw[li] = 1 + sw_ids.setdefault(a, len(sw_ids))
    n_sw = len(sw_ids) + 2  # 0 == "no switch" (host endpoints)
    # structural fault-targeting groups: edge-up / edge-down / fabric from
    # the endpoint kinds, then the single most-path-traversed link is
    # promoted to GROUP_HOT ("the flapping link" / "the dying optic" —
    # deterministic, so fault scenarios target it without naming ids).
    # The sink (index L) stays GROUP_NONE and is untouchable by events.
    link_group = np.zeros(L + 1, np.int32)
    for li, gi in enumerate(used):
        a, b = topo.link_names[int(gi)]
        if isinstance(a, tuple) and a[0] == "h":
            link_group[li] = GROUP_EDGE_UP
        elif isinstance(b, tuple) and b[0] == "h":
            link_group[li] = GROUP_EDGE_DOWN
        else:
            link_group[li] = GROUP_FABRIC
    traversals = np.bincount(paths_np[paths_np < L].ravel(), minlength=L)
    if traversals.size and traversals.max() > 0:
        link_group[int(np.argmax(traversals))] = GROUP_HOT
    # switch-level group: the busiest switch (max summed path traversals
    # over its incident links) contributes its WHOLE link set — the
    # deterministic switch analog of GROUP_HOT, so switch_outage events
    # target it without naming ids. Kept in a separate array; the sink
    # (index L) and host endpoints (switch id 0) stay GROUP_NONE.
    link_sw_group = np.zeros(L + 1, np.int32)
    if traversals.size and traversals.max() > 0:
        sw_load = np.zeros(n_sw, np.float64)
        np.add.at(sw_load, src_sw[:L], traversals)
        np.add.at(sw_load, dst_sw[:L], traversals)
        sw_load[0] = 0.0  # "no switch" (host endpoints) is not a switch
        if sw_load.max() > 0:
            hot_sw = int(np.argmax(sw_load))
            incident = (src_sw[:L] == hot_sw) | (dst_sw[:L] == hot_sw)
            link_sw_group[:L][incident] = GROUP_SWITCH
    # source (NIC) ids densified the same way
    src_raw = np.asarray(flows.src_id, np.int64)
    if prune and len(src_raw):
        _, src_dense = np.unique(src_raw, return_inverse=True)
        n_src = int(src_dense.max()) + 1
    else:
        src_dense = src_raw
        n_src = int(src_raw.max()) + 1 if len(src_raw) else 1
    # sprayed "home" path per flow: deterministic hash spread over the
    # candidates so concurrent flows do not herd onto one port
    F = flows.n_flows
    spray = (np.arange(F, dtype=np.int64) * 2654435761 % (1 << 31)) \
        % np.maximum(flows.n_paths, 1)
    return FabricGeometry(
        caps_pad=caps_pad, caps_finite=caps_finite,
        dst_sw=jnp.asarray(dst_sw), src_sw=jnp.asarray(src_sw),
        paths=jnp.asarray(paths_np), n_paths=jnp.asarray(flows.n_paths),
        spray_choice=jnp.asarray(spray.astype(np.int32)),
        path_len=jnp.asarray(flows.path_len, jnp.float32),
        is_victim=jnp.asarray(flows.is_victim),
        fixed_choice=jnp.asarray(flows.fixed_choice),
        ecmp_choice=jnp.asarray(flows.ecmp_choice, jnp.int32),
        nslb_choice=jnp.asarray(flows.nslb_choice, jnp.int32),
        src_id=jnp.asarray(src_dense.astype(np.int32)),
        flow_job=jnp.asarray(flows.flow_job, jnp.int32),
        flow_phase=jnp.asarray(flows.flow_phase, jnp.int32),
        n_phases=jnp.asarray(flows.n_phases, jnp.int32),
        phase_gap=jnp.asarray(flows.phase_gap, jnp.float32),
        link_group=jnp.asarray(link_group),
        link_sw_group=jnp.asarray(link_sw_group),
        L=L, n_sw=n_sw, n_src=n_src, n_jobs=flows.n_jobs,
        intra_node=int(bool(intra_node)))


# --------------------------------------------------------------------------
# Geometry padding: heterogeneous topologies in one batch (DESIGN.md §11)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GeometryDims:
    """Bucket shape every member geometry is padded to. Equal dims make
    FabricGeometry pytrees stackable: the meta fields become identical,
    so ``jax.vmap`` batches the data fields (routing policy is traced
    SimParams data, not meta — mixed-routing cells share a bucket)."""

    n_links: int  # L (sink lives at index n_links)
    n_flows: int
    k_max: int
    max_hops: int
    n_sw: int
    n_src: int
    n_jobs: int
    n_phases: int
    # 0/1 flag, not a size: never rounded up by the bucket policy (a
    # pow2 round would turn 0 into 1 and arm the stage for every bucket)
    intra_node: int = 0


def geometry_dims(geom: FabricGeometry) -> GeometryDims:
    return GeometryDims(
        n_links=geom.L, n_flows=geom.n_flows,
        k_max=int(geom.paths.shape[1]), max_hops=int(geom.paths.shape[2]),
        n_sw=geom.n_sw, n_src=geom.n_src, n_jobs=geom.n_jobs,
        n_phases=int(geom.phase_gap.shape[1]),
        intra_node=int(geom.intra_node))


_DIM_FLAG_FIELDS = ("intra_node",)


def bucket_dims(geoms: Sequence[FabricGeometry],
                round_up=None) -> GeometryDims:
    """Elementwise max over member dims, optionally rounded up (the
    bucket-size policy — bench rounds to powers of two so different cell
    sets resolve to the same bucket shape and reuse compiles). Flag
    fields max without rounding: a bucket mixing stage-on and stage-off
    cells arms the stage, and stage-off members run it inert
    (node_cap=inf is bit-identical — DESIGN.md §16)."""
    dims = [geometry_dims(g) for g in geoms]
    out = {}
    for f in dataclasses.fields(GeometryDims):
        v = max(getattr(d, f.name) for d in dims)
        if round_up is not None and f.name not in _DIM_FLAG_FIELDS:
            v = round_up(v)
        out[f.name] = v
    return GeometryDims(**out)


def pad_geometry(geom: FabricGeometry, dims: GeometryDims) -> FabricGeometry:
    """Pad one geometry to a bucket shape with provably inert padding.

    Padding rows are constructed so the padded run is *bit-identical* to
    the unpadded run of the same cell (tests/test_grid.py):

    * pad links ([L, n_links)) are referenced by no path and see zero
      arrival, so their queues stay at exactly 0.0;
    * pad flows carry a sink-only path, zero path length and ``is_victim
      == False``; their byte budget (SimParams) must be 0, which keeps
      them out of ``alive`` forever — they inject 0.0 into every scatter;
    * pad jobs have ``n_phases == 1`` and no member flows; their phase
      counter free-runs without touching any real job's barrier;
    * pad switches/sources are referenced by no link/flow.

    The old sink (index ``geom.L``) is remapped to the new sink
    (``dims.n_links``) everywhere in the path table.
    """
    cur = geometry_dims(geom)
    for f in dataclasses.fields(GeometryDims):
        if getattr(dims, f.name) < getattr(cur, f.name):
            raise ValueError(
                f"pad_geometry: {f.name}={getattr(dims, f.name)} < "
                f"current {getattr(cur, f.name)}")
    L_old, L_new = geom.L, dims.n_links
    F, J = dims.n_flows, dims.n_jobs

    paths = np.asarray(geom.paths)
    paths = np.where(paths >= L_old, L_new, paths).astype(np.int32)
    padded_paths = np.full((F, dims.k_max, dims.max_hops), L_new, np.int32)
    padded_paths[: paths.shape[0], : paths.shape[1], : paths.shape[2]] = paths

    path_len = np.zeros((F, dims.k_max), np.float32)
    pl = np.asarray(geom.path_len)
    path_len[: pl.shape[0], : pl.shape[1]] = pl

    caps_pad = np.full((L_new + 1,), np.inf, np.float32)
    caps_pad[:L_old] = np.asarray(geom.caps_pad)[:L_old]
    caps_finite = np.ones((L_new + 1,), np.float32)
    caps_finite[:L_old] = np.asarray(geom.caps_finite)[:L_old]
    dst_sw = np.zeros((L_new + 1,), np.int32)
    dst_sw[:L_old] = np.asarray(geom.dst_sw)[:L_old]
    src_sw = np.zeros((L_new + 1,), np.int32)
    src_sw[:L_old] = np.asarray(geom.src_sw)[:L_old]
    # pad links stay GROUP_NONE: no fault event can ever scale them
    link_group = np.zeros((L_new + 1,), np.int32)
    link_group[:L_old] = np.asarray(geom.link_group)[:L_old]
    link_sw_group = np.zeros((L_new + 1,), np.int32)
    link_sw_group[:L_old] = np.asarray(geom.link_sw_group)[:L_old]

    n_phases = pad_rows(np.asarray(geom.n_phases), J, 1)
    phase_gap = np.zeros((J, dims.n_phases), np.float32)
    pg = np.asarray(geom.phase_gap)
    phase_gap[: pg.shape[0], : pg.shape[1]] = pg

    return FabricGeometry(
        caps_pad=jnp.asarray(caps_pad), caps_finite=jnp.asarray(caps_finite),
        dst_sw=jnp.asarray(dst_sw), src_sw=jnp.asarray(src_sw),
        paths=jnp.asarray(padded_paths),
        n_paths=jnp.asarray(pad_rows(np.asarray(geom.n_paths), F, 1)),
        spray_choice=jnp.asarray(pad_rows(np.asarray(geom.spray_choice), F, 0)),
        path_len=jnp.asarray(path_len),
        is_victim=jnp.asarray(pad_rows(np.asarray(geom.is_victim), F, False)),
        fixed_choice=jnp.asarray(pad_rows(np.asarray(geom.fixed_choice), F, 0)),
        ecmp_choice=jnp.asarray(pad_rows(np.asarray(geom.ecmp_choice), F, 0)),
        nslb_choice=jnp.asarray(pad_rows(np.asarray(geom.nslb_choice), F, 0)),
        src_id=jnp.asarray(pad_rows(np.asarray(geom.src_id), F,
                                 dims.n_src - 1)),
        flow_job=jnp.asarray(pad_rows(np.asarray(geom.flow_job), F, J - 1)),
        flow_phase=jnp.asarray(pad_rows(np.asarray(geom.flow_phase), F, 0)),
        n_phases=jnp.asarray(n_phases), phase_gap=jnp.asarray(phase_gap),
        link_group=jnp.asarray(link_group),
        link_sw_group=jnp.asarray(link_sw_group),
        L=L_new, n_sw=dims.n_sw, n_src=dims.n_src, n_jobs=J,
        intra_node=int(dims.intra_node))


def stack_geometries(geoms: Sequence[FabricGeometry]) -> FabricGeometry:
    """Stack same-shape geometries into one batched pytree (leading cell
    axis on every data field). All meta fields must agree; pad to a
    common :class:`GeometryDims` first. Routing policy is traced data
    (SimParams.policy), so mixed-routing cells stack freely."""
    metas = {(g.L, g.n_sw, g.n_src, g.n_jobs, g.intra_node) for g in geoms}
    if len(metas) != 1:
        raise ValueError(f"cannot stack geometries with differing meta "
                         f"fields: {sorted(metas)}")
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *geoms)


# --------------------------------------------------------------------------
# Traced sweep parameters
# --------------------------------------------------------------------------


@partial(jax.tree_util.register_dataclass,
         data_fields=["dt", "bytes_per_iter", "host_caps", "env", "policy",
                      "flowlet_gap_s", "flow_start", "fct_mask", "fault",
                      "node_cap", "kind",
                      "qmax_bytes", "kmin", "kmax", "md", "rai_frac",
                      "cc_interval_s", "hol_factor", "hol_start",
                      "min_rate_frac", "follow_tau_s", "follow_gain",
                      "thresh_adapt", "burst_jitter", "iter_drain"],
         meta_fields=[])
@dataclasses.dataclass(frozen=True)
class SimParams:
    """Traced per-cell parameters. Every leaf is an array, so a stack of
    cells (leading batch axis on each leaf) vmaps through the engine."""

    dt: jnp.ndarray  # () seconds
    bytes_per_iter: jnp.ndarray  # (F,)
    host_caps: jnp.ndarray  # (F,)
    env: jnp.ndarray  # (ENV_COMPONENTS, 5) congestion-envelope components
    # routing policy id (routing.POLICY_*) + flowlet idle-gap threshold —
    # traced, so mixed-routing grids batch in one compile
    policy: jnp.ndarray  # () int32
    flowlet_gap_s: jnp.ndarray  # () seconds
    # stochastic-workload fields (core/workload.py): a flow is eligible
    # only once sim time reaches its start (Poisson arrivals), and
    # fct_mask selects which flows feed the FCT histogram (short flows).
    # Scalar 0.0 defaults reproduce legacy behavior bit-for-bit.
    flow_start: jnp.ndarray  # () or (F,) seconds
    fct_mask: jnp.ndarray  # () or (F,) 0/1 weight
    # link-fault event table (envelopes.fault_scale_at). None keeps the
    # legacy no-fault trace byte-identical (an absent pytree leaf); grids
    # mixing fault and clean lanes put the inert all-``none`` table on
    # the clean lanes so stacked params share one structure.
    fault: Optional[jnp.ndarray]  # (FAULT_EVENTS, FAULT_FIELDS) or None
    # intra-node stage capacity in bytes/s (scalar or (n_src,)); +inf is
    # exactly inert, so stage-on buckets can host stage-off cells
    node_cap: jnp.ndarray  # () or (n_src,)
    # CC scalars (cc.CCParams lowered to data; kind selects the update
    # rule — scalar per cell, or (F,) for per-flow/tenant CC mixes)
    kind: jnp.ndarray  # () or (F,) int32
    qmax_bytes: jnp.ndarray
    kmin: jnp.ndarray
    kmax: jnp.ndarray
    md: jnp.ndarray
    rai_frac: jnp.ndarray
    cc_interval_s: jnp.ndarray
    hol_factor: jnp.ndarray
    hol_start: jnp.ndarray
    min_rate_frac: jnp.ndarray
    follow_tau_s: jnp.ndarray
    follow_gain: jnp.ndarray
    thresh_adapt: jnp.ndarray
    burst_jitter: jnp.ndarray
    iter_drain: jnp.ndarray


def make_params(cc: CCParams, *, dt: float, bytes_per_iter: np.ndarray,
                host_caps: np.ndarray, env: np.ndarray,
                policy: int = POLICY_FIXED,
                flowlet_gap_s: float = 200e-6,
                flow_start=0.0, fct_mask=0.0,
                fault=None, node_cap=np.inf) -> SimParams:
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    return SimParams(
        dt=f32(dt), bytes_per_iter=f32(bytes_per_iter),
        host_caps=f32(host_caps), env=f32(env),
        policy=jnp.asarray(policy, jnp.int32),
        flowlet_gap_s=f32(flowlet_gap_s),
        flow_start=f32(flow_start), fct_mask=f32(fct_mask),
        fault=None if fault is None else f32(fault),
        node_cap=f32(node_cap),
        kind=jnp.asarray(cc.kind, jnp.int32),
        qmax_bytes=f32(cc.qmax_bytes), kmin=f32(cc.kmin), kmax=f32(cc.kmax),
        md=f32(cc.md), rai_frac=f32(cc.rai_frac),
        cc_interval_s=f32(cc.cc_interval_s), hol_factor=f32(cc.hol_factor),
        hol_start=f32(cc.hol_start), min_rate_frac=f32(cc.min_rate_frac),
        follow_tau_s=f32(cc.follow_tau_s), follow_gain=f32(cc.follow_gain),
        thresh_adapt=f32(1.0 if cc.thresh_adapt else 0.0),
        burst_jitter=f32(cc.burst_jitter), iter_drain=f32(cc.iter_drain))


def stack_params(params: List[SimParams]) -> SimParams:
    """Stack per-cell SimParams into one batched pytree (leading axis)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params)


# --------------------------------------------------------------------------
# Pure step / run functions
# --------------------------------------------------------------------------


def init_state(geom: FabricGeometry, p: SimParams, metrics: bool = False):
    """Initial scan carry. ``metrics=True`` adds the streaming-statistics
    accumulators (core/metrics.py): O(bins + F + J) extra state,
    independent of step count. ``_step_impl`` detects the extra keys and
    emits the matching updates — the flag is structural (dict keys), so
    it is static under jit without an extra argument."""
    F, J = geom.n_flows, geom.n_jobs
    state = _base_state(geom, p)
    if metrics:
        from repro.core import metrics as met
        state.update({
            # time each flow (re-)armed its current byte budget: short
            # flows arm at their Poisson arrival, tenant flows at every
            # phase entry — completion at t samples FCT = t - armed_t
            "armed_t": jnp.zeros((F,), jnp.float32) + p.flow_start,
            "h_qd": jnp.zeros((met.NBINS,), jnp.float32),
            "h_fct": jnp.zeros((met.NBINS,), jnp.float32),
            "wn": jnp.zeros((J,), jnp.float32),
            "wmean": jnp.zeros((J,), jnp.float32),
            "wm2": jnp.zeros((J,), jnp.float32),
        })
    return state


def _base_state(geom: FabricGeometry, p: SimParams):
    F, J = geom.n_flows, geom.n_jobs
    return {
        "c": p.host_caps,
        "rem": p.bytes_per_iter,
        "q": jnp.zeros((geom.L + 1,), jnp.float32),
        "arr": jnp.zeros((geom.L + 1,), jnp.float32),
        "thresh": jnp.full((geom.L + 1,), jnp.float32(1.0)) * p.kmin
        * p.qmax_bytes,
        "last_dec": jnp.zeros((F,), jnp.float32),
        # --- traced-routing state: flowlet current path + idle time,
        # per-flow delivered-bytes accumulator (mitigation scoring)
        "rc": geom.spray_choice,
        "idle": jnp.zeros((F,), jnp.float32),
        "fbytes": jnp.zeros((F,), jnp.float32),
        # --- traffic-program state: per-job phase counter, remaining
        # compute gap of the current phase, completed program iterations
        "ph": jnp.zeros((J,), jnp.int32),
        "gap": geom.phase_gap[:, 0],
        "it": jnp.zeros((J,), jnp.int32),
        "t_done": jnp.zeros((J, TDONE_SLOTS), jnp.float32),
        "qd_acc": jnp.zeros((), jnp.float32),
        "t": jnp.zeros((), jnp.float32),
    }


def _cc_update(p: SimParams, c, a, fmark, fstrength, can_dec):
    """Branchless CC dispatch: the per-fabric rate update is a lax.switch
    over ``p.kind``, so fabric kind is data (vmap lowers it to a select
    across branches — cells with different fabrics batch together)."""
    inc = p.rai_frac * p.host_caps * (p.dt / 1e-3)
    # credit-window follower (ib / slingshot); tau guarded for the kinds
    # that leave it at 0 — their branches never read ``f``.
    f = 1.0 - jnp.exp(-p.dt / jnp.maximum(p.follow_tau_s, 1e-9))

    def dcqcn(_):
        dec = fmark & can_dec
        return jnp.where(dec, c * p.md, c + inc), dec

    def ib(_):
        # credit semantics: the send window tracks what actually drains
        # (hop-by-hop credits), SYMMETRICALLY — senders pause when the
        # downstream buffer fills and resume the instant it drains. The
        # overshoot keeps the hot buffer fed (full, not at the mark
        # point); FECN/BECN marking is the slower outer loop.
        c2 = (1 - f) * c + f * jnp.maximum(
            a * p.follow_gain, p.min_rate_frac * p.host_caps)
        dec = fmark & can_dec
        return jnp.where(dec, c2 * p.md, c2 + inc), dec

    def slingshot(_):
        # throttle only flows actually bottlenecked
        bottlenecked = fmark & (a < 0.95 * c)
        c2 = jnp.where(bottlenecked,
                       (1 - f) * c + f * a * p.follow_gain,
                       c + inc)
        return c2, bottlenecked & can_dec

    def ai_ecn(_):
        dec = fmark & can_dec
        return jnp.where(dec, c * (1.0 - (1.0 - p.md) * fstrength),
                         c + inc), dec

    branches = [None] * 4
    branches[KIND_DCQCN] = dcqcn
    branches[KIND_IB] = ib
    branches[KIND_SLINGSHOT] = slingshot
    branches[KIND_AI_ECN] = ai_ecn
    if p.kind.ndim == 0:
        # scalar kind per cell: lax.switch (vmap lowers it to a select)
        return jax.lax.switch(p.kind, branches, None)
    # per-flow kind (F,) — tenant CC mixes inside ONE cell (workload.py):
    # evaluate every branch and select elementwise. jnp.select returns the
    # chosen branch's exact value, so a uniform vector matches the scalar
    # path bit-for-bit.
    outs = [b(None) for b in branches]
    preds = [p.kind == k for k in range(len(branches))]
    return (jnp.select(preds, [c2 for c2, _ in outs], outs[0][0]),
            jnp.select(preds, [d for _, d in outs], outs[0][1]))


def _chosen(table, choice):
    """``table[f, choice[f]]`` of an (F, K, ...) table, picked by the mask
    ``arange(K) == choice`` over K, never by a per-lane index: under vmap
    a gather whose indices differ per lane costs 19-27x more per element
    on a TPU v5e than one that indexes a table every lane shares
    (DESIGN.md §13). Exact: every other candidate meets the reduction's
    identity (False, 0, -inf)."""
    pick = np.arange(table.shape[1]) == choice[:, None]
    pick = pick.reshape(pick.shape + (1,) * (table.ndim - 2))
    if table.dtype == jnp.bool_:
        return jnp.any(table & pick, axis=1)
    if jnp.issubdtype(table.dtype, jnp.integer):
        return jnp.sum(jnp.where(pick, table, 0), axis=1)
    return jnp.max(jnp.where(pick, table, -jnp.inf), axis=1)


def _hop_max(link_vals, geom: FabricGeometry, pad=None):
    """(F, K): max (``any`` for booleans) of per-link values over the hops
    of every candidate path; with ``pad`` given, pad hops read it. The
    gather indexes only the shared candidate table, laid out (K, H, F):
    with flows on the minor axis its tiles are dense on a TPU, which
    took 3-14% off a step on a TPU v5e against (F, K, H)."""
    paths = jnp.transpose(geom.paths, (1, 2, 0))
    hops = link_vals[paths]
    if pad is not None:
        hops = jnp.where(paths < geom.L, hops, pad)
    return jnp.max(hops, axis=1).T


def _path_max(link_vals, geom: FabricGeometry, choice):
    """Max (``any`` for booleans) of per-link values over the hops of each
    flow's chosen path; pad hops read False / 0. The chosen candidate is
    picked after the hop reduction (:func:`_chosen`)."""
    return _chosen(_hop_max(link_vals, geom,
                            pad=jnp.zeros((), link_vals.dtype)), choice)


def step(geom: FabricGeometry, p: SimParams, state,
         backend: Optional[str] = None):
    return _step_impl(geom, p, state, with_aux=False,
                      backend=resolve_step_backend(backend))


def step_debug(geom: FabricGeometry, p: SimParams, state,
               backend: Optional[str] = None):
    """Like :func:`step` but also returns an aux dict of internal rates
    (injection, per-stage link loads/served rates, effective capacities)
    for the invariant test suite. The state update is the identical
    computation — the aux branch only adds read-only observers."""
    return _step_impl(geom, p, state, with_aux=True,
                      backend=resolve_step_backend(backend))


def _step_impl(geom: FabricGeometry, p: SimParams, state, with_aux: bool,
               backend: str = "ref"):
    with jax.named_scope(STEP_SCOPE):
        return _step_sections(geom, p, state, with_aux, backend)


def _step_sections(geom: FabricGeometry, p: SimParams, state,
                   with_aux: bool, backend: str):
    dt = p.dt
    with jax.named_scope("envelope"):
        # aggressor envelope: traceable function of sim time (no host
        # callback)
        env_t = envelope_at(p.env, state["t"])
        # phase membership: a flow transmits only while its job's phase
        # counter sits on the flow's phase (and its phase bytes remain);
        # negative phase id = wildcard, member of every phase
        # (traffic.WILDCARD_PHASE — uniform ring schedules)
        in_phase = (geom.flow_phase == state["ph"][geom.flow_job]) \
            | (geom.flow_phase < 0)
        # flow_start gates stochastic arrivals (workload.py); the scalar
        # 0.0 default keeps the predicate all-true — legacy runs are
        # bit-identical
        alive = (state["rem"] > 0) & in_phase & (state["t"] >= p.flow_start)
        active = (geom.is_victim | (env_t > 0)) & alive
        gate = jnp.where(geom.is_victim, 1.0, env_t) * alive
        inject = state["c"] * gate
        # (The NIC injection limit now lives in the fused step core below
        # — it has no data dependence on routing, so applying it after
        # the path choice is bit-identical.)

        # ---- link-fault engine (envelopes.fault_scale_at, DESIGN.md
        # §16) ----
        # Per-link capacity scale at sim time t, folded into the caps
        # operand OUTSIDE the kernel launch so both step-core backends
        # consume already-scaled capacities and the fused kernel body is
        # untouched. p.fault is None on the legacy path (absent pytree
        # leaf — the trace is byte-identical to a build without the
        # feature); the all-``none`` table lowers to an exact 1.0 scale,
        # and caps * 1.0 is bit-exact for finite positive f32 capacities
        # (the inertness contract the fault-table tests pin on every
        # state leaf).
        caps_lk = geom.caps_finite
        if p.fault is not None:
            caps_lk = caps_lk * fault_scale_at(
                p.fault, geom.link_group, state["t"],
                link_sw_group=geom.link_sw_group)

        # ---- optional intra-node stage (NVLink/PCIe ahead of the NIC)
        # Flows sharing a source node proportionally split the node's
        # internal bandwidth BEFORE the NIC limit — the same fluid share
        # rule the core applies per NIC, one stage earlier
        # (Tarraga-Moreno et al.; DESIGN.md §16). The flag is geometry
        # meta (static), so flag-off traces carry none of these ops;
        # node_cap == +inf makes the stage an exact no-op (scale 1.0),
        # letting stage-on buckets host stage-off cells bit-identically.
        if geom.intra_node:
            nload = jnp.zeros((geom.n_src,), jnp.float32) \
                .at[geom.src_id].add(inject)
            ncap = p.node_cap + jnp.zeros((geom.n_src,), jnp.float32)
            nscale = jnp.minimum(1.0, ncap / jnp.maximum(nload, 1.0))
            inject = inject * nscale[geom.src_id]

    with jax.named_scope("route"):
        # Traced per-cell policy (lax.switch over p.policy). Static
        # tables (fixed / ecmp / nslb) read precomputed host-side
        # assignments; dynamic policies score candidates by queue
        # occupancy. Under vmap the switch lowers to a select, so one
        # compile serves a grid mixing every policy. The candidate scores
        # are hoisted out of the branches and computed ONCE: the
        # dominant engine entries are batched (run_cells/_hetero evaluate
        # every branch anyway). Gathers here and in ``signals`` /
        # ``queue_delay`` index only the candidate table ``geom.paths``,
        # which every lane shares; each lane's chosen candidate is a mask
        # over K (``_chosen``), as a per-lane gather index costs 19-27x
        # more per element on the chip. ``occ`` is shared with the
        # backpressure stage of the core.
        occ = state["q"] / p.qmax_bytes
        score = _hop_max(occ, geom) \
            + 0.05 * geom.path_len / jnp.maximum(geom.path_len[:, :1], 1)
        score = jnp.where(np.arange(geom.paths.shape[1])[None, :]
                          < geom.n_paths[:, None], score, jnp.inf)
        best = jnp.argmin(score, axis=1)
        best_score = jnp.min(score, axis=1)

        def _hysteresis(anchor):
            # Production AR does NOT send every flow to the globally
            # least-loaded port (that herds and oscillates): a flow
            # leaves its anchor path only when its occupancy is clearly
            # worse than the best alternative.
            a_score = _chosen(score, anchor)
            return jnp.where(a_score > best_score + 0.10, best, anchor)

        def _route_adaptive(_):
            # anchored on the sprayed home path, re-evaluated every step
            return _hysteresis(geom.spray_choice), state["rc"]

        def _route_flowlet(_):
            # flowlet re-pathing: keep the current path while the flow
            # transmits; once its idle gap exceeds the traced threshold
            # the next burst re-evaluates — anchored on the CURRENT path
            # with the same hysteresis as adaptive (all-idle flows
            # re-picking a global argmin would herd onto one uplink), but
            # only at flowlet boundaries (idle resets on activity below,
            # so a live flow never re-orders mid-burst).
            rc = jnp.where(state["idle"] >= p.flowlet_gap_s,
                           _hysteresis(state["rc"]), state["rc"])
            return rc, rc

        route_branches = [None] * 5
        route_branches[POLICY_FIXED] = \
            lambda _: (geom.fixed_choice, state["rc"])
        route_branches[POLICY_ECMP] = \
            lambda _: (geom.ecmp_choice, state["rc"])
        route_branches[POLICY_NSLB] = \
            lambda _: (geom.nslb_choice, state["rc"])
        route_branches[POLICY_ADAPTIVE] = _route_adaptive
        route_branches[POLICY_FLOWLET] = _route_flowlet
        choice, rc_new = jax.lax.switch(p.policy, route_branches, None)
        idle_new = jnp.where(active, 0.0, state["idle"] + dt)
        plinks = _chosen(geom.paths, choice)  # (F, H)

    with jax.named_scope("step_core"):
        # Fused step core (NIC limit, backpressure stall, staged
        # propagation, queue update). The memory-bound
        # scatter/segment-sum core lives in repro.kernels:
        # kernels/ref.py holds the original lax code verbatim (the oracle
        # and CPU default), kernels/fabric_step.py the fused Pallas
        # kernel. The physics — why backpressure is share-weighted, why
        # propagation is feed-forward FIFO fluid sharing — is documented
        # on the oracle and in DESIGN.md §13.
        core_fn = kernel_ops.fabric_step_core if backend == "pallas" \
            else kernel_ref.fabric_step_core
        core = core_fn(
            plinks, inject, geom.src_id, p.host_caps, state["q"], occ,
            caps_lk, geom.src_sw, geom.dst_sw, dt, p.qmax_bytes,
            p.hol_factor, p.hol_start, p.burst_jitter,
            n_src=geom.n_src, n_sw=geom.n_sw, with_aux=with_aux)
        inject = core["inject"]  # NIC-scaled
        a = core["achieved"]  # achieved end-to-end rate
        arrival = core["arrival"]
        caps_eff = core["caps_eff"]
        served_stage_max = core["served_stage_max"]
        q = core["q_new"]

    with jax.named_scope("signals"):
        # AI-ECN: threshold tracks a fraction of the observed queue so
        # marking strength is proportional, not bang-bang.
        # thresh_adapt == 0 keeps the static kmin threshold.
        adapted = jnp.clip(0.9 * state["thresh"]
                           + 0.1 * (0.5 * q + p.kmin * p.qmax_bytes),
                           0.05 * p.qmax_bytes, p.kmax * p.qmax_bytes)
        thresh = jnp.where(p.thresh_adapt > 0, adapted, state["thresh"])
        over_thresh = q > thresh
        fmark = _path_max(over_thresh, geom, choice)
        # proportional mark strength (ai_ecn) in [0, 1]
        strength_l = jnp.clip((q - thresh)
                              / (p.kmax * p.qmax_bytes - thresh + 1.0),
                              0.0, 1.0)
        fstrength = _path_max(strength_l, geom, choice)

    with jax.named_scope("cc"):
        # lax.switch over fabric kind
        can_dec = state["last_dec"] >= p.cc_interval_s
        c, dec = _cc_update(p, state["c"], a, fmark, fstrength, can_dec)
        # CC state only evolves for flows that are actually transmitting
        # — an idle flow (finished its iteration early, or paused
        # aggressor) keeps its rate limit.
        c = jnp.where(active, c, state["c"])
        dec = dec & active
        c = jnp.clip(c, p.min_rate_frac * p.host_caps, p.host_caps)
        last_dec = jnp.where(dec, 0.0, state["last_dec"] + dt)

    with jax.named_scope("progress"):
        # progress + phase/program bookkeeping
        rem = state["rem"] - a * dt
        # completion event: the flow was eligible and its budget crossed
        # zero this very step (captured before `enter` re-arms rem below)
        done_now = alive & (rem <= 0)
        t_new = state["t"] + dt
        # per-job barrier: a phase completes only when its SLOWEST member
        # flow has drained (straggler semantics, DESIGN.md §7) ...
        busy = jnp.zeros((geom.n_jobs,), jnp.int32).at[geom.flow_job].max(
            (in_phase & (rem > 0)).astype(jnp.int32)) > 0
        # ... then the compute gap of the phase runs before the barrier
        # releases the next phase (gap == 0 -> advance in the same step,
        # which is exactly the pre-program iteration semantics)
        gap = state["gap"] - dt * (~busy)
        advance = ~busy & (gap <= 0)
        ph_next = jnp.where(advance,
                            (state["ph"] + 1) % geom.n_phases, state["ph"])
        wrap = advance & (state["ph"] + 1 >= geom.n_phases)
        gap = jnp.where(advance,
                        jnp.take_along_axis(geom.phase_gap,
                                            ph_next[:, None], axis=1)[:, 0],
                        gap)
        # flows of the newly-entered phase reload their byte budget
        # (wildcard flows re-arm at every phase entry)
        enter = advance[geom.flow_job] \
            & ((geom.flow_phase == ph_next[geom.flow_job])
               | (geom.flow_phase < 0))
        rem = jnp.where(enter, p.bytes_per_iter, rem)
        # a job wrapping phase 0 completed one program iteration
        it = state["it"]
        slot = jnp.minimum(it, TDONE_SLOTS - 1)
        onehot = _TDONE_ARANGE[None, :] == slot[:, None]
        t_done = jnp.where(wrap[:, None] & onehot, t_new, state["t_done"])
        it = it + wrap.astype(jnp.int32)
        # synchronization gap between iterations of the primary
        # (measured) job partially drains queues
        q = jnp.where(wrap[0], q * p.iter_drain, q)

    with jax.named_scope("queue_delay"):
        # queueing delay experienced by victim flows (seconds) — against
        # the fault-scaled capacity: a drained-down link serves its queue
        # slower
        qdel = _path_max(q / caps_lk, geom, choice)
        mean_qdel = jnp.sum(qdel * geom.is_victim) / jnp.maximum(
            jnp.sum(geom.is_victim), 1)
        vict_goodput = jnp.sum(a * geom.is_victim)

    # the two accumulators, in the order the state lists them
    with jax.named_scope("progress"):
        fbytes = state["fbytes"] + a * dt
    with jax.named_scope("queue_delay"):
        qd_acc = state["qd_acc"] + mean_qdel * dt

    new_state = {"c": c, "rem": rem, "q": q, "arr": arrival,
                 "thresh": thresh, "last_dec": last_dec,
                 "rc": rc_new, "idle": idle_new, "fbytes": fbytes,
                 "ph": ph_next, "gap": gap, "it": it, "t_done": t_done,
                 "qd_acc": qd_acc, "t": t_new}

    if "h_qd" in state:  # streaming metrics carry (init_state(metrics=True))
        from repro.core import metrics as met
        with jax.named_scope("metrics_carry"):
            # queue delay: every transmitting flow contributes one
            # sample/step
            w_qd = active.astype(jnp.float32)
            h_qd = met.hist_add(state["h_qd"], qdel, w_qd, jnp)
            # completion: an alive flow whose budget crossed zero this
            # step (done is computed BEFORE the `enter` re-arm overwrote
            # rem)
            fct = t_new - state["armed_t"]
            w_done = done_now.astype(jnp.float32)
            h_fct = met.hist_add(state["h_fct"], fct,
                                 w_done * (p.fct_mask + jnp.zeros_like(fct)),
                                 jnp)
            # per-tenant slowdown: FCT normalized by the flow's ideal
            # (uncontended line-rate) drain time, merged Welford-style
            # per job
            ideal = p.bytes_per_iter / jnp.maximum(p.host_caps, 1.0)
            slow = fct / jnp.maximum(ideal, 1e-9)
            wn, wmean, wm2 = met.welford_update(
                state["wn"], state["wmean"], state["wm2"], slow, w_done,
                geom.flow_job, geom.n_jobs, jnp)
            new_state.update({
                "armed_t": jnp.where(enter, t_new, state["armed_t"]),
                "h_qd": h_qd, "h_fct": h_fct,
                "wn": wn, "wmean": wmean, "wm2": wm2})

    if with_aux:
        aux = {"inject": inject, "achieved": a, "arrival": arrival,
               "served_stage_max": served_stage_max, "caps_eff": caps_eff,
               "active": active, "advance": advance, "wrap": wrap,
               "qdel": qdel, "done": done_now}
        return new_state, vict_goodput, aux
    return new_state, vict_goodput


def _run_cell(geom: FabricGeometry, p: SimParams, n_iters,
              chunk: int, max_chunks: int, stride: int,
              backend: str = "ref", metrics: bool = False,
              with_trace: bool = True):
    """Run one cell to ``n_iters`` victim iterations (or the step budget),
    chunked so the early exit happens at chunk granularity. Pure and
    vmap-able: under vmap the while_loop runs until every cell finishes.

    ``metrics=True`` threads the streaming accumulators through the scan
    and returns them; ``with_trace=False`` drops the strided goodput
    buffer — the replay path's peak memory is then O(F + bins) per cell,
    independent of the step budget (no O(T) allocation at all)."""
    assert chunk % stride == 0, (chunk, stride)
    # the chunk loop's own ops (initial state, the exit test, the goodput
    # buffer write) lie under ENGINE_SCOPE; each step under STEP_SCOPE
    with jax.named_scope(ENGINE_SCOPE):
        trace_chunk = chunk // stride
        state = init_state(geom, p, metrics=metrics)
        buf = jnp.zeros((max_chunks * trace_chunk if with_trace else 1,),
                        jnp.float32)

        def cond(carry):
            state, _, k = carry
            # job 0 is the primary (measured) job; background jobs loop
            # for as long as it runs and report however many programs
            # they closed
            return (k < max_chunks) & (state["it"][0] < n_iters)

        def body(carry):
            state, buf, k = carry
            state, gp = jax.lax.scan(
                lambda s, _: _step_impl(geom, p, s, with_aux=False,
                                        backend=backend),
                state, None, length=chunk)
            if with_trace:
                buf = jax.lax.dynamic_update_slice(buf, gp[::stride],
                                                   (k * trace_chunk,))
            return state, buf, k + 1

        state, buf, k = jax.lax.while_loop(
            cond, body, (state, buf, jnp.zeros((), jnp.int32)))
        out = {"t_done": state["t_done"], "it": state["it"],
               "qd_acc": state["qd_acc"], "t": state["t"],
               "fbytes": state["fbytes"],
               "trace": buf, "chunks": k}
        if metrics:
            out.update({k2: state[k2]
                        for k2 in ("h_qd", "h_fct", "wn", "wmean", "wm2")})
        return out


# The public entries resolve the step-core backend EAGERLY (a Python
# string) and forward it as a static jit argument: a backend switch via
# set_step_backend()/$REPRO_FABRIC_KERNEL is a different cache key, never
# a stale compile. TRACE_COUNTS increments live in the inner jitted
# functions so they still fire once per compile.


@partial(jax.jit, static_argnames=("chunk", "max_chunks", "stride",
                                   "backend", "metrics", "with_trace"))
def _run_cell_jit(geom, p, n_iters, *, chunk, max_chunks, stride, backend,
                  metrics=False, with_trace=True):
    TRACE_COUNTS["run_cell"] += 1
    return _run_cell(geom, p, n_iters, chunk, max_chunks, stride, backend,
                     metrics, with_trace)


def run_cell(geom: FabricGeometry, p: SimParams, n_iters,
             *, chunk: int = 2048, max_chunks: int = 98, stride: int = 8,
             backend: Optional[str] = None, metrics: bool = False,
             with_trace: bool = True):
    ensure_compile_cache()
    return _run_cell_jit(geom, p, n_iters, chunk=chunk,
                         max_chunks=max_chunks, stride=stride,
                         backend=resolve_step_backend(backend),
                         metrics=metrics, with_trace=with_trace)


@partial(jax.jit, static_argnames=("chunk", "max_chunks", "stride",
                                   "backend", "metrics", "with_trace"))
def _run_cells_jit(geom, params, n_iters, *, chunk, max_chunks, stride,
                   backend, metrics=False, with_trace=True):
    TRACE_COUNTS["run_cells"] += 1
    return jax.vmap(
        lambda pp: _run_cell(geom, pp, n_iters, chunk, max_chunks, stride,
                             backend, metrics, with_trace)
    )(params)


def run_cells(geom: FabricGeometry, params: SimParams, n_iters,
              *, chunk: int = 2048, max_chunks: int = 98, stride: int = 8,
              backend: Optional[str] = None, metrics: bool = False,
              with_trace: bool = True):
    """Batched engine: ``params`` has a leading cell axis on every leaf.
    One compile serves the whole grid; all cells advance in lockstep until
    the slowest finishes."""
    ensure_compile_cache()
    return _run_cells_jit(geom, params, n_iters, chunk=chunk,
                          max_chunks=max_chunks, stride=stride,
                          backend=resolve_step_backend(backend),
                          metrics=metrics, with_trace=with_trace)


@partial(jax.jit, static_argnames=("chunk", "max_chunks", "stride",
                                   "backend", "metrics", "with_trace"))
def _run_cells_hetero_jit(geoms, params, n_iters, *, chunk, max_chunks,
                          stride, backend, metrics=False, with_trace=True):
    TRACE_COUNTS["run_cells_hetero"] += 1

    def one_geom(g, ps):
        return jax.vmap(
            lambda pp: _run_cell(g, pp, n_iters, chunk, max_chunks, stride,
                                 backend, metrics, with_trace)
        )(ps)

    if geoms.paths.shape[0] == 1:
        # One topology cell, as each device of the sharded sweep runs it:
        # drop the axis rather than vmap it, so the step's gathers index
        # a table every lane shares (DESIGN.md §13). Under vmap their
        # indices would carry the topology axis.
        g, ps = jax.tree_util.tree_map(lambda x: x[0], (geoms, params))
        return jax.tree_util.tree_map(lambda x: x[None], one_geom(g, ps))
    return jax.vmap(one_geom)(geoms, params)


def run_cells_hetero(geoms: FabricGeometry, params: SimParams, n_iters,
                     *, chunk: int = 2048, max_chunks: int = 98,
                     stride: int = 8, backend: Optional[str] = None,
                     mesh=None, shard_axis: str = "cell",
                     donate: bool = False, metrics: bool = False,
                     with_trace: bool = True):
    """Scale-batched engine: ``geoms`` is a stack of bucket-padded
    geometries (leading axis = topology cell) and ``params`` carries TWO
    leading axes — (topology cell, sub-cell) — so a whole
    (system x n_nodes) x (size x profile) grid runs in one compile.
    The nested vmap closes each geometry over its own sub-cell row, so
    path tables are not replicated per sub-cell.

    ``mesh`` partitions the batch across a 1-D device mesh with
    ``jax.shard_map`` instead: ``shard_axis='cell'`` splits the topology
    cells (geometries travel with their cells), ``'lane'`` splits the
    sub-cell lanes (geometries replicate — the mitigation search's
    candidate axis). Batches are padded to a mesh multiple by repeating
    lane 0 (finished lanes freeze under the vmapped while_loop, so real
    lanes are unaffected) and sliced back. NOTE: multi-device shard_map
    executables may differ from the single-device path by ~1 ulp in the
    float accumulators (XLA's partitioned compile reassociates — a
    measured, deterministic effect; DESIGN.md §14). The bit-exact
    multi-device path is launch.sweep's per-device dispatch."""
    ensure_compile_cache()
    backend = resolve_step_backend(backend)
    if mesh is None:
        return _run_cells_hetero_jit(geoms, params, n_iters, chunk=chunk,
                                     max_chunks=max_chunks, stride=stride,
                                     backend=backend, metrics=metrics,
                                     with_trace=with_trace)
    if shard_axis not in ("cell", "lane"):
        raise ValueError(f"shard_axis must be 'cell' or 'lane', "
                         f"got {shard_axis!r}")
    n_dev = int(mesh.devices.size)
    axis, = mesh.axis_names
    if shard_axis == "cell":
        n_real = _leading_dim(geoms)
        geoms = pad_batch(geoms, n_dev)
        params = pad_batch(params, n_dev)
    else:
        n_real = _leading_dim(params, axis=1)
        params = pad_batch(params, n_dev, axis=1)
    fn = _sharded_hetero_jit(mesh, axis, shard_axis, chunk, max_chunks,
                             stride, backend, donate, metrics, with_trace)
    out = fn(geoms, params, n_iters)
    take = 0 if shard_axis == "cell" else 1
    return {k: jax.lax.slice_in_dim(v, 0, n_real, axis=take)
            for k, v in out.items()}


def _leading_dim(tree, axis: int = 0) -> int:
    return int(jax.tree_util.tree_leaves(tree)[0].shape[axis])


def pad_batch(tree, multiple: int, axis: int = 0):
    """Pad every leaf's ``axis`` up to a multiple of ``multiple`` by
    repeating index 0 (a real, already-validated cell — never garbage:
    padded lanes run redundant work and are sliced off, and under the
    vmapped while_loop they cannot perturb real lanes)."""
    n = _leading_dim(tree, axis)
    target = -(-n // multiple) * multiple
    if target == n:
        return tree

    def pad(x):
        fill = np.repeat(np.take(np.asarray(x), [0], axis=axis),
                         target - n, axis=axis)
        return np.concatenate([np.asarray(x), fill], axis=axis)

    return jax.tree_util.tree_map(pad, tree)


# One jitted shard_map entry per (mesh, shard axis, static engine args):
# meshes are hashable, so the builder memoizes — re-launching on the same
# mesh reuses the executable (asserted via TRACE_COUNTS in test_sweep.py).
_SHARDED_JITS: dict = {}


def _sharded_hetero_jit(mesh, axis: str, shard_axis: str, chunk: int,
                        max_chunks: int, stride: int, backend: str,
                        donate: bool, metrics: bool = False,
                        with_trace: bool = True):
    key = (mesh, axis, shard_axis, chunk, max_chunks, stride, backend,
           donate, metrics, with_trace)
    fn = _SHARDED_JITS.get(key)
    if fn is not None:
        return fn
    from jax.sharding import PartitionSpec as P
    if shard_axis == "cell":
        in_specs = (P(axis), P(axis), P())
        out_specs = P(axis)
    else:  # lane: geometries replicate, sub-cell lanes split
        in_specs = (P(), P(None, axis), P())
        out_specs = P(None, axis)

    def sharded(geoms, params, n_iters):
        TRACE_COUNTS["run_cells_hetero_sharded"] += 1

        def shard(g, ps, ni):
            return jax.vmap(lambda gg, row: jax.vmap(
                lambda pp: _run_cell(gg, pp, ni, chunk, max_chunks,
                                     stride, backend, metrics,
                                     with_trace))(row))(g, ps)

        return jax.shard_map(shard, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(
                                 geoms, params, n_iters)

    # buffer donation frees the params stack for the outputs; XLA CPU
    # does not implement donation (it would only warn), so gate on backend
    donate_argnums = (1,) if donate and jax.default_backend() != "cpu" \
        else ()
    fn = jax.jit(sharded, donate_argnums=donate_argnums)
    _SHARDED_JITS[key] = fn
    return fn


# --------------------------------------------------------------------------
# Result marshalling (host side)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class SimResult:
    iter_times: np.ndarray  # (n_done - warmup,) seconds per victim iteration
    n_done: int
    mean_qdelay_s: float  # mean victim queueing delay per step
    victim_rate_trace: np.ndarray  # (T_sub,) aggregate victim goodput B/s
    time_trace: np.ndarray
    # False when the run finished too few iterations to discard the full
    # warmup prefix (n_done <= warmup): iter_times then holds only the
    # LAST completed iteration (closest to steady state) — a usable but
    # warmup-tainted estimate that callers must not report silently
    warmup_ok: bool = True


def _drop_warmup(times: np.ndarray, n_done: int, warmup: int):
    """Discard the warmup prefix of per-iteration times. When the run
    completed fewer than warmup+1 iterations, every iteration is warmup:
    keep only the last one (never silently average a warmup-dominated
    prefix — the pre-fix behavior) and report ``warmup_ok=False``."""
    if n_done > warmup:
        return times[warmup:], True
    return times[max(0, n_done - 1):], False


def summarize(out: dict, *, n_iters: int, warmup: int, dt: float,
              chunk: int, stride: int, cell: Optional[int] = None,
              job: int = 0) -> SimResult:
    """Build a :class:`SimResult` from (optionally batched) run outputs.
    ``job`` selects which job's program completions to report (0 = the
    primary job; background jobs may have closed fewer iterations)."""
    pick = (lambda x: np.asarray(x)) if cell is None else \
        (lambda x: np.asarray(x)[cell])
    n_done = min(int(pick(out["it"])[job]), n_iters, TDONE_SLOTS)
    t_done = pick(out["t_done"])[job][:n_done]
    iter_times = np.diff(np.concatenate([[0.0], t_done]))
    iter_times, warmup_ok = _drop_warmup(iter_times, n_done, warmup)
    total_t = float(pick(out["t"])) or 1e-9
    n_valid = int(pick(out["chunks"])) * (chunk // stride)
    trace = pick(out["trace"])[:n_valid]
    return SimResult(
        iter_times=iter_times,
        n_done=n_done,
        mean_qdelay_s=float(pick(out["qd_acc"])) / total_t,
        victim_rate_trace=trace,
        time_trace=np.arange(n_valid) * stride * dt,
        warmup_ok=warmup_ok,
    )


# --------------------------------------------------------------------------
# Object façade (compat): one geometry + one cc, sequential runs
# --------------------------------------------------------------------------


class FabricSim:
    """Thin wrapper over the pure-functional engine for single-experiment
    use. Sweeps should go through bench.run_grid, which batches cells."""

    def __init__(self, topo: Topology, flows: FlowSet, cc: CCParams,
                 routing: int = ROUTE_FIXED, dt: float = 10e-6,
                 maxmin_iters: int = 4, seed: int = 0):
        self.topo = topo
        self.flows = flows
        self.cc = cc
        self.dt = float(dt)
        # legacy routing flag (cc.ROUTE_*) -> traced policy id: FIXED
        # replays the host-side static table baked into the flow set
        self.policy = POLICY_ADAPTIVE if routing == ROUTE_ADAPTIVE \
            else POLICY_FIXED
        self.geom = make_geometry(topo, flows)

    def params(self, profile=None) -> SimParams:
        profile = profile or no_congestion()
        return make_params(
            self.cc, dt=self.dt, bytes_per_iter=self.flows.bytes_per_iter,
            host_caps=self.flows.host_caps, env=profile.params(),
            policy=self.policy)

    def run(self, *, n_iters: int = 60, warmup: int = 10, profile=None,
            max_steps: int = 400_000, chunk: int = 2048,
            trace_stride: int = 8) -> SimResult:
        """Run until ``n_iters`` victim iterations complete (or budget)."""
        check_iter_budget(n_iters)
        max_chunks = -(-max_steps // chunk)
        out = run_cell(self.geom, self.params(profile),
                       jnp.asarray(n_iters, jnp.int32), chunk=chunk,
                       max_chunks=max_chunks, stride=trace_stride)
        return summarize(out, n_iters=n_iters, warmup=warmup, dt=self.dt,
                         chunk=chunk, stride=trace_stride)
