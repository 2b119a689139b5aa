"""Plain reference of the fluid fabric model, for the benchmark's check.

It answers the same question as one grid of ``repro.core.bench.run_grid``
(a ring AllGather victim on an interleaved allocation, one baseline and
one congested lane per vector size) from the configuration and traffic
files alone: it builds the machine's topology, the allocation, the flows
and their candidate paths, and steps the fluid model one lane at a time
with plain scatter-adds. It imports nothing of the program and takes
nothing the program has made.

What differs between fabrics and mixes lives in files of its own, found
by name (``chipbench/spec.py``): the topology family
(``families/<topology.family>.py``), the CC rule (``cc/<cc.kind>.py``)
and the aggressor's envelope (``profiles/<profile>.py``; the baseline
lane's is ``off``). The rule and the envelope are bound into a lane's
compiled step, one program per rule and envelope.

The semantics follow the paper's protocol as the program states it
(DESIGN.md §7, §13): NIC injection limit, lossless back-pressure
head-of-line stall, FIFO fluid sharing hop by hop, the configuration's
rate control, adaptive routing with hysteresis around a sprayed home
path, iterations closing when the victim's slowest flow drains, and the
early exit checked every ``chunk`` steps. Only what the benchmark's
traffic uses is here: no phases, faults or intra-node stage.

``dtype`` sets the precision of the step's arithmetic. The clock, the
iteration completion times and the queueing-delay sum stay float32, so a
lower precision is judged on the fabric's arithmetic, not on a clock
that stops advancing.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec
from chipbench.topology import Topology

# The protocol's fixed numbers (paper §III as the program implements it).
LAT_PER_STEP_S = 2e-6       # analytic latency per serialized schedule step
DT_LADDER_S = tuple(2.0 ** k * 1e-6 for k in range(8))
TDONE_SLOTS = 96
ENDLESS_BYTES = 1e30
SPRAY_MULT = 2654435761

_U64 = np.uint64


# --------------------------------------------------------------------------
# topology: the family file the configuration names
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=4)
def _machine(base: str, family: str, machine_nodes: int,
             params: tuple) -> Topology:
    return spec.family(family, base)(machine_nodes, **dict(params))


def machine(config: dict, base: str = spec.HERE) -> Topology:
    """The whole machine, built by ``families/<topology.family>.py``."""
    topo = dict(config["topology"])
    family = topo.pop("family")
    return _machine(base, family, int(config["machine_nodes"]),
                    tuple(sorted(topo.items())))


# --------------------------------------------------------------------------
# allocation and flows
# --------------------------------------------------------------------------


def _splitmix64(x):
    with np.errstate(over="ignore"):
        z = np.asarray(x, _U64) + _U64(0x9E3779B97F4A7C15)
        z = (z ^ (z >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> _U64(27))) * _U64(0x94D049BB133111EB)
        return z ^ (z >> _U64(31))


def allocate(machine_nodes: int, n_nodes: int, seed: int = 7) -> np.ndarray:
    """A scattered allocation as a batch scheduler hands it out: the
    (seed, size) pair mixed through splitmix64 seeds a uniform draw."""
    if n_nodes >= machine_nodes:
        return np.arange(machine_nodes)
    mixed = _splitmix64((_U64(seed) << _U64(32)) | _U64(np.uint32(n_nodes)))
    rng = np.random.RandomState(int(mixed & _U64(0xFFFFFFFF)))
    return np.sort(rng.choice(machine_nodes, size=n_nodes, replace=False))


def flows(victims: Sequence[int], aggressors: Sequence[int], aggressor: str):
    """(src, dst, bytes at unit vector size, is_victim): the victim's
    flattened ring AllGather, then the endless aggressor loop."""
    n = len(victims)
    out = [(victims[i], victims[(i + 1) % n], (n - 1) / n, True)
           for i in range(n)]
    if aggressor == "incast":
        out += [(a, aggressors[0], ENDLESS_BYTES, False) for a in aggressors[1:]]
    elif aggressor == "alltoall":
        out += [(a, b, ENDLESS_BYTES, False)
                for a in aggressors for b in aggressors if a != b]
    else:
        raise KeyError(aggressor)
    return out


@dataclasses.dataclass
class Case:
    """One (fabric, allocation, aggressor) experiment, bound to its links."""
    n_victims: int
    caps_max: float                  # fastest link of the whole machine
    caps: np.ndarray                 # (L+1,) used links, sink cap 1
    src_sw: np.ndarray               # (L+1,) 1 + switch feeding the link
    dst_sw: np.ndarray               # (L+1,) 1 + switch the link feeds
    n_sw: int
    paths: np.ndarray                # (F, K, H), pad = sink = L
    n_paths: np.ndarray              # (F,)
    path_len: np.ndarray             # (F, K)
    spray: np.ndarray                # (F,)
    src: np.ndarray                  # (F,) dense source id
    n_src: int
    host_caps: np.ndarray            # (F,)
    unit_bytes: np.ndarray           # (F,)
    is_victim: np.ndarray            # (F,)

    @property
    def lat(self) -> float:
        return (self.n_victims - 1) * LAT_PER_STEP_S


def build_case(config: dict, n_nodes: int, aggressor: str,
               base: str = spec.HERE) -> Case:
    topo = machine(config, base)
    k_max = int(config["routing"]["k_max"])
    nodes = allocate(int(config["machine_nodes"]), n_nodes)
    ids = np.arange(n_nodes)
    victims, aggressors = nodes[ids % 2 == 0], nodes[ids % 2 == 1]
    fl = flows([int(x) for x in victims], [int(x) for x in aggressors],
               aggressor)
    cand = [topo.paths(s, d) for s, d, _, _ in fl]
    L_full = len(topo.caps)
    F = len(fl)
    H = max(len(p) for ps in cand for p in ps)
    paths = np.full((F, k_max, H), L_full, np.int64)
    n_paths = np.zeros(F, np.int64)
    path_len = np.zeros((F, k_max), np.float64)
    for f, ps in enumerate(cand):
        ps = ps[:k_max]
        n_paths[f] = len(ps)
        for k, p in enumerate(ps):
            paths[f, k, :len(p)] = p
            path_len[f, k] = len(p)
    # keep only the links some candidate path uses, in link order
    used = np.unique(paths[paths < L_full])
    L = len(used)
    remap = np.full(L_full + 1, L, np.int64)
    remap[used] = np.arange(L)
    paths = remap[paths]
    switches: Dict = {}
    src_sw = np.zeros(L + 1, np.int64)
    dst_sw = np.zeros(L + 1, np.int64)
    for li, gi in enumerate(used):
        a, b = topo.links[int(gi)]
        if b[0] != "h":
            dst_sw[li] = 1 + switches.setdefault(b, len(switches))
        if a[0] != "h":
            src_sw[li] = 1 + switches.setdefault(a, len(switches))
    src_nodes = np.array([s for s, _, _, _ in fl])
    _, src = np.unique(src_nodes, return_inverse=True)
    spray = (np.arange(F, dtype=np.int64) * SPRAY_MULT % (1 << 31)) \
        % np.maximum(n_paths, 1)
    return Case(
        n_victims=len(victims), caps_max=float(topo.caps.max()),
        caps=np.concatenate([topo.caps[used], [1.0]]),
        src_sw=src_sw, dst_sw=dst_sw, n_sw=len(switches) + 2,
        paths=paths, n_paths=n_paths, path_len=path_len, spray=spray,
        src=src, n_src=int(src.max()) + 1,
        host_caps=np.array([topo.caps[ps[0][0]] for ps in cand]),
        unit_bytes=np.array([u for _, _, u, _ in fl]),
        is_victim=np.array([v for _, _, _, v in fl]))


def choose_dt(case: Case, vector_bytes: float) -> float:
    """dt sized so one uncongested iteration spans ~100 steps, snapped
    down to the power-of-two microsecond ladder."""
    per_flow = vector_bytes / max(case.n_victims, 1)
    t_est = max(per_flow / case.caps_max, 2e-6) * 2 + case.lat
    raw = float(np.clip(t_est / 100, 1e-6, 200e-6))
    return max([d for d in DT_LADDER_S if d <= raw], default=DT_LADDER_S[0])


# --------------------------------------------------------------------------
# the fluid step, one lane
# --------------------------------------------------------------------------


def _step(g, p, s, dtype, rule, envelope):
    """One dt of the fluid model. ``g`` holds the case's arrays, ``p`` the
    lane's scalars, byte budgets and envelope arguments, ``s`` the state;
    ``rule`` is the CC rule's ``update`` and ``envelope`` the aggressor
    profile's."""
    f = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    L = g["caps"].shape[0] - 1
    dt = f(p["dt"])
    qmax = f(p["qmax_bytes"])
    vict = g["is_victim"]
    env = envelope(s["t"], **p["env_args"])
    alive = s["rem"] > 0
    active = (vict | (env > 0)) & alive
    inject = s["c"] * f(jnp.where(vict, 1.0, env)) * f(alive)

    # adaptive routing: least-occupied candidate, kept off the home path
    # unless the home path is clearly worse
    occ = s["q"] / qmax
    score = jnp.max(occ[g["paths"]], axis=2) \
        + f(0.05) * g["path_len"] / jnp.maximum(g["path_len"][:, :1], f(1))
    k_ids = jnp.arange(g["paths"].shape[1])
    score = jnp.where(k_ids[None, :] < g["n_paths"][:, None], score,
                      f(jnp.inf))
    best = jnp.argmin(score, axis=1)
    home = g["spray"]
    home_score = jnp.take_along_axis(score, home[:, None], 1)[:, 0]
    choice = jnp.where(home_score > jnp.min(score, axis=1) + f(0.10),
                       best, home)
    plinks = jnp.take_along_axis(g["paths"], choice[:, None, None], 1)[:, 0]
    valid = plinks < L

    # NIC: a source's flows share its injection link
    src_load = jnp.zeros(g["n_src"], dtype).at[g["src"]].add(inject)
    inject = inject * jnp.minimum(
        f(1), f(p["host_caps"]) / jnp.maximum(src_load[g["src"]], f(1)))
    # lossless back-pressure: a saturated switch stalls its feeders
    sat = jnp.clip((occ - f(p["hol_start"])) / (f(1) - f(p["hol_start"])),
                   f(0), f(1))
    n_sw = g["n_sw"]
    hot_q = jnp.zeros(n_sw, dtype).at[g["src_sw"]].add(s["q"] * sat)
    tot_q = jnp.zeros(n_sw, dtype).at[g["src_sw"]].add(s["q"])
    sw_sat = jnp.zeros(n_sw, dtype).at[g["src_sw"]].max(sat)
    stall = f(1) - f(p["hol_factor"]) * sw_sat * (
        hot_q / jnp.maximum(tot_q, f(1)))
    stall = stall.at[0].set(f(1))
    caps_eff = f(g["caps"]) * stall[g["dst_sw"]]
    # FIFO fluid sharing, hop by hop
    r = inject
    arrival = jnp.zeros(L + 1, dtype)
    for h in range(plinks.shape[1]):
        lk = plinks[:, h]
        load = jnp.zeros(L + 1, dtype).at[lk].add(r * f(valid[:, h]))
        arrival = arrival + load
        over = jnp.maximum(load / caps_eff, f(1))
        r = jnp.where(valid[:, h], r / over[lk], r)
    q = jnp.clip(s["q"] + (arrival * f(1 + p["burst_jitter"]) - caps_eff) * dt,
                 f(0), qmax)
    q = q.at[L].set(f(0))

    # rate control: the configuration's CC rule on its marks and timers
    thresh = f(p["kmin"]) * qmax
    mark = jnp.any((q > thresh)[plinks] & valid, axis=1)
    can_dec = s["last_dec"] >= p["cc_interval_s"]
    hc = f(p["host_caps"])
    follow = f(1) - jnp.exp(-dt / jnp.maximum(f(p["follow_tau_s"]), f(1e-9)))
    inc = f(p["rai_frac"]) * hc * (dt / f(1e-3))
    c, dec = rule(s["c"], r, mark, can_dec, follow, inc, hc, p)
    c = jnp.where(active, c, s["c"])
    dec = dec & active
    c = jnp.clip(c, f(p["min_rate_frac"]) * hc, hc)
    last_dec = jnp.where(dec, 0.0, s["last_dec"] + p["dt"])

    # progress: an iteration closes when the victim's last flow drains
    rem = s["rem"] - r * dt
    t_new = s["t"] + p["dt"]
    wrap = ~jnp.any(vict & (rem > 0))
    rem = jnp.where(wrap & vict, f(p["bytes"]), rem)
    slot = jnp.minimum(s["it"], TDONE_SLOTS - 1)
    t_done = jnp.where(wrap & (jnp.arange(TDONE_SLOTS) == slot), t_new,
                       s["t_done"])
    q = jnp.where(wrap, q * f(p["iter_drain"]), q)
    qdel = jnp.max(jnp.where(valid, (q / f(g["caps"]))[plinks], f(0)), axis=1)
    mean_qdel = jnp.sum(qdel.astype(jnp.float32) * vict) / jnp.maximum(
        jnp.sum(vict), 1)
    return {"c": c, "rem": rem, "q": q, "last_dec": last_dec,
            "it": s["it"] + wrap.astype(jnp.int32), "t_done": t_done,
            "t": t_new, "qd_acc": s["qd_acc"] + mean_qdel * p["dt"]}


@functools.partial(jax.jit, static_argnames=("n_sw", "n_src", "chunk",
                                             "max_chunks", "dtype", "rule",
                                             "envelope"))
def _run_lane(g, p, n_iters, *, n_sw, n_src, chunk, max_chunks, dtype, rule,
              envelope):
    g = dict(g, n_sw=n_sw, n_src=n_src)
    F = g["is_victim"].shape[0]
    state = {"c": jnp.asarray(p["host_caps"], dtype),
             "rem": jnp.asarray(p["bytes"], dtype),
             "q": jnp.zeros(g["caps"].shape[0], dtype),
             "last_dec": jnp.zeros(F, jnp.float32),
             "it": jnp.zeros((), jnp.int32),
             "t_done": jnp.zeros(TDONE_SLOTS, jnp.float32),
             "t": jnp.zeros((), jnp.float32),
             "qd_acc": jnp.zeros((), jnp.float32)}

    def cond(carry):
        s, k = carry
        return (k < max_chunks) & (s["it"] < n_iters)

    def body(carry):
        s, k = carry
        s = jax.lax.fori_loop(
            0, chunk, lambda _, x: _step(g, p, x, dtype, rule, envelope), s)
        return s, k + 1

    s, k = jax.lax.while_loop(cond, body, (state, jnp.zeros((), jnp.int32)))
    return {"it": s["it"], "t_done": s["t_done"], "t": s["t"],
            "qd_acc": s["qd_acc"], "chunks": k}


def launch_lane(case: Case, cc: dict, vector_bytes: float, profile: str,
                dt: float, *, n_iters: int, chunk: int, max_chunks: int,
                profile_args: dict = None, dtype=jnp.float32, device=None,
                base: str = spec.HERE) -> dict:
    """Dispatch one lane, stepped until its victim has closed ``n_iters``
    iterations, checked every ``chunk`` steps. The aggressor follows
    ``profiles/<profile>.py`` with ``profile_args``, and the flows' rates
    ``cc/<cc["kind"]>.py``. Returns device arrays."""
    bytes_ = np.where(case.is_victim, case.unit_bytes * vector_bytes,
                      case.unit_bytes)
    g = {"caps": case.caps.astype(np.float32),
         "src_sw": case.src_sw.astype(np.int32),
         "dst_sw": case.dst_sw.astype(np.int32),
         "paths": case.paths.astype(np.int32),
         "n_paths": case.n_paths.astype(np.int32),
         "path_len": case.path_len.astype(np.float32),
         "spray": case.spray.astype(np.int32),
         "src": case.src.astype(np.int32),
         "is_victim": case.is_victim}
    p = {k: np.float32(v) for k, v in cc.items() if k != "kind"}
    p.update(dt=np.float32(dt),
             env_args={k: np.float32(v)
                       for k, v in (profile_args or {}).items()},
             host_caps=case.host_caps.astype(np.float32),
             bytes=bytes_.astype(np.float32))
    g, p = jax.device_put((g, p), device)
    return _run_lane(g, p, np.int32(n_iters), n_sw=case.n_sw,
                     n_src=case.n_src, chunk=chunk, max_chunks=max_chunks,
                     dtype=dtype, rule=spec.cc_rule(cc["kind"], base),
                     envelope=spec.profile(profile, base).envelope)


# --------------------------------------------------------------------------
# the answer, as the protocol reports it
# --------------------------------------------------------------------------


def lane_answer(out, lat: float, n_iters: int, warmup: int):
    """(iterations done, reported mean iteration time, mean of the
    measured iterations alone). Warm-up iterations are dropped, or all
    but the last where no more were done; the reported time adds the
    analytic latency and the mean victim queueing delay over the run."""
    n_done = min(int(out["it"]), n_iters, TDONE_SLOTS)
    times = np.diff(np.concatenate([[0.0], out["t_done"][:n_done]]))
    times = times[warmup:] if n_done > warmup else times[max(0, n_done - 1):]
    if n_done == 0:
        return 0, float("nan"), float("nan")
    qdelay = float(out["qd_acc"]) / (float(out["t"]) or 1e-9)
    mean = float(np.mean(times))
    return n_done, mean + lat + qdelay, mean


_CASES: Dict[tuple, Case] = {}


def cached_case(config: dict, n_nodes: int, aggressor: str,
                base: str = spec.HERE) -> Case:
    key = (base, config["name"], int(n_nodes), aggressor)
    if key not in _CASES:
        _CASES[key] = build_case(config, n_nodes, aggressor, base)
    return _CASES[key]


def answer(config: dict, nodes: Sequence[int], aggressor: str,
           sizes: Sequence[float], *, n_iters: int, warmup: int, chunk: int,
           max_steps: int, profile: str = "steady", profile_args: dict = None,
           dtype=jnp.float32, devices=(None,),
           base: str = spec.HERE) -> List[dict]:
    """One grid's rows, allocation sizes major, then vector sizes: per
    size the baseline lane (aggressor idle) and the congested lane
    (aggressor on ``profile``, a traffic mix's ``profile`` and
    ``profile_args``), as ``{vector_bytes, dt, t_uncongested_s,
    t_congested_s, ratio, n_iters, victim_mean_s}``. Lanes are
    dispatched round-robin over ``devices`` before any is read back.
    ``base`` holds the part files the configuration and profile name."""
    cc = config["cc"]
    lanes = []
    for n in nodes:
        case = cached_case(config, n, aggressor, base)
        for v in sizes:
            dt = choose_dt(case, float(v))
            for prof, args in ((spec.BASELINE_PROFILE, None),
                               (profile, profile_args)):
                dev = devices[len(lanes) % len(devices)]
                lanes.append((case, float(v), dt, launch_lane(
                    case, cc, float(v), prof, dt, n_iters=n_iters,
                    chunk=chunk, max_chunks=-(-max_steps // chunk),
                    profile_args=args, dtype=dtype, device=dev,
                    base=base)))
    rows = []
    for (case, v, dt, idle), (_, _, _, cong) in zip(lanes[::2], lanes[1::2]):
        (n_u, t_u, _), (n_c, t_c, mean_c) = [
            lane_answer(jax.tree_util.tree_map(np.asarray, out), case.lat,
                        n_iters, warmup) for out in (idle, cong)]
        rows.append({"vector_bytes": v, "dt": dt,
                     "t_uncongested_s": t_u, "t_congested_s": t_c,
                     "ratio": t_u / t_c if n_u and n_c else float("nan"),
                     "n_iters": (n_u, n_c), "victim_mean_s": mean_c})
    return rows
