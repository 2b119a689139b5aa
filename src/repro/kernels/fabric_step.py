"""Fused Pallas kernel for the fabric simulator's per-step hot core.

After PR 5 the whole characterization grid runs as one ``jit(vmap(vmap))``
over the simulator scan, so the per-step scatter/segment-sum core of
``fabric/simulator._step_impl`` dominates wall-clock: ~10 separate
O(F*H) scatter/gather passes over the packed path table (``plinks``) —
NIC segment-sum, three backpressure segment-reductions, and per hop a
link-load scatter, an over-subscription gather, and (under ``step_debug``)
a served-rate scatter. XLA lowers each as an independent HBM-round-trip
scatter with full-size zero-init.

This kernel fuses the whole core into ONE launch that keeps flow rows and
per-link state resident in VMEM across hops (DESIGN.md §13):

* Scatters/gathers become flow-blocked one-hot contractions: a
  (block_flows, n_out) equality mask against a ``broadcasted_iota`` link
  row, contracted on the MXU (``jnp.dot`` with fp32 accumulation). This
  is the TPU-native segment-sum lowering — Mosaic has no vector scatter,
  and the mask never touches HBM.
* Segment-max (``sw_sat``) uses the same mask with a masked ``jnp.max``
  (order-independent, so it is exact vs the reference scatter-max).
* The H-hop loop is unrolled in-kernel (H is static geometry meta); the
  flow- and link-block loops are ``lax.fori_loop``s over 128-aligned
  slices, so the kernel body (and its compile time) does not grow with
  F x L. The per-flow rate vector ``r`` lives in the ``achieved`` output
  ref, updated hop by hop, and never leaves VMEM.

Exactness contract: identical arithmetic to ``kernels.ref.fabric_step_core``
except that one-hot contractions may sum a link's contributions in a
different order than XLA's scatter-add — fp32-allclose always, and
bit-exact whenever every (link, hop) has at most one contributing flow
(tests/test_kernels.py pins both). The reference stays the default on CPU
and in interpret mode; ``REPRO_FABRIC_KERNEL=pallas`` (or
``simulator.set_step_backend``) routes the engine through this kernel.

VMEM budget (defaults, fp32): the dominant residents are one
(block_flows, L+1) one-hot tile (128 x 4096 -> 2 MiB; 7.1 MiB at the
4096-node engine cell's L=14559), the per-link rows
(q/occ/caps/arrival/load: 6 x (L+1) -> ~100 KiB at L=4096), and the
per-flow rows (~4 x F). Flow/link axes are padded to block multiples with
provably inert rows (pad flows inject 0 onto the sink; pad links have
cap 1, queue 0, and are referenced by no path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _onehot(idx, n_out):
    """(B,) int32 -> (B, n_out) fp32 equality mask (the scatter/gather
    surrogate: dot(vals, onehot) == segment-sum, dot(onehot, col) ==
    gather). iota is 2D (broadcasted_iota) per the Mosaic constraint."""
    ids = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], n_out), 1)
    return (idx[:, None] == ids).astype(jnp.float32)


def _dot(a, b):
    """fp32 contraction at full precision: the MXU's default f32 pass
    rounds operands to bf16, which would round the rates it carries."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _kernel(plinks_ref, inject_ref, src_id_ref, host_caps_ref, q_ref,
            occ_ref, caps_finite_ref, src_sw_ref, dst_sw_ref, s_ref,
            *out_refs, sink: int, n_src: int, n_sw: int, bf: int, bl: int,
            with_aux: bool):
    inject_out_ref, a_ref, arrival_ref, qnew_ref, caps_eff_ref = out_refs[:5]
    dt = s_ref[0, 0]
    qmax_bytes = s_ref[0, 1]
    hol_factor = s_ref[0, 2]
    hol_start = s_ref[0, 3]
    burst_jitter = s_ref[0, 4]

    H, F = plinks_ref.shape          # flow axis padded to a bf multiple
    Lp = q_ref.shape[1]              # link axis padded to a bl multiple
    n_fb, n_lb = F // bf, Lp // bl
    zeros_l = jnp.zeros((1, Lp), jnp.float32)

    # Block loops are lax.fori_loops over aligned dynamic slices, so the
    # kernel body stays the same size at any F and L; blocks are visited
    # in index order, so every accumulation sums in the same order as a
    # fully unrolled loop would.
    def fsl(fb):
        return pl.ds(pl.multiple_of(fb * bf, bf), bf)

    def lsl(lb):
        return pl.ds(pl.multiple_of(lb * bl, bl), bl)

    # ---- NIC limit: src_load segment-sum, then per-flow gather+scale ----
    def src_sum(fb, src_load):
        sl = fsl(fb)
        sel = _onehot(src_id_ref[0, sl], n_src)
        return src_load + _dot(inject_ref[0, sl][None, :], sel)

    src_load = jax.lax.fori_loop(0, n_fb, src_sum,
                                 jnp.zeros((1, n_src), jnp.float32))

    def nic_scale(fb, carry):
        sl = fsl(fb)
        sel = _onehot(src_id_ref[0, sl], n_src)
        mine = _dot(sel, src_load.T)[:, 0]
        scale = jnp.minimum(1.0, host_caps_ref[0, sl]
                            / jnp.maximum(mine, 1.0))
        inj = (inject_ref[0, sl] * scale)[None, :]
        inject_out_ref[:, sl] = inj  # (1, F), NIC-scaled
        a_ref[:, sl] = inj  # the per-flow rate r, updated hop by hop
        return carry

    jax.lax.fori_loop(0, n_fb, nic_scale, 0)

    # ---- backpressure: hot_q/tot_q segment-sums + sw_sat segment-max ----
    def sw_reduce(lb, carry):
        hot_q, tot_q, sw_sat = carry
        sl = lsl(lb)
        sat_b = jnp.clip((occ_ref[0, sl] - hol_start)
                         / (1.0 - hol_start), 0.0, 1.0)
        q_b = q_ref[0, sl]
        sel = _onehot(src_sw_ref[0, sl], n_sw)
        hot_q = hot_q + _dot((q_b * sat_b)[None, :], sel)
        tot_q = tot_q + _dot(q_b[None, :], sel)
        # masked max: exact (order-free) surrogate of .at[].max on zeros
        sw_sat = jnp.maximum(
            sw_sat, jnp.max(sel * sat_b[:, None], axis=0)[None, :])
        return hot_q, tot_q, sw_sat

    zeros_sw = jnp.zeros((1, n_sw), jnp.float32)
    hot_q, tot_q, sw_sat = jax.lax.fori_loop(
        0, n_lb, sw_reduce, (zeros_sw, zeros_sw, zeros_sw))
    share = hot_q / jnp.maximum(tot_q, 1.0)
    stall = 1.0 - hol_factor * sw_sat * share
    sw_ids = jax.lax.broadcasted_iota(jnp.int32, (1, n_sw), 1)
    stall = jnp.where(sw_ids == 0, 1.0, stall)  # 0 == host endpoint

    def stall_caps(lb, carry):
        sl = lsl(lb)
        sel = _onehot(dst_sw_ref[0, sl], n_sw)
        st = _dot(sel, stall.T)[:, 0]
        caps_eff_ref[:, sl] = (caps_finite_ref[0, sl] * st)[None, :]
        return carry

    jax.lax.fori_loop(0, n_lb, stall_caps, 0)
    caps_eff = caps_eff_ref[...]  # (1, Lp)

    # ---- H-hop staged propagation: flow rows resident across hops ----
    arrival = zeros_l
    served_max = zeros_l
    for h in range(H):
        def link_load(fb, load, h=h):
            sl = fsl(fb)
            lk = plinks_ref[h, sl]
            contrib = a_ref[0, sl] * (lk < sink).astype(jnp.float32)
            return load + _dot(contrib[None, :], _onehot(lk, Lp))

        load = jax.lax.fori_loop(0, n_fb, link_load, zeros_l)
        arrival = arrival + load
        over = jnp.maximum(load / caps_eff, 1.0)

        def share_rate(fb, served, h=h, over=over):
            sl = fsl(fb)
            lk = plinks_ref[h, sl]
            validh = lk < sink
            sel = _onehot(lk, Lp)
            og = _dot(sel, over.T)[:, 0]
            r = a_ref[0, sl]
            r_b = jnp.where(validh, r / og, r)
            a_ref[:, sl] = r_b[None, :]
            if with_aux:
                served = served + _dot(
                    (r_b * validh.astype(jnp.float32))[None, :], sel)
            return served

        served = jax.lax.fori_loop(0, n_fb, share_rate, zeros_l)
        if with_aux:
            served_max = jnp.maximum(served_max, served)
    arrival_ref[...] = arrival

    # ---- queue update ----
    link_ids = jax.lax.broadcasted_iota(jnp.int32, (1, Lp), 1)
    q_new = jnp.clip(q_ref[...] + (arrival * (1.0 + burst_jitter)
                                   - caps_eff) * dt,
                     0.0, qmax_bytes)
    qnew_ref[...] = jnp.where(link_ids == sink, 0.0, q_new)
    if with_aux:
        out_refs[5][...] = served_max


@functools.partial(jax.jit, static_argnames=(
    "n_src", "n_sw", "with_aux", "interpret", "block_flows", "block_links"))
def fabric_step_core(plinks, inject, src_id, host_caps, q, occ, caps_finite,
                     src_sw, dst_sw, dt, qmax_bytes, hol_factor, hol_start,
                     burst_jitter, *, n_src: int, n_sw: int,
                     with_aux: bool = False, interpret: bool = True,
                     block_flows: int = 128, block_links: int = 256):
    """Fused fabric-step core (one kernel launch). Same signature and
    return dict as :func:`repro.kernels.ref.fabric_step_core` (the
    oracle); ``interpret=True`` runs the kernel through the Pallas
    interpreter (the only mode available off-TPU). Vmappable — the
    batched engine entries (``run_cells``/``run_cells_hetero``) vmap this
    along with the rest of the step.

    ``caps_finite`` may arrive already scaled by the link-fault engine
    (envelopes.fault_scale_at, DESIGN.md §16): the simulator folds the
    time-varying per-link fault scale into this operand OUTSIDE the
    launch, so fault scenarios ride through the kernel as plain data and
    the body stays byte-identical to the fault-free build."""
    F, H = plinks.shape
    Lp1 = q.shape[0]
    sink = Lp1 - 1
    # blocks are whole 128-lane tiles: Mosaic slices the lane axis only
    # at offsets it can prove are multiples of 128
    bf = min(block_flows, _round_up(max(F, 1), 128))
    bl = min(block_links, _round_up(Lp1, 128))
    Fp, Lp = _round_up(max(F, 1), bf), _round_up(Lp1, bl)

    def pad_f(x, value, dtype):
        return jnp.pad(x.astype(dtype), (0, Fp - F), constant_values=value)

    def pad_l(x, value, dtype):
        return jnp.pad(x.astype(dtype), (0, Lp - Lp1), constant_values=value)

    # inert padding: pad flows inject 0 onto the sink from source 0; pad
    # links carry cap 1 / queue 0 and hang off switch 0 (the host bucket)
    # path table hop-major (H, Fp): each hop's link ids are one lane-dense
    # row of the kernel's flow blocks
    plinks_p = jnp.pad(plinks.astype(jnp.int32).T,
                       ((0, 0), (0, Fp - F)), constant_values=sink)
    args = (
        plinks_p,
        pad_f(inject, 0.0, jnp.float32)[None, :],
        pad_f(src_id, 0, jnp.int32)[None, :],
        pad_f(host_caps, 1.0, jnp.float32)[None, :],
        pad_l(q, 0.0, jnp.float32)[None, :],
        pad_l(occ, 0.0, jnp.float32)[None, :],
        pad_l(caps_finite, 1.0, jnp.float32)[None, :],
        pad_l(src_sw, 0, jnp.int32)[None, :],
        pad_l(dst_sw, 0, jnp.int32)[None, :],
        jnp.stack([dt, qmax_bytes, hol_factor, hol_start,
                   burst_jitter]).astype(jnp.float32)[None, :],
    )
    fvec = jax.ShapeDtypeStruct((1, Fp), jnp.float32)
    lvec = jax.ShapeDtypeStruct((1, Lp), jnp.float32)
    out_shape = [fvec, fvec, lvec, lvec, lvec] + ([lvec] if with_aux else [])
    outs = pl.pallas_call(
        functools.partial(_kernel, sink=sink, n_src=n_src, n_sw=n_sw,
                          bf=bf, bl=bl, with_aux=with_aux),
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    inject_s, a, arrival, q_new, caps_eff = [o[0] for o in outs[:5]]
    return {"inject": inject_s[:F], "achieved": a[:F],
            "arrival": arrival[:Lp1], "q_new": q_new[:Lp1],
            "caps_eff": caps_eff[:Lp1],
            "served_stage_max": outs[5][0][:Lp1] if with_aux else None}
