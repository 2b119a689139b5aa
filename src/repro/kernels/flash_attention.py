"""Blockwise (flash) causal attention Pallas kernel — TPU target.

Grid: (batch, kv_head, q_block). Each program holds one q tile in VMEM and
loops over kv tiles with running (max, sum, acc) fp32 accumulators — the
same online-softmax recurrence as the XLA-native path in
``models/layers.flash_attention_xla`` (which the dry-run lowers, since
Mosaic does not target the CPU backend; see DESIGN.md §9).

Block sizes default to (q=128, kv=128): tiles of 128x128 keep the MXU fully
occupied and the VMEM working set per program is
q(128xD) + k/v(128xD each) + acc(128xD f32) ~= 0.4 MiB at D=128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _kernel(q_ref, k_ref, v_ref, o_ref, *, block_q: int, block_kv: int,
            seq_kv: int, causal: bool, scale: float):
    g = q_ref.shape[1]
    d = q_ref.shape[-1]
    q = q_ref[...].astype(jnp.float32) * scale  # (bq, G, D)
    qi = pl.program_id(2)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    def body(ci, carry):
        acc, m, l = carry
        kc = k_ref[pl.ds(ci * block_kv, block_kv), :].astype(
            jnp.float32)  # (bkv, D)
        vc = v_ref[pl.ds(ci * block_kv, block_kv), :].astype(jnp.float32)
        s = jax.lax.dot_general(q.reshape(-1, d), kc,
                                (((1,), (1,)), ((), ())))  # (bq*G, bkv)
        s = s.reshape(block_q, g, block_kv)
        kv_pos = ci * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1)
        mask = kv_pos < seq_kv
        if causal:
            mask = mask & (q_pos >= kv_pos)
        s = jnp.where(mask[:, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[:, None, :], p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(p.reshape(-1, block_kv), vc,
                                 (((1,), (0,)), ((), ())))
        acc_new = acc * corr.reshape(block_q, g, 1) + pv.reshape(
            block_q, g, d)
        return acc_new, m_new, l_new

    n_kv = pl.cdiv(seq_kv, block_kv)
    if causal:
        # skip fully-masked kv blocks beyond the diagonal
        n_kv_eff = jnp.minimum(
            n_kv, (qi + 1) * block_q // block_kv + 1).astype(jnp.int32)
    else:
        n_kv_eff = n_kv
    acc0 = jnp.zeros((block_q, g, d), jnp.float32)
    m0 = jnp.full((block_q, g), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q, g), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_kv_eff, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_kv: int = 128, interpret: bool = True):
    """q: (B, Sq, H, D); k/v: (B, Skv, KH, D); GQA via H = KH * G."""
    B, Sq, H, D = q.shape
    _, Skv, KH, _ = k.shape
    G = H // KH
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    scale = 1.0 / np.sqrt(D)
    # pad KV to a block multiple so in-kernel dslice loads stay in bounds;
    # padded keys are masked out via seq_kv inside the kernel
    if Skv % bkv:
        pad = bkv - Skv % bkv
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Skv_pad = k.shape[1]
    qr = q.reshape(B, Sq, KH, G, D).transpose(0, 2, 1, 3, 4)  # (B,KH,Sq,G,D)
    kr = k.transpose(0, 2, 1, 3)  # (B, KH, Skv_pad, D)
    vr = v.transpose(0, 2, 1, 3)
    grid = (B, KH, pl.cdiv(Sq, bq))
    out = pl.pallas_call(
        functools.partial(_kernel, block_q=bq, block_kv=bkv, seq_kv=Skv,
                          causal=causal, scale=scale),
        out_shape=jax.ShapeDtypeStruct((B, KH, Sq, G, D), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, bq, G, D), lambda b, h, i: (b, h, i, 0, 0)),
            pl.BlockSpec((None, None, Skv_pad, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((None, None, Skv_pad, D), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, G, D),
                               lambda b, h, i: (b, h, i, 0, 0)),
        interpret=interpret,
    )(qr, kr, vr)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, Sq, H, D)
