"""Causal flash-attention forward Pallas kernel (GQA-aware).

Online-softmax over KV blocks with the Q tile, running max/denominator
and output accumulator resident in VMEM scratch; out-of-band (fully
masked) KV blocks are skipped with pl.when, so the kernel does the
triangular FLOP count, not the rectangular one.

Layout: q (B, H, S, D), k/v (B, KV, S, D), KV | H.  Grid =
(B*H, S/BQ, S/BK) with the KV dimension innermost (revisiting scratch).
"""
from __future__ import annotations

import functools
from typing import Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gee_scatter import resolve_interpret

DEFAULT_BQ = 256
DEFAULT_BK = 256
_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq: int, bk: int, scale: float, n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal block skip: KV block strictly above the diagonal has no
    # unmasked entry.
    @pl.when(ki * bk <= qi * bq + (bq - 1))
    def _compute():
        q = q_ref[0].astype(jnp.float32)                       # (BQ, D)
        k = k_ref[0].astype(jnp.float32)                       # (BK, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = (acc_scr[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: Union[bool, str] = "auto"):
    """Causal self-attention. q: (B,H,S,D); k,v: (B,KV,S,D). Returns
    (B,H,S,D).  ``interpret`` resolves per platform like the GEE
    kernels (`gee_scatter.resolve_interpret`)."""
    interpret = resolve_interpret(interpret)
    B, H, S, D = q.shape
    KV = k.shape[1]
    G = H // KV
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    n_q, n_k = S // bq, S // bk
    scale = D ** -0.5

    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * KV, S, D)
    vf = v.reshape(B * KV, S, D)

    def kv_index(bh, qi, ki):
        b, h = bh // H, bh % H
        return (b * KV + h // G, ki, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, scale=scale, n_k=n_k),
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), kv_index),
            pl.BlockSpec((1, bk, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, D)
