"""Attention layers: GQA, sliding-window, chunked-flash, decode paths.

Three exact-softmax implementations with one math:
  * full        — dense mask, O(S^2) memory. Small seq / encoder / cross.
  * flash       — lax.map over Q chunks x lax.scan over KV chunks with
                  online softmax.  O(S * chunk) memory, compiles on any
                  backend (CPU dry-run path; Pallas kernel is the TPU twin).
  * triangular  — statically unrolled lower-triangular block loop: Q chunk
                  i attends KV[: (i+1)*C].  Halves attention FLOPs vs.
                  `flash` (which masks but still computes upper blocks).
                  This is a beyond-paper §Perf lever.

Decode:
  * plain cache attention (one-token query vs. (B, S, KV, Dh) cache)
  * ring-buffer sliding-window cache (SWA archs; O(window) memory)
  * sequence-sharded flash-decoding under shard_map with LSE merge —
    used when kv_heads < model-axis size so the cache can shard over
    sequence instead of heads (qwen1.5-110b, yi, chameleon, grok).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.distributed import shard_map
from repro.models.layers import (ParamSpec, apply_rope, ashard,
                                 head_norm_specs, rms_norm)

_NEG = -1e30


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


def attn_specs(cfg, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    sp = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", None)),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", None)),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", None)),
        "wo": ParamSpec((h, hd, d), ("heads", None, "embed"), fan_in=h * hd),
    }
    if cfg.qkv_bias:
        sp["bq"] = ParamSpec((h, hd), ("heads", None), "zeros")
        sp["bk"] = ParamSpec((kv, hd), ("kv_heads", None), "zeros")
        sp["bv"] = ParamSpec((kv, hd), ("kv_heads", None), "zeros")
    if cfg.qk_norm:
        sp["q_norm"] = head_norm_specs(cfg, h, hd)
        sp["k_norm"] = head_norm_specs(cfg, kv, hd)
    return sp


def project_qkv(cfg, p, x, positions, rope: bool = True):
    """x: (B, S, D) -> q (B,S,H,Dh), k,v (B,S,KV,Dh)."""
    cdt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cdt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"]["scale"])
        k = rms_norm(k, p["k_norm"]["scale"])
    if rope and cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = ashard(q, "batch", "seq", "heads", None)
    k = ashard(k, "batch", "seq", "kv_heads", None)
    v = ashard(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def out_proj(cfg, p, attn_out):
    """attn_out: (B, S, H, Dh) -> (B, S, D)."""
    return jnp.einsum("bshk,hkd->bsd", attn_out,
                      p["wo"].astype(attn_out.dtype))


# ---------------------------------------------------------------------------
# Exact softmax attention variants (training / prefill)
# ---------------------------------------------------------------------------


def _gqa_scores(q, k, scale):
    """q: (B,Sq,H,Dh) k: (B,Skv,KV,Dh) -> scores (B,KV,G,Sq,Skv) f32."""
    B, Sq, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, Dh).astype(jnp.float32)
    return jnp.einsum("bqkgd,bskd->bkgqs", qg,
                      k.astype(jnp.float32)) * scale


def _gqa_weighted(pweights, v):
    """pweights: (B,KV,G,Sq,Skv) f32, v: (B,Skv,KV,Dh) -> (B,Sq,H,Dh) f32."""
    B, KV, G, Sq, Skv = pweights.shape
    out = jnp.einsum("bkgqs,bskd->bqkgd", pweights, v.astype(jnp.float32))
    return out.reshape(B, Sq, KV * G, v.shape[-1])


def _mask(q_pos, kv_pos, causal: bool, window: int, kv_len=None):
    """(Sq, Skv) boolean mask (True = attend)."""
    m = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    if causal:
        m &= kv_pos[None, :] <= q_pos[:, None]
    if window:
        m &= kv_pos[None, :] > q_pos[:, None] - window
    if kv_len is not None:
        m &= kv_pos[None, :] < kv_len
    return m


def attn_full(q, k, v, q_pos, kv_pos, *, causal, window=0, scale=None):
    """Dense-mask exact attention. Memory O(Sq*Skv)."""
    scale = scale or q.shape[-1] ** -0.5
    s = _gqa_scores(q, k, scale)
    m = _mask(q_pos, kv_pos, causal, window)
    s = jnp.where(m[None, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = _gqa_weighted(p, v)
    return out.astype(q.dtype)


def _online_block(q, kb, vb, q_pos, kv_pos_b, carry, *, causal, window, scale):
    """One KV block of online-softmax. carry = (m, l, acc)."""
    m, l, acc = carry
    s = _gqa_scores(q, kb, scale)                       # (B,KV,G,Sq,C)
    msk = _mask(q_pos, kv_pos_b, causal, window)
    s = jnp.where(msk[None, None, None], s, _NEG)
    m_new = jnp.maximum(m, s.max(-1))
    alpha = jnp.exp(m - m_new)
    pexp = jnp.exp(s - m_new[..., None])
    l = l * alpha + pexp.sum(-1)
    acc = acc * alpha[..., None] + jnp.einsum(
        "bkgqs,bskd->bkgqd", pexp, vb.astype(jnp.float32))
    return m_new, l, acc


def _finish(q, l, acc):
    B, KV, G, Sq, Dh = acc.shape
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, KV * G, Dh)
    return out.astype(q.dtype)


def attn_flash(q, k, v, q_pos, kv_pos, *, causal, window=0, scale=None,
               q_chunk=1024, kv_chunk=1024):
    """Chunked online-softmax attention: lax.map over Q, lax.scan over KV.

    Baseline flash path: computes (and masks) every QxKV block, so causal
    attention does 2x the minimal FLOPs — `attn_triangular` removes that.
    """
    scale = scale or q.shape[-1] ** -0.5
    B, Sq, H, Dh = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    nq, nkv = -(-Sq // q_chunk), -(-Skv // kv_chunk)
    assert Sq % q_chunk == 0 and Skv % kv_chunk == 0, (Sq, q_chunk, Skv, kv_chunk)
    G = H // KV

    kc = k.reshape(B, nkv, kv_chunk, KV, Dh)
    vc = v.reshape(B, nkv, kv_chunk, KV, Dh)

    kvp_all = kv_pos.reshape(nkv, kv_chunk)

    def one_q_chunk(qi, unroll=False):
        qb = jax.lax.dynamic_slice_in_dim(q, qi * q_chunk, q_chunk, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(q_pos, qi * q_chunk, q_chunk)

        def kv_step(carry, inputs):
            kb, vb, kvp = inputs
            return _online_block(qb, kb, vb, qp, kvp, carry,
                                 causal=causal, window=window,
                                 scale=scale), None

        init = (jnp.full((B, KV, G, q_chunk), _NEG, jnp.float32),
                jnp.zeros((B, KV, G, q_chunk), jnp.float32),
                jnp.zeros((B, KV, G, q_chunk, Dh), jnp.float32))
        xs = (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4),
              kvp_all)
        if unroll:
            carry = init
            for j in range(nkv):
                carry, _ = kv_step(carry, jax.tree_util.tree_map(
                    lambda a, j=j: a[j], xs))
            m, l, acc = carry
        else:
            (m, l, acc), _ = jax.lax.scan(kv_step, init, xs)
        return _finish(qb, l, acc)

    from repro.models import unrollctl
    if unrollctl.enabled():
        outs = [one_q_chunk(qi, unroll=True) for qi in range(nq)]
        return jnp.concatenate(outs, axis=1)
    if nq == 1:
        return one_q_chunk(0)
    outs = jax.lax.map(one_q_chunk, jnp.arange(nq))   # (nq, B, C, H, Dh)
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, Dh)


def attn_triangular(q, k, v, q_pos, kv_pos, *, window=0, scale=None,
                    chunk=2048):
    """FLOP-optimal causal attention: statically-unrolled lower-triangular
    block loop.  Q chunk i runs online-softmax over KV chunks 0..i only —
    upper-triangular blocks are never materialized, halving attention
    FLOPs vs. `attn_flash`.  Requires Sq == Skv (self-attention)."""
    scale = scale or q.shape[-1] ** -0.5
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    chunk = min(chunk, S)
    assert S % chunk == 0
    n = S // chunk
    G = H // KV
    outs = []
    for i in range(n):
        qb = q[:, i * chunk:(i + 1) * chunk]
        qp = q_pos[i * chunk:(i + 1) * chunk]
        carry = (jnp.full((B, KV, G, chunk), _NEG, jnp.float32),
                 jnp.zeros((B, KV, G, chunk), jnp.float32),
                 jnp.zeros((B, KV, G, chunk, Dh), jnp.float32))
        lo = 0
        if window:  # blocks entirely left of the window are all-masked
            lo = max(0, (i * chunk - window) // chunk)
        for j in range(lo, i + 1):
            kb = k[:, j * chunk:(j + 1) * chunk]
            vb = v[:, j * chunk:(j + 1) * chunk]
            kvp = kv_pos[j * chunk:(j + 1) * chunk]
            # off-diagonal in-window blocks need no mask at all
            need_mask = (j == i) or (window and (i * chunk - window
                                                 < (j + 1) * chunk))
            carry = _online_block(qb, kb, vb, qp, kvp, carry,
                                  causal=(j == i), window=window if need_mask
                                  else 0, scale=scale)
        outs.append(_finish(qb, carry[1], carry[2]))
    return jnp.concatenate(outs, axis=1)


def self_attention(cfg, q, k, v, q_pos, kv_pos, *, impl="flash"):
    window = cfg.swa_window
    if (impl == "full" or q.shape[1] <= cfg.attn_chunk
            or q.shape[1] % cfg.attn_chunk != 0):
        # small or chunk-indivisible sequences: dense-mask path
        return attn_full(q, k, v, q_pos, kv_pos, causal=True, window=window)
    if impl == "triangular":
        return attn_triangular(q, k, v, q_pos, kv_pos, window=window,
                               chunk=cfg.attn_chunk)
    return attn_flash(q, k, v, q_pos, kv_pos, causal=True, window=window,
                      q_chunk=cfg.attn_chunk, kv_chunk=cfg.attn_chunk)


# ---------------------------------------------------------------------------
# Decode paths
# ---------------------------------------------------------------------------


def init_kv_cache(cfg, batch: int, max_len: int, dtype):
    """(k, v) cache; SWA archs allocate only the window ring-buffer."""
    S = min(max_len, cfg.swa_window) if cfg.swa_window else max_len
    shape = (batch, S, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_cache_specs(cfg, batch: int, max_len: int, dtype):
    S = min(max_len, cfg.swa_window) if cfg.swa_window else max_len
    shape = (batch, S, cfg.n_kv_heads, cfg.head_dim)
    seq_ax = "kv_seq" if _seq_sharded(cfg) else None
    sp = ParamSpec(shape, ("batch", seq_ax, "kv_heads", None), "zeros", dtype)
    return {"k": sp, "v": sp}


def _seq_sharded(cfg) -> bool:
    return bool(cfg.decode_seq_shard) and not cfg.swa_window


def fill_kv_cache(cfg, cache, k, v, start: int = 0):
    """Write prefill k/v (B, S, KV, Dh) into the cache."""
    if cfg.swa_window:
        W = cache["k"].shape[1]
        S = k.shape[1]
        if S >= W:
            # last W positions; slot p % W. (S - W) % W == 0 when W | S.
            assert (S - W) % W == 0 or S == W
            return {"k": k[:, -W:], "v": v[:, -W:]}
        k_new = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, start, 1)
        v_new = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, start, 1)
        return {"k": k_new, "v": v_new}
    k_new = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, start, 1)
    v_new = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, start, 1)
    return {"k": k_new, "v": v_new}


def decode_attention(cfg, cache, q, new_k, new_v, pos, mesh=None):
    """One-token decode. q: (B,H,Dh), new_k/new_v: (B,KV,Dh), pos: scalar.

    Returns (attn_out (B,H,Dh), new_cache).  Dispatches to the
    sequence-sharded flash-decoding path when configured and a mesh with a
    model axis is active.
    """
    if (_seq_sharded(cfg) and mesh is not None
            and "model" in getattr(mesh, "axis_names", ())
            and cache["k"].shape[1] % mesh.shape["model"] == 0):
        return _decode_attn_seq_sharded(cfg, mesh, cache, q, new_k, new_v, pos)
    return _decode_attn_local(cfg, cache, q, new_k, new_v, pos)


def _write_slot(cfg, pos, S):
    if cfg.swa_window:
        return pos % cache_window(cfg, S)
    return pos


def cache_window(cfg, S):
    return min(S, cfg.swa_window) if cfg.swa_window else S


def _decode_attn_local(cfg, cache, q, new_k, new_v, pos):
    B, S, KV, Dh = cache["k"].shape
    slot = _write_slot(cfg, pos, S)
    kc = jax.lax.dynamic_update_slice(cache["k"], new_k[:, None],
                                      (0, slot, 0, 0))
    vc = jax.lax.dynamic_update_slice(cache["v"], new_v[:, None],
                                      (0, slot, 0, 0))
    slots = jnp.arange(S)
    if cfg.swa_window:
        # ring buffer: slot s holds global position pos - ((pos - s) mod S)
        kv_pos = pos - jnp.mod(pos - slots, S)
        valid = kv_pos >= 0
    else:
        kv_pos = slots
        valid = slots <= pos
    out = _decode_scores(cfg, q, kc, vc, valid)
    return out, {"k": kc, "v": vc}


def _decode_scores(cfg, q, kc, vc, valid):
    """q (B,H,Dh), kc/vc (B,S,KV,Dh), valid (S,) -> (B,H,Dh)."""
    B, S, KV, Dh = kc.shape
    H = q.shape[1]
    G = H // KV
    scale = Dh ** -0.5
    qg = q.reshape(B, KV, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, kc.astype(jnp.float32)) * scale
    s = jnp.where(valid[None, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, vc.astype(jnp.float32))
    return o.reshape(B, H, Dh).astype(q.dtype)


def _decode_attn_seq_sharded(cfg, mesh, cache, q, new_k, new_v, pos):
    """Flash-decoding: cache sharded over sequence on the model axis;
    every shard computes a partial softmax over its chunk; LSE-merged
    with psum.  Replaces head-sharding when kv_heads < model-axis size."""
    batch_axes = tuple(a for a in mesh.axis_names if a != "model")
    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0]
                                                    if batch_axes else None)
    B, S, KV, Dh = cache["k"].shape
    H = q.shape[1]
    G = H // KV
    scale = Dh ** -0.5

    def body(q, kc, vc, nk, nv, pos):
        midx = jax.lax.axis_index("model")
        S_loc = kc.shape[1]
        start = midx * S_loc
        owned = jnp.logical_and(pos >= start, pos < start + S_loc)
        li = jnp.clip(pos - start, 0, S_loc - 1)
        kc_u = jax.lax.dynamic_update_slice(kc, nk[:, None], (0, li, 0, 0))
        vc_u = jax.lax.dynamic_update_slice(vc, nv[:, None], (0, li, 0, 0))
        kc = jnp.where(owned, kc_u, kc)
        vc = jnp.where(owned, vc_u, vc)
        kv_pos = start + jnp.arange(S_loc)
        valid = kv_pos <= pos
        qg = q.reshape(-1, KV, G, Dh).astype(jnp.float32)
        s = jnp.einsum("bkgd,bskd->bkgs", qg,
                       kc.astype(jnp.float32)) * scale
        s = jnp.where(valid[None, None, None], s, _NEG)
        m_l = s.max(-1)
        pexp = jnp.exp(s - m_l[..., None])
        l_l = pexp.sum(-1)
        o_l = jnp.einsum("bkgs,bskd->bkgd", pexp, vc.astype(jnp.float32))
        m_g = jax.lax.pmax(m_l, "model")
        corr = jnp.exp(m_l - m_g)
        l_g = jax.lax.psum(l_l * corr, "model")
        o_g = jax.lax.psum(o_l * corr[..., None], "model")
        o = o_g / jnp.maximum(l_g, 1e-30)[..., None]
        return o.reshape(-1, H, Dh).astype(q.dtype), kc, vc

    out, kc, vc = shard_map(
        body, mesh,
        in_specs=(P(bspec, None, None),
                  P(bspec, "model", None, None), P(bspec, "model", None, None),
                  P(bspec, None, None), P(bspec, None, None), P()),
        out_specs=(P(bspec, None, None),
                   P(bspec, "model", None, None),
                   P(bspec, "model", None, None)),
    )(q, cache["k"], cache["v"], new_k, new_v, pos)
    return out, {"k": kc, "v": vc}


# ---------------------------------------------------------------------------
# Cross attention (enc-dec)
# ---------------------------------------------------------------------------


def cross_attention(cfg, q, enc_k, enc_v):
    """q: (B,Sq,H,Dh) vs. precomputed encoder k/v (B,F,KV,Dh). Non-causal."""
    Sq = q.shape[1]
    F = enc_k.shape[1]
    q_pos = jnp.arange(Sq)
    kv_pos = jnp.arange(F)
    return attn_full(q, enc_k, enc_v, q_pos, kv_pos, causal=False)
