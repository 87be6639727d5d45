"""Transformer building blocks: norms, RoPE, GQA/SWA/cross attention, MLP.

Pure functions over explicit param pytrees (nested dicts of jax.Array).
Every matmul goes through core.numerics.DotEngine, so any registered
numerics mode — native, the truncated digit-plane matmul (tpmm), or the
fused online inner-product array (olm) — can be enabled per layer by
constructing the engine with that mode. Shapes use the convention
  x: (B, S, d_model)   q: (B, S, Hq, Dh)   kv: (B, S, Hkv, Dh)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.numerics import DotEngine
from repro.distributed.constraints import constrain, dp_axes, mesh_axes
from .config import ModelConfig

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = (2.0 / (d_in + d_out)) ** 0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------
# rotary embeddings (standard full and chatglm-style half/2d)
# --------------------------------------------------------------------------

def rope_angles(positions: jax.Array, dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions (..., S) -> cos/sin (..., S, dim/2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, positions: jax.Array, *, style: str, theta: float) -> jax.Array:
    """x (B, S, H, Dh). style 'full' rotates all dims; 'half' (chatglm 2d)
    rotates the first half of head dims and passes the rest through."""
    B, S, H, Dh = x.shape
    rot = Dh if style == "full" else Dh // 2
    cos, sin = rope_angles(positions, rot, theta)  # (B?, S, rot/2)
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    xr = x[..., :rot].astype(jnp.float32)
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(B, S, H, rot)
    if rot < Dh:
        out = jnp.concatenate([out, x[..., rot:].astype(jnp.float32)], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# paged KV cache plumbing (block pools + per-lane block tables)
# --------------------------------------------------------------------------
#
# A paged attention cache replaces the contiguous per-lane (B, T, H, D)
# ring with a per-layer block pool (num_blocks, block_size, H, D) plus a
# per-lane block table (B, max_blocks_per_lane) of pool indices, so KV
# memory scales with the pool size (live tokens) instead of B * max_len.
# Block id 0 is the permanently-reserved TRASH block: unowned table
# entries point at it, so padding rows and idle decode lanes write their
# garbage there instead of corrupting live lanes. View slot t of a lane
# holds absolute position t (block j covers positions [j*bs, (j+1)*bs)),
# exactly the contiguous layout, so causal masking makes the paged read
# bit-identical to the contiguous one. Each helper below is one indexed
# XLA op over the pool's leading axis (a gather to read, a scatter to
# write); ids outside [0, num_blocks) are first mapped to the trash block,
# so a corrupt table entry reads and writes garbage, never another lane.

TRASH_BLOCK = 0


def _owned(table, num_blocks):
    """`table` with every id outside [0, num_blocks) sent to TRASH_BLOCK."""
    return jnp.where((table >= 0) & (table < num_blocks), table, TRASH_BLOCK)


def paged_pool_write(pool, table, lane_pos, vals):
    """Write one decode step's k or v into the block pool.

    pool (NB, bs, H, D); table (B, MBL) int32; lane_pos (B,) absolute
    position each lane writes; vals (B, 1, H, D). Lanes whose table row
    is unowned (all TRASH_BLOCK) land in the trash block, and so does
    any out-of-range id (a corrupted table entry). A position past the
    table's last slot writes through that slot's id. Several lanes may
    write one trash slot: it is garbage by design and always masked.
    """
    NB, bs = pool.shape[0], pool.shape[1]
    B, MBL = table.shape
    blk = lane_pos // bs
    off = lane_pos - blk * bs
    bid = _owned(table, NB)[jnp.arange(B), jnp.clip(blk, 0, MBL - 1)]
    return pool.at[bid, off].set(vals[:, 0].astype(pool.dtype),
                                 mode="promise_in_bounds")


def paged_pool_view(pool, table):
    """Materialize each lane's owned blocks as a contiguous (B, T, H, D)
    view, T = MBL * block_size, by one gather of the pool's blocks
    through the block table (unowned slots read the trash block —
    garbage, but always causally masked because they sit past the lane's
    position; so do out-of-range ids, so a corrupt entry can never leak
    another lane's KV into this lane's scores)."""
    NB, bs, H, D = pool.shape
    B, MBL = table.shape
    blocks = jnp.take(pool, _owned(table, NB).reshape(-1), axis=0,
                      mode="clip")
    return blocks.reshape(B, MBL * bs, H, D)


def paged_scatter_rows(pool, rows, scatter_table):
    """Scatter contiguous prefill rows into the block pool.

    rows (Bp, S, H, D) from a fresh contiguous row cache; scatter_table
    (Bp, ceil(S/bs)) int32 block ids — entries past a row's owned blocks
    (and whole padding rows) point at TRASH_BLOCK, which absorbs them, as
    it does out-of-range ids.
    """
    NB, bs, H, D = pool.shape
    Bp, S = rows.shape[:2]
    pad = (-S) % bs
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad), (0, 0), (0, 0)))
    blocks = rows.reshape(-1, bs, H, D).astype(pool.dtype)
    return pool.at[_owned(scatter_table, NB).reshape(-1)].set(
        blocks, mode="promise_in_bounds")


# --------------------------------------------------------------------------
# attention (GQA, optional sliding window, optional cross)
# --------------------------------------------------------------------------

def attention_init(key, cfg: ModelConfig) -> Params:
    d, dt = cfg.d_model, cfg.pdtype
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, cfg.d_head_total, dt),
        "wk": dense_init(ks[1], d, cfg.d_kv_total, dt),
        "wv": dense_init(ks[2], d, cfg.d_kv_total, dt),
        "wo": dense_init(ks[3], cfg.d_head_total, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.d_head_total,), dt)
        p["bk"] = jnp.zeros((cfg.d_kv_total,), dt)
        p["bv"] = jnp.zeros((cfg.d_kv_total,), dt)
    return p


def _split_heads(x, n, dh):
    B, S, _ = x.shape
    return x.reshape(B, S, n, dh)


# Sequence sizes at/above this use the flash (online-softmax) path; below
# it the plain einsum path is cheaper to compile. Both are numerically
# equivalent (tested) so the threshold is purely a compile/memory choice.
FLASH_MIN_ELEMS = 512 * 1024


def _attn_plain(q, k, v, qpos, kpos, *, causal, window, t_sharded=False):
    """Flat-head core: q (B,S,H,D) attends over k/v (B,T,H,D) head by
    head (`_attn_core` brings grouped queries to this form); qpos (B,S),
    kpos (T,) or (B,T) absolute positions (kpos = -1 marks empty cache
    slots). t_sharded: pin scores to length-sharding (decode against a
    T-sharded cache: the softmax becomes the partial-softmax combine, the
    cache never gathers)."""
    D = q.shape[-1]
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32)
    scores = scores / (D ** 0.5)
    if t_sharded:
        scores = constrain(scores, dp_axes(), None, None, "model")
    kp = kpos if kpos.ndim == 2 else kpos[None]       # (B|1, T)
    valid = (kp >= 0)[:, None, None, :]
    if causal:
        rel = kp[:, None, :] <= qpos[:, :, None]      # (B, S, T)
        valid = jnp.logical_and(valid, rel[:, None])
        if window is not None:
            wn = kp[:, None, :] > qpos[:, :, None] - window
            valid = jnp.logical_and(valid, wn[:, None])
    scores = jnp.where(valid, scores, jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", w, v)


def _attn_flash(q, k, v, qpos, kpos, *, causal, window, chunk=1024):
    """Online-softmax attention, scanning key/value chunks: peak memory is
    O(S * chunk) per head instead of O(S * T). Same signature as plain."""
    B, S, H, D = q.shape
    T = k.shape[1]
    chunk = min(chunk, T)
    kp2 = kpos if kpos.ndim == 2 else kpos[None]
    pad = (-T) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kp2 = jnp.pad(kp2, ((0, 0), (0, pad)), constant_values=-1)
    nc = k.shape[1] // chunk
    kc = k.reshape(B, nc, chunk, H, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nc, chunk, H, D).transpose(1, 0, 2, 3, 4)
    pc = kp2.reshape(kp2.shape[0], nc, chunk).transpose(1, 0, 2)  # (nc,B|1,C)
    qf = q.astype(jnp.float32)
    scale = 1.0 / (D ** 0.5)

    # Pin batch->DP, heads->model through the scan. Without this the
    # replicated carry init poisons GSPMD propagation and the O(S*chunk)
    # score tensors replicate across the data axis (measured 16x traffic
    # blowup on yi-34b train). allow_uneven: 56 heads over 16 shards pads.
    dp = dp_axes()
    qf = constrain(qf, dp, None, "model", None, allow_uneven=True)
    kc = constrain(kc, None, dp, None, "model", None, allow_uneven=True)
    vc = constrain(vc, None, dp, None, "model", None, allow_uneven=True)

    # S x chunk tiles are materialized in the model compute dtype (bf16
    # halves the dominant flash traffic — the flash-attn norm); score
    # accumulation and m/l statistics stay f32. f32 inputs (tests/oracles)
    # keep f32 tiles for exactness vs the plain path.
    tile_dt = jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, pb = inp
        s = jax.lax.dot_general(
            qf.astype(tile_dt), kb.astype(tile_dt),
            (((3,), (3,)), ((0, 2), (0, 2))),
            preferred_element_type=jnp.float32)  # (B,H,S,chunk)
        s = s * scale
        s = constrain(s, dp, "model", None, None, allow_uneven=True)
        valid = (pb >= 0)[:, None, None, :]
        if causal:
            rel = pb[:, None, :] <= qpos[:, :, None]
            valid = jnp.logical_and(valid, rel[:, None])
            if window is not None:
                wn = pb[:, None, :] > qpos[:, :, None] - window
                valid = jnp.logical_and(valid, wn[:, None])
        s = jnp.where(valid, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p_ = jnp.exp(s - m_safe[..., None])
        p_ = jnp.where(valid, p_, 0.0).astype(tile_dt)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + p_.astype(jnp.float32).sum(axis=-1)
        pv = jax.lax.dot_general(
            p_, vb.astype(tile_dt),
            (((3,), (1,)), ((0, 1), (0, 2))),
            preferred_element_type=jnp.float32)  # (B,H,S,D)
        acc_new = acc * corr[..., None] + pv
        acc_new = constrain(acc_new, dp, "model", None, None,
                            allow_uneven=True)
        return (m_new, l_new, acc_new), None

    m0 = constrain(jnp.full((B, H, S), -jnp.inf, jnp.float32),
                   dp, "model", None, allow_uneven=True)
    l0 = constrain(jnp.zeros((B, H, S), jnp.float32),
                   dp, "model", None, allow_uneven=True)
    a0 = constrain(jnp.zeros((B, H, S, D), jnp.float32),
                   dp, "model", None, None, allow_uneven=True)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(v.dtype)  # (B,S,H,D)


def grouped_gqa(n_heads: int, n_kv_heads: int, model_size: int) -> bool:
    """Whether `_attn_core` contracts each KV head's group of query heads
    against the un-expanded K/V (True) or copies K/V out to the query
    heads first (False). The grouped path keeps the `model` mesh axis on
    the KV-head axis, so it needs that axis to be 1 or to divide
    n_kv_heads; otherwise a (kv, G) reshape cannot be sharded (e.g. 8 KV
    heads over a 16-way axis) and GSPMD would replicate every attention
    tensor, while the repeat keeps the single head axis sharded. MHA has
    nothing to group."""
    return n_heads != n_kv_heads and (
        model_size == 1 or n_kv_heads % model_size == 0)


def _attn_core(q, k, v, qpos, kpos, *, causal, window, t_sharded=False):
    """GQA attention: q (B,S,Hq,D) over k/v (B,T,Hkv,D), query head
    h = kv * G + g reading KV head kv, G = Hq // Hkv.

    Where `grouped_gqa` allows, the G query heads of each KV head are
    folded into its query rows, q -> (B, G*S, Hkv, D) with qpos tiled G
    times, and the flat-head core runs over the Hkv un-expanded heads:
    its dots are `bskgd,btkd->bkgst` and `bkgst,btkd->bskgd` with (g, s)
    as rows, and K/V are never copied. Otherwise (MHA, or a `model` axis
    that does not divide Hkv, including the length-sharded `t_sharded`
    decode) K/V are repeated out to the Hq heads. Name scopes: `attn_core`
    holds the whole core, `gqa_grouped` the grouped path inside it, and
    `kv_repeat` the fallback's copy, so a profile says which path each
    program took."""
    with jax.named_scope("attn_core"):
        B, S, Hq, D = q.shape
        Hkv, T = k.shape[2], k.shape[1]
        G = Hq // Hkv
        if S * T >= FLASH_MIN_ELEMS:
            core = _attn_flash
        else:
            core = functools.partial(_attn_plain, t_sharded=t_sharded)
        if grouped_gqa(Hq, Hkv, mesh_axes().get("model", 1)):
            with jax.named_scope("gqa_grouped"):
                qg = q.reshape(B, S, Hkv, G, D).transpose(0, 3, 1, 2, 4)
                qg = constrain(qg.reshape(B, G * S, Hkv, D),
                               dp_axes(), None, "model", None)
                out = core(qg, k, v, jnp.tile(qpos, (1, G)), kpos,
                           causal=causal, window=window)
                out = out.reshape(B, G, S, Hkv, D).transpose(0, 2, 3, 1, 4)
                return out.reshape(B, S, Hq, D)
        if G > 1:
            with jax.named_scope("kv_repeat"):
                k = jnp.repeat(k, G, axis=2)
                v = jnp.repeat(v, G, axis=2)
        return core(q, k, v, qpos, kpos, causal=causal, window=window)


def attention_apply(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,                 # (B, S, d)
    positions: jax.Array,         # (B, S) absolute positions
    eng: DotEngine,
    *,
    kv_cache: Optional[Dict[str, jax.Array]] = None,  # {"k","v" (B,T,Hkv,D), "len" ()}
    memory: Optional[jax.Array] = None,               # cross-attn memory (B,M,d)
    causal: bool = True,
    chunked: bool = False,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Self- or cross-attention with optional KV cache (decode) and SWA.

    The cache dict selects the layout: {"k","v","len"} is the contiguous
    per-lane layout; {"kpool","vpool","table","len"} is the paged layout
    (see the block-pool helpers above). `chunked=True` treats an S>1 call
    like a decode step that writes S entries at each lane's position and
    attends over the whole cache (chunked prefill); the default S>1 path
    is fresh whole-prompt prefill.

    Returns (output (B,S,d), updated kv_cache or None).
    """
    B, S, d = x.shape
    Dh = cfg.head_dim
    q = eng.dot(x, p["wq"])
    src = memory if memory is not None else x
    k = eng.dot(src, p["wk"])
    v = eng.dot(src, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = _split_heads(q, cfg.n_heads, Dh)
    k = _split_heads(k, cfg.n_kv_heads, Dh)
    v = _split_heads(v, cfg.n_kv_heads, Dh)
    if memory is None:  # RoPE only for self-attention
        q = apply_rope(q, positions, style=cfg.rope_style, theta=cfg.rope_theta)
        k = apply_rope(k, positions, style=cfg.rope_style, theta=cfg.rope_theta)

    window = cfg.sliding_window if memory is None else None
    new_cache = None
    if kv_cache is not None and memory is None and "kpool" in kv_cache:
        # paged decode: write this step through the block table, then
        # attend over the contiguous view gathered from owned blocks.
        if S != 1:
            raise ValueError(
                "paged KV cache supports decode steps only (S == 1); "
                "prefill goes through a contiguous row cache that the "
                "serving engine scatters into the pool")
        msize = mesh_axes().get("model", 1)
        t_sharded = msize > 1 and cfg.n_kv_heads % msize != 0
        table = kv_cache["table"]
        lane_pos = positions[:, 0]
        kpool = paged_pool_write(kv_cache["kpool"], table, lane_pos, k)
        vpool = paged_pool_write(kv_cache["vpool"], table, lane_pos, v)
        new_cache = {"kpool": kpool, "vpool": vpool, "table": table,
                     "len": jnp.maximum(kv_cache["len"], lane_pos.max() + 1)}
        with jax.named_scope("paged_view"):
            ck = paged_pool_view(kpool, table)
            cv = paged_pool_view(vpool, table)
        # view slot index == absolute position, exactly the contiguous
        # layout; unowned slots hold trash but sit past lane_pos, so the
        # causal mask zeroes them (exp underflows to exact 0.0) and the
        # softmax is bit-identical to the contiguous path.
        kpos = jnp.arange(ck.shape[1])
        out = _attn_core(q, ck, cv, positions, kpos,
                         causal=causal, window=window, t_sharded=t_sharded)
        out = eng.dot(out.reshape(B, S, cfg.d_head_total), p["wo"])
        return out, new_cache
    if kv_cache is not None and memory is None:
        T = kv_cache["k"].shape[1]
        cur = kv_cache["len"]
        ring = window is not None and T == window
        if S == 1 or chunked:
            # decode / chunked prefill: per-lane write of S entries at each
            # lane's own position (lanes in a serving pool are at
            # heterogeneous depths), then attend over the whole cache
            msize = mesh_axes().get("model", 1)
            # cache is LENGTH-sharded when kv heads don't divide the model
            # axis; attention must then compute T-sharded (partial-softmax
            # combine) instead of gathering the full cache per layer
            # (measured: 172 GB/step on qwen1.5-110b decode_32k).
            t_sharded = msize > 1 and cfg.n_kv_heads % msize != 0
            lane_pos = positions[:, 0]
            if ring:
                if S != 1:
                    raise ValueError(
                        "chunked prefill does not support sliding-window "
                        "ring caches; disable prefill chunking for SWA "
                        "models")
                idx_b = jnp.mod(lane_pos, T)
            else:
                idx_b = jnp.minimum(lane_pos, T - S)
            # zero indices take i's dtype: mixing traced int32 lane
            # indices with bare Python 0s type-errors under x64
            _upd = lambda c, kk, i: jax.lax.dynamic_update_slice(
                c, kk, (i,) + (jnp.zeros((), i.dtype),) * 2)
            ck = jax.vmap(_upd)(kv_cache["k"],
                                k.astype(kv_cache["k"].dtype), idx_b)
            cv = jax.vmap(_upd)(kv_cache["v"],
                                v.astype(kv_cache["v"].dtype), idx_b)
            new_cache = {"k": ck, "v": cv, "len": jnp.maximum(cur, lane_pos.max() + S)}
            slots = jnp.arange(T)
            if ring:  # per-lane slot->absolute-position map
                newest = lane_pos[:, None]
                kpos = newest - jnp.mod(newest - slots[None], T)
                kpos = jnp.where(kpos >= 0, kpos, -1)
            else:
                kpos = slots  # slot index == absolute position
            out = _attn_core(q, ck, cv, positions, kpos,
                             causal=causal, window=window,
                             t_sharded=t_sharded)
            out = eng.dot(out.reshape(B, S, cfg.d_head_total), p["wo"])
            return out, new_cache
        # prefill: fill the cache so slot s holds position p with
        # s == p mod T (ring) or s == p (full), then attend over the full
        # fresh sequence; the cache is only for later decode steps.
        if S > T:  # SWA prompt longer than the ring: keep last T, aligned
            kw, vw = k[:, -T:], v[:, -T:]
            shift = (S - T) % T
            kw = jnp.roll(kw, shift, axis=1)
            vw = jnp.roll(vw, shift, axis=1)
        else:
            kw, vw = k, v
        # all-Python-int indices: a mixed (0, jnp.int32-zero, 0, 0) tuple
        # type-errors under x64, where bare 0 canonicalizes to int64
        ck = jax.lax.dynamic_update_slice(
            kv_cache["k"], kw.astype(kv_cache["k"].dtype), (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            kv_cache["v"], vw.astype(kv_cache["v"].dtype), (0, 0, 0, 0))
        new_cache = {"k": ck, "v": cv, "len": cur + S}

    if memory is not None:
        kpos = jnp.arange(k.shape[1])
        out = _attn_core(q, k, v, positions, kpos, causal=False, window=None)
    else:
        kpos = jnp.arange(k.shape[1])
        out = _attn_core(q, k, v, positions, kpos, causal=causal,
                         window=window)
    out = eng.dot(out.reshape(B, S, cfg.d_head_total), p["wo"])
    return out, new_cache


def _cache_positions(cur, T, S, window):
    """Absolute position held in each cache slot (-1 = empty), for a cache
    that was just updated with S entries ending at position cur + S - 1."""
    slots = jnp.arange(T)
    if window is not None and T == window:
        newest = cur + S - 1
        pos = newest - jnp.mod(newest - slots, T)
        return jnp.where(pos >= 0, pos, -1)
    return jnp.where(slots < cur + S, slots, -1)


# --------------------------------------------------------------------------
# MLP / MoE-free feed-forward
# --------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d, dt = cfg.d_model, cfg.pdtype
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_type == "swiglu":
        return {
            "wg": dense_init(ks[0], d, f, dt),
            "wu": dense_init(ks[1], d, f, dt),
            "wd": dense_init(ks[2], f, d, dt),
        }
    return {
        "wu": dense_init(ks[0], d, f, dt),
        "wd": dense_init(ks[1], f, d, dt),
    }


def mlp_apply(p: Params, cfg: ModelConfig, x: jax.Array, eng: DotEngine) -> jax.Array:
    if cfg.mlp_type == "swiglu":
        g = jax.nn.silu(eng.dot(x, p["wg"]).astype(jnp.float32)).astype(x.dtype)
        u = eng.dot(x, p["wu"])
        return eng.dot(g * u, p["wd"])
    h = jax.nn.gelu(eng.dot(x, p["wu"]).astype(jnp.float32)).astype(x.dtype)
    return eng.dot(h, p["wd"])


# --------------------------------------------------------------------------
# embeddings / head
# --------------------------------------------------------------------------

def embedding_init(key, cfg: ModelConfig) -> Params:
    e = jax.random.normal(key, (cfg.vocab_padded, cfg.d_model), jnp.float32) * 0.02
    return {"table": e.astype(cfg.pdtype)}


def embed(p: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    return p["table"].astype(cfg.cdtype)[tokens]


def unembed(p: Params, x: jax.Array, cfg: ModelConfig, eng: DotEngine) -> jax.Array:
    logits = eng.dot(x, p["table"].astype(cfg.cdtype).T)
    if cfg.vocab_padded != cfg.vocab_size:
        mask = (jnp.arange(cfg.vocab_padded) >= cfg.vocab_size) * jnp.asarray(
            -1e9, logits.dtype)
        logits = logits + mask
    return logits
