"""Block dispatch + pattern-scanned stacks for every assigned family.

A model is a sequence of blocks tiled from cfg.block_pattern:
  attn  — pre-norm self-attention (GQA/SWA/RoPE) + MLP or MoE
  rec   — pre-norm RG-LRU recurrent mixer + MLP            (recurrentgemma)
  ssm   — Mamba2 SSD block (no separate MLP)               (mamba2)
  cross — pre-norm cross-attention to frontend memory + MLP (llama-vision)
  xdec  — self-attn + cross-attn + MLP                      (seamless decoder)

Whole pattern groups are scanned (jax.lax.scan over stacked params) so
compile time and HLO size are O(len(pattern)) instead of O(n_layers);
remainder layers are materialized individually. Activation checkpointing
wraps the group body (cfg.remat).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.numerics import DotEngine
from .config import ModelConfig
from .layers import (attention_apply, attention_init, mlp_apply, mlp_init,
                     rmsnorm, rmsnorm_init)
from .moe import moe_apply, moe_init
from .recurrent import (rglru_apply, rglru_init, rglru_state_init, ssd_apply,
                        ssd_init, ssd_state_init)

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# single block
# --------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig, kind: str) -> Params:
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    p: Params = {"norm1": rmsnorm_init(d, cfg.pdtype)}
    if kind == "attn":
        p["attn"] = attention_init(ks[0], cfg)
    elif kind == "rec":
        p["rec"] = rglru_init(ks[0], cfg)
    elif kind == "ssm":
        p["ssm"] = ssd_init(ks[0], cfg)
        return p  # SSD block has no separate MLP
    elif kind == "cross":
        p["cross"] = attention_init(ks[0], cfg)
    elif kind == "xdec":
        p["attn"] = attention_init(ks[0], cfg)
        p["norm_x"] = rmsnorm_init(d, cfg.pdtype)
        p["cross"] = attention_init(ks[1], cfg)
    else:
        raise ValueError(f"unknown block kind {kind}")
    p["norm2"] = rmsnorm_init(d, cfg.pdtype)
    if cfg.n_experts and kind == "attn":
        p["moe"] = moe_init(ks[2], cfg)
    else:
        p["mlp"] = mlp_init(ks[2], cfg)
    return p


def block_cache_init(cfg: ModelConfig, kind: str, batch: int,
                     max_len: int,
                     paged: Optional[Dict[str, int]] = None
                     ) -> Optional[Params]:
    """Per-block decode cache. `paged={"num_blocks": NB, "block_size": bs}`
    switches full-attention KV caches to the block-pool layout (pool +
    per-lane block table; block 0 is the shared trash block, see
    layers.paged_pool_write). Sliding-window layers keep the contiguous
    ring — their residency is already bounded by the window — as do
    recurrent/SSM states (O(1) per lane)."""
    if kind in ("attn", "xdec"):
        T = max_len
        if cfg.sliding_window is not None:
            T = min(T, cfg.sliding_window)
        if paged is not None and cfg.sliding_window is None:
            nb, bs = paged["num_blocks"], paged["block_size"]
            mbl = -(-max_len // bs)
            return {
                "kpool": jnp.zeros((nb, bs, cfg.n_kv_heads, cfg.head_dim),
                                   cfg.cdtype),
                "vpool": jnp.zeros((nb, bs, cfg.n_kv_heads, cfg.head_dim),
                                   cfg.cdtype),
                "table": jnp.zeros((batch, mbl), jnp.int32),
                "len": jnp.zeros((), jnp.int32),
            }
        return {
            "k": jnp.zeros((batch, T, cfg.n_kv_heads, cfg.head_dim), cfg.cdtype),
            "v": jnp.zeros((batch, T, cfg.n_kv_heads, cfg.head_dim), cfg.cdtype),
            "len": jnp.zeros((), jnp.int32),
        }
    if kind == "rec":
        return rglru_state_init(cfg, batch)
    if kind == "ssm":
        return ssd_state_init(cfg, batch)
    if kind == "cross":
        return None
    raise ValueError(kind)


def block_apply(
    p: Params,
    cfg: ModelConfig,
    kind: str,
    x: jax.Array,
    positions: jax.Array,
    eng: DotEngine,
    *,
    cache: Optional[Params] = None,
    memory: Optional[jax.Array] = None,
    causal: bool = True,
    chunked: bool = False,
) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """Returns (x, new_cache, aux_loss).

    Per-layer precision assignment resolves here: attention-family GEMMs
    run under eng.for_role("attn") and the MLP/MoE under
    eng.for_role("mlp"), so a DotEngine with layer_modes (e.g. MLPs on a
    truncated olm{n}t{p} tier, attention at full width) splits precision
    per role with no other plumbing. Recurrent/SSM mixers keep the base
    engine — their GEMMs are gate projections, not attention. The two
    roles are also the `attn` and `mlp` name scopes of the compiled
    program, which a profiler trace attributes device time by."""
    aux = jnp.zeros((), jnp.float32)
    attn_eng = eng.for_role("attn")
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    new_cache = cache
    if kind == "attn":
        with jax.named_scope("attn"):
            o, new_cache = attention_apply(p["attn"], cfg, h, positions,
                                           attn_eng, kv_cache=cache,
                                           causal=causal, chunked=chunked)
    elif kind == "rec":
        o, new_cache = rglru_apply(p["rec"], cfg, h, eng, state=cache)
    elif kind == "ssm":
        o, new_cache = ssd_apply(p["ssm"], cfg, h, eng, state=cache)
        return x + o, new_cache, aux
    elif kind == "cross":
        with jax.named_scope("attn"):
            o, _ = attention_apply(p["cross"], cfg, h, positions, attn_eng,
                                   memory=memory)
    elif kind == "xdec":
        with jax.named_scope("attn"):
            o, new_cache = attention_apply(p["attn"], cfg, h, positions,
                                           attn_eng, kv_cache=cache,
                                           causal=causal, chunked=chunked)
        x = x + o
        hx = rmsnorm(p["norm_x"], x, cfg.norm_eps)
        with jax.named_scope("attn"):
            o, _ = attention_apply(p["cross"], cfg, hx, positions, attn_eng,
                                   memory=memory)
    else:
        raise ValueError(kind)
    x = x + o
    h2 = rmsnorm(p["norm2"], x, cfg.norm_eps)
    mlp_eng = eng.for_role("mlp")
    with jax.named_scope("mlp"):
        if "moe" in p:
            m, aux = moe_apply(p["moe"], cfg, h2, mlp_eng)
        else:
            m = mlp_apply(p["mlp"], cfg, h2, mlp_eng)
    return x + m, new_cache, aux


# --------------------------------------------------------------------------
# pattern-scanned stack
# --------------------------------------------------------------------------

def stack_init(key, cfg: ModelConfig, pattern: Tuple[str, ...],
               n_groups: int, remainder: Tuple[str, ...]) -> Params:
    """Params: {"scan": tuple_per_slot(stacked over groups), "rem": [...]}"""
    keys = jax.random.split(key, n_groups * len(pattern) + len(remainder))
    scan_params = []
    for s, kind in enumerate(pattern):
        per_group = [block_init(keys[g * len(pattern) + s], cfg, kind)
                     for g in range(n_groups)]
        scan_params.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_group)
                           if n_groups > 1 else
                           jax.tree.map(lambda v: v[None], per_group[0]))
    rem_params = [block_init(keys[n_groups * len(pattern) + i], cfg, kind)
                  for i, kind in enumerate(remainder)]
    return {"scan": tuple(scan_params), "rem": rem_params}


def stack_cache_init(cfg: ModelConfig, pattern, n_groups, remainder,
                     batch: int, max_len: int,
                     paged: Optional[Dict[str, int]] = None) -> Params:
    scan_caches = []
    for kind in pattern:
        c = block_cache_init(cfg, kind, batch, max_len, paged=paged)
        scan_caches.append(
            jax.tree.map(lambda v: jnp.broadcast_to(v[None], (n_groups,) + v.shape), c)
            if c is not None else None)
    rem = [block_cache_init(cfg, kind, batch, max_len, paged=paged)
           for kind in remainder]
    return {"scan": tuple(scan_caches), "rem": rem}


def stack_apply(
    params: Params,
    cfg: ModelConfig,
    pattern: Tuple[str, ...],
    x: jax.Array,
    positions: jax.Array,
    eng: DotEngine,
    *,
    caches: Optional[Params] = None,
    memory: Optional[jax.Array] = None,
    causal: bool = True,
    chunked: bool = False,
) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """Run the scanned groups then the remainder blocks."""

    def group_body(carry, slice_in):
        xg, aux_acc = carry
        gp, gc = slice_in
        new_caches = []
        for s, kind in enumerate(pattern):
            xg, nc, aux = block_apply(
                gp[s], cfg, kind, xg, positions, eng,
                cache=None if gc is None else gc[s],
                memory=memory, causal=causal, chunked=chunked)
            new_caches.append(nc)
        return (xg, aux_acc + aux), tuple(new_caches)

    # Remat only on the training path: under serving (caches present)
    # there is no backward pass, and the checkpoint barrier blocks GSPMD
    # propagation through the cache update (measured: a full-length f32
    # KV regather per layer on decode_32k).
    if cfg.remat == "block" and caches is None:
        group_body = jax.checkpoint(group_body)

    scan_caches = caches["scan"] if caches is not None else None
    if scan_caches is None:
        scan_caches_in = tuple(None for _ in pattern)
        (x, aux), _ = jax.lax.scan(
            lambda c, gp: group_body((c[0], c[1]), (gp, scan_caches_in)),
            (x, jnp.zeros((), jnp.float32)), params["scan"])
        new_scan_caches = None
    else:
        (x, aux), new_scan_caches = jax.lax.scan(
            lambda c, inp: group_body(c, inp),
            (x, jnp.zeros((), jnp.float32)),
            (params["scan"], scan_caches))

    new_rem = []
    rem_kinds = cfg.remainder_blocks
    for i, kind in enumerate(rem_kinds):
        c = None if caches is None else caches["rem"][i]
        x, nc, a = block_apply(params["rem"][i], cfg, kind, x, positions,
                               eng, cache=c, memory=memory, causal=causal,
                               chunked=chunked)
        new_rem.append(nc)
        aux = aux + a
    new_caches = None
    if caches is not None:
        new_caches = {"scan": new_scan_caches, "rem": new_rem}
    return x, new_caches, aux
