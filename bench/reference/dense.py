"""Plain reference of a dense decoder-only transformer, in float32.

Pre-norm blocks: RMSNorm, grouped-query attention with rotary positions
(on all head dims, or on the first half as ChatGLM's 2d-RoPE), optional
QKV bias, SwiGLU MLP; a final RMSNorm and an untied output table. It
follows the published descriptions of InternLM2 (arXiv:2403.17297) and
ChatGLM3 (arXiv:2406.12793), with one departure: rotary positions rotate
adjacent pairs of dims (2i, 2i+1), where InternLM2's published code pairs
(i, i + D/2). With seeded random weights the two differ only by a fixed
permutation of the q and k projection columns.

It imports nothing of the serving program, draws its weights itself from
the seed (`bench.weights`), and runs one layer at a time, one sequence
at a time inside a layer, at `precision="highest"`, so that it fits
beside nothing else on one chip.

`numerics="fp8"` is the control: every matmul of the model takes its
operands rounded to float8_e4m3fn (weights scaled per output column,
activations per token) and the residual stream and activations are
rounded to bfloat16 between operations, as a serving path in bfloat16
with fp8 GEMMs would compute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST


def _f8(x, axis):
    """float8_e4m3fn rounding along `axis`, scaled so the largest
    magnitude maps to the format's largest (values kept as float32)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, numerics):
    if numerics == "fp8":
        x, w = _f8(x, -1), _f8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _act(x, numerics):
    if numerics == "fp8":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, rot, theta):
    """x (S, H, D): rotate pairs (2i, 2i+1) of the first `rot` dims."""
    S, H, D = x.shape
    inv = 1.0 / (theta ** (np.arange(0, rot, 2, dtype=np.float32) / rot))
    ang = pos.astype(jnp.float32)[:, None] * inv[None]       # (S, rot/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.reshape(S, H, rot), x[..., rot:]], -1)


def _block_one(x, w, m, numerics):
    """One block on one sequence x (S, d)."""
    S, d = x.shape
    H, Hkv = m["n_heads"], m["n_kv_heads"]
    D = d // H
    eps = m["norm_eps"]
    rot = D if m.get("rope_style", "full") == "full" else D // 2
    h = _act(_rmsnorm(x, w["norm1.scale"], eps), numerics)
    q = _mm(h, w["attn.wq"], numerics)
    k = _mm(h, w["attn.wk"], numerics)
    v = _mm(h, w["attn.wv"], numerics)
    if m.get("qkv_bias"):
        q, k, v = q + w["attn.bq"], k + w["attn.bk"], v + w["attn.bv"]
    pos = jnp.arange(S)
    q = _rope(_act(q, numerics).reshape(S, H, D), pos, rot, m["rope_theta"])
    k = _rope(_act(k, numerics).reshape(S, Hkv, D), pos, rot,
              m["rope_theta"])
    v = _act(v, numerics).reshape(S, Hkv, D)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("shd,thd->hst", q, k, precision=HIGHEST) / D ** 0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    a = jnp.einsum("hst,thd->shd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    a = _act(a.reshape(S, H * D), numerics)
    x = _act(x + _mm(a, w["attn.wo"], numerics), numerics)
    h = _act(_rmsnorm(x, w["norm2.scale"], eps), numerics)
    g = _act(jax.nn.silu(_mm(h, w["mlp.wg"], numerics)), numerics)
    u = _act(_mm(h, w["mlp.wu"], numerics), numerics)
    return _act(x + _mm(_act(g * u, numerics), w["mlp.wd"], numerics),
                numerics)


@functools.partial(jax.jit, static_argnames=("m_items", "numerics"))
def _block(x, w, m_items, numerics):
    m = dict(m_items)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    return jax.lax.map(lambda xs: _block_one(xs, w, m, numerics), x)


@functools.partial(jax.jit, static_argnames=("m_items", "numerics"))
def _head(x, ends, pick, m_items, numerics):
    """Logits at the rows `pick` (B, n) of each sequence, over the real
    vocabulary."""
    m = dict(m_items)
    xs = jnp.take_along_axis(x, pick[..., None], axis=1)       # (B, n, d)
    xs = _act(_rmsnorm(xs, ends["final_norm.scale"].astype(jnp.float32),
                       m["norm_eps"]), numerics)
    table = ends["unembed.table"][:m["vocab_size"]].astype(jnp.float32)
    return _mm(xs, table.T, numerics)


def _hashable(m: dict) -> tuple:
    keep = ("d_model", "n_heads", "n_kv_heads", "norm_eps", "rope_style",
            "rope_theta", "qkv_bias", "vocab_size")
    return tuple((k, m[k]) for k in keep if k in m)


def logits_at(seed: int, m: dict, tokens: np.ndarray, pick: np.ndarray,
              numerics: str = "f32") -> np.ndarray:
    """tokens (B, S) int32, right-padded; pick (B, n) positions whose
    next-token logits are wanted. Returns float32 (B, n, vocab_size).
    Padding after a sequence's last picked position cannot reach it:
    attention is causal."""
    key = W.root_key(seed)
    mi = _hashable(m)
    with jax.default_matmul_precision("highest"):
        ends = W.end_weights(key, m)
        x = ends["embed.table"].astype(jnp.float32)[jnp.asarray(tokens)]
        x = _act(x, numerics)
        for layer in range(m["n_layers"]):
            x = _block(x, W.layer_weights(key, m, layer), mi, numerics)
        out = _head(x, ends, jnp.asarray(pick), mi, numerics)
    return np.asarray(out)
