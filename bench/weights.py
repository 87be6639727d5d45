"""Weights made from the seed, on the device, in one jitted call.

Every tensor is drawn by `draw(key, name, layer, shape, dtype)`, keyed by
its semantic name and layer index. The serving program's parameter tree
is assembled from those draws (`program_params`), and the plain
reference draws the same tensors one layer at a time (`layer_weights`),
so both see bit-identical values without the reference touching anything
the program made.
"""
from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

def root_key(seed: int) -> jax.Array:
    """A threefry key from any non-negative integer seed (more than 32
    bits allowed): the seed is hashed to two 32-bit words."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _name_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


# A draw is the sum of four uniform 16-bit integers (near-normal, standard
# deviation SUM_STD) times a power of two: integer arithmetic and an exact
# scaling, so the values do not depend on how a compiler fuses or
# reorders the arithmetic, and the program (inside one jitted call) and
# the reference (a layer at a time) get the same bits.
SUM_STD = 65536 / 12 ** 0.5 * 2


def _pow2_near(x: float) -> float:
    return 2.0 ** round(math.log2(x))


@functools.partial(jax.jit, static_argnames=("name", "shape", "dtype"))
def draw(key, name: str, layer, shape, dtype) -> jax.Array:
    """One tensor. Dense weights: standard deviation near
    sqrt(2 / (d_in + d_out)), as the model's own init scales them;
    biases near 0.1; norm scales 1 plus near 0.1; embedding and output
    tables near 0.02 (each rounded to a power of two)."""
    k = jax.random.fold_in(jax.random.fold_in(key, _name_id(name)), layer)
    u = jax.random.bits(k, (4,) + shape, jnp.uint16)
    z = (u.astype(jnp.int32) - 32768).sum(axis=0).astype(jnp.float32)
    leaf = name.split(".")[-1]
    if leaf.startswith("w"):
        std = (2.0 / (shape[-2] + shape[-1])) ** 0.5
    elif leaf.startswith("b") or leaf == "scale":
        std = 0.1
    else:
        std = 0.02
    z = z * _pow2_near(std / SUM_STD)
    if leaf == "scale":
        z = 1.0 + z
    return z.astype(dtype)


def vocab_rows(vocab_size: int) -> int:
    """Rows of the embedding and output tables as the program holds them:
    the vocabulary rounded up to a multiple of 256."""
    return -(-vocab_size // 256) * 256


def block_shapes(m: dict) -> dict:
    """Shape of each tensor of one dense block, from the config's sizes."""
    d, hd = m["d_model"], m["d_model"] // m["n_heads"]
    hq, hkv, f = m["n_heads"] * hd, m["n_kv_heads"] * hd, m["d_ff"]
    shapes = {"attn.wq": (d, hq), "attn.wk": (d, hkv), "attn.wv": (d, hkv),
              "attn.wo": (hq, d), "mlp.wg": (d, f), "mlp.wu": (d, f),
              "mlp.wd": (f, d), "norm1.scale": (d,), "norm2.scale": (d,)}
    if m.get("qkv_bias"):
        shapes.update({"attn.bq": (hq,), "attn.bk": (hkv,),
                       "attn.bv": (hkv,)})
    return shapes


def layer_weights(key, m: dict, layer: int) -> dict:
    """One block's tensors in their stored dtype (for the reference)."""
    dt = jnp.dtype(m["param_dtype"])
    return {n: draw(key, n, layer, tuple(s), dt)
            for n, s in block_shapes(m).items()}


def end_weights(key, m: dict) -> dict:
    """Embedding, final norm and output table in their stored dtype."""
    dt = jnp.dtype(m["param_dtype"])
    rows = vocab_rows(m["vocab_size"])
    return {"embed.table": draw(key, "embed.table", 0,
                                (rows, m["d_model"]), dt),
            "unembed.table": draw(key, "unembed.table", 0,
                                  (rows, m["d_model"]), dt),
            "final_norm.scale": draw(key, "final_norm.scale", 0,
                                     (m["d_model"],), dt)}


def _stacked(key, name, n_layers, shape, dtype):
    # lax.map, not vmap: one layer's float32 draw is live at a time, so
    # a 6 GB bf16 stack never passes through a 12 GB float32 one.
    return jax.lax.map(lambda l: draw(key, name, l, shape, dtype),
                       jnp.arange(n_layers, dtype=jnp.uint32))


def program_params(key, m: dict) -> dict:
    """The serving program's parameter tree for a dense attention-only
    model (`models/model.py` layout: one scanned block slot stacked over
    the layers, no remainder blocks), built on the device in one jitted
    call from `key`."""
    if tuple(m.get("block_pattern", ("attn",))) != ("attn",):
        raise ValueError("program_params builds dense attention-only models")

    def build(key):
        dt = jnp.dtype(m["param_dtype"])
        L = m["n_layers"]
        block: dict = {}
        for name, shape in block_shapes(m).items():
            group, leaf = name.split(".")
            block.setdefault(group, {})[leaf] = _stacked(key, name, L,
                                                         shape, dt)
        ends = end_weights(key, m)
        return {"embed": {"table": ends["embed.table"]},
                "blocks": {"scan": (block,), "rem": []},
                "final_norm": {"scale": ends["final_norm.scale"]},
                "unembed": {"table": ends["unembed.table"]}}

    return jax.jit(build)(key)
