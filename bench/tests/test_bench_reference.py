"""The weights made from the seed and the plain reference, against the
serving program at a small size on the CPU."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.reference import dense
from bench.session import model_config

SMALL = {"name": "small", "family": "dense", "n_layers": 2, "d_model": 64,
         "n_heads": 4, "n_kv_heads": 2, "d_ff": 96, "vocab_size": 300,
         "rope_theta": 10000.0, "norm_eps": 1e-5, "mlp_type": "swiglu",
         "tie_embeddings": False, "block_pattern": ["attn"],
         "param_dtype": "float32", "compute_dtype": "float32"}


def variants():
    yield "full", dict(SMALL, rope_style="full", qkv_bias=False)
    yield "half_bias", dict(SMALL, rope_style="half", qkv_bias=True,
                            rope_theta=500.0)


@pytest.mark.parametrize("name,m", list(variants()))
def test_program_params_equal_the_reference_draws(name, m):
    key = weights.root_key(3 * 2**31)
    p = weights.program_params(key, m)
    blk = p["blocks"]["scan"][0]
    for layer in range(m["n_layers"]):
        lw = weights.layer_weights(key, m, layer)
        for n, v in lw.items():
            g, leaf = n.split(".")
            np.testing.assert_array_equal(np.asarray(blk[g][leaf][layer]),
                                          np.asarray(v))
    ends = weights.end_weights(key, m)
    np.testing.assert_array_equal(np.asarray(p["unembed"]["table"]),
                                  np.asarray(ends["unembed.table"]))


def test_program_tree_matches_the_models_own():
    from repro.models.model import Model
    m = dict(SMALL, rope_style="half", qkv_bias=True)
    want = jax.eval_shape(Model(model_config(m)).init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: weights.program_params(
        weights.root_key(1), m))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), want) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), got)


@pytest.mark.parametrize("name,m", list(variants()))
def test_reference_agrees_with_the_program_forward(name, m):
    from repro.models.model import Model
    seed = 77
    model = Model(model_config(m))
    params = weights.program_params(weights.root_key(seed), m)
    tokens = np.random.default_rng(0).integers(0, 300, (2, 40)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(params, {"tokens": jnp.asarray(tokens)})
    pick = np.tile(np.arange(40, dtype=np.int32), (2, 1))
    got = dense.logits_at(seed, m, tokens, pick)
    np.testing.assert_allclose(got, np.asarray(want)[..., :300],
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("seed", [5, 2**31 + 11])
def test_lower_precision_departs_from_the_reference(seed):
    m = dict(SMALL, rope_style="full", qkv_bias=False, d_model=128,
             n_heads=4, d_ff=256, vocab_size=1000)
    tokens = np.random.default_rng(1).integers(0, 1000, (2, 64)).astype(
        np.int32)
    pick = np.tile(np.arange(64, dtype=np.int32), (2, 1))
    ref = dense.logits_at(seed, m, tokens, pick)
    low = dense.logits_at(seed, m, tokens, pick, numerics="fp8")
    err = np.abs(low - ref).max()
    assert 1e-3 < err < 1.0
    assert (low.argmax(-1) != ref.argmax(-1)).any()
