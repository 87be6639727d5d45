"""ChatGLM3's attention at a test size: 32 query heads over 2 KV heads
(16 query heads share each KV head), RoPE on half of each head's dims,
QKV bias. The plain reference against the program's forward, and a
small closed long-document cell through the harness, whose prompts
cross several prefill chunks and whose decode reads the paged pool.

At this size (2 layers, d_model 512, head dim 16, bfloat16 weights and
activations), with samples of 8 requests, sound runs read gap_max 0 to
0.0175 and the fp8 control 0.080 to 0.171 on four seeds and windows of
0.2 to 2 s, so the limit is 0.035. (Samples of 4 let the control read
as low as 0.0094 when a loaded machine shortens the window.)"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run, spec, weights
from bench.reference import dense
from bench.session import model_config

GLM = {"name": "small-chatglm3", "family": "dense", "n_layers": 2,
       "d_model": 512, "n_heads": 32, "n_kv_heads": 2, "d_ff": 256,
       "vocab_size": 512, "rope_theta": 10000.0, "rope_style": "half",
       "qkv_bias": True, "norm_eps": 1e-5, "mlp_type": "swiglu",
       "tie_embeddings": False, "block_pattern": ["attn"],
       "param_dtype": "bfloat16", "compute_dtype": "bfloat16"}
CHUNK = 32
TRAFFIC = {"kind": "closed", "mode": "native",
           "engine": {"slots": 4, "max_len": 128, "kv_block_size": 16,
                      "prefill_chunk": CHUNK},
           "clients": 4, "requests": 200,
           "prompt": {"dist": "lognormal", "median": 80, "sigma": 0.2,
                      "lo": 2 * CHUNK + 2, "hi": 110},
           "output": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                      "lo": 4, "hi": 16},
           "same_order": True, "warm_finished": 4, "trace_steps": 2,
           "check": {"sample": 8, "gap_max": 0.035, "control": "fp8"}}


def test_the_grouping_is_chatglm3s():
    m = spec.load_json(f"{spec.BENCH_DIR}/configs/chatglm3_6b.json")["model"]
    assert m["n_heads"] // m["n_kv_heads"] == GLM["n_heads"] // \
        GLM["n_kv_heads"] == 16
    assert (m["rope_style"], m["qkv_bias"]) == (GLM["rope_style"],
                                                GLM["qkv_bias"])
    assert GLM["d_model"] // GLM["n_heads"] == 16


@pytest.mark.parametrize("seed", [9, 2**31 + 13])
def test_reference_agrees_with_the_program_forward_at_16_to_1(seed):
    """float32 on both sides, every matmul at full precision: what is
    left is the order of float32 sums, 2.6e-6 at most on logits up to
    1.8 (both seeds). 2e-4 leaves that room and still fails the program
    computed in bfloat16, which departs by 0.021."""
    from repro.models.model import Model
    m = dict(GLM, param_dtype="float32", compute_dtype="float32")
    model = Model(model_config(m))
    params = weights.program_params(weights.root_key(seed), m)
    tokens = np.random.default_rng(seed).integers(0, 512, (2, 48)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(params, {"tokens": jnp.asarray(tokens)})
    pick = np.tile(np.arange(48, dtype=np.int32), (2, 1))
    got = dense.logits_at(seed, m, tokens, pick)
    np.testing.assert_allclose(got, np.asarray(want)[..., :512],
                               rtol=2e-4, atol=2e-4)


def cell():
    return {"workload": {"name": "small-longdoc", "chips": 1},
            "config": {"reference": "dense", "model": dict(GLM)},
            "traffic": TRAFFIC,
            "end_to_end": [{"name": "out_tok_s", "unit": "tokens/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


@pytest.mark.parametrize("seed", [3, 2**31 + 3])
def test_closed_cell_at_16_to_1_is_correct_and_the_control_is_not(
        monkeypatch, seed):
    from repro.serving.engine import ServeEngine
    seen = []
    advance = ServeEngine._advance_chunk

    def counted(self, done):
        seen.append((self.kv_layout, self.pending_chunk["nchunks"]))
        return advance(self, done)

    monkeypatch.setattr(ServeEngine, "_advance_chunk", counted)
    res = run.run_cell(cell(), seed, 1.0, False, spec.peaks("TPU v5 lite"),
                       time.monotonic(), control=True)
    assert {layout for layout, _ in seen} == {"paged"}
    assert min(n for _, n in seen) >= 3
    limit = TRAFFIC["check"]["gap_max"]
    assert res["correct"] is True, res["checks"]
    assert res["control"]["gap_max"] <= limit
    assert res["control"]["control_gap_max"] > limit
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["out_tok_s"]["value"] > 0
