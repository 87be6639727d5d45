"""The harness, past its look for a chip, decides `correct` by the
reference: a sound run passes, a run whose timed path is broken
underneath fails, once for each fault a serving cell can have, and the
fp8 control fails the limit that sound runs meet.

At this size (2 layers, d_model 64) sound runs read gap_max 0 to 0.0053
and the fp8 control 0.048 to 0.13 on five seeds, so the limit is 0.02."""
import time

import jax.numpy as jnp
import pytest

from bench import run, spec

MODEL = {"name": "small", "family": "dense", "n_layers": 2, "d_model": 64,
         "n_heads": 4, "n_kv_heads": 2, "d_ff": 128, "vocab_size": 512,
         "rope_theta": 10000.0, "rope_style": "half", "qkv_bias": True,
         "norm_eps": 1e-5, "mlp_type": "swiglu", "tie_embeddings": False,
         "block_pattern": ["attn"], "param_dtype": "float32",
         "compute_dtype": "bfloat16"}
TRAFFIC = {"kind": "closed", "mode": "native",
           "engine": {"slots": 4, "max_len": 128, "kv_block_size": 16,
                      "prefill_chunk": 32},
           "clients": 4, "requests": 200,
           "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                      "lo": 8, "hi": 60},
           "output": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "lo": 4, "hi": 24},
           "trace_steps": 2,
           "check": {"sample": 4, "gap_max": 0.02, "control": "fp8"}}


def cell():
    return {"workload": {"name": "small-closed", "chips": 1},
            "config": {"reference": "dense", "model": dict(MODEL)},
            "traffic": TRAFFIC,
            "end_to_end": [{"name": "out_tok_s", "unit": "tokens/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def unchanged_state(decode):
    def f(self, params, token, pos, cache, memory=None):
        logits, _ = decode(self, params, token, pos, cache, memory)
        return logits, cache
    return f


def token_altered(decode):
    def f(self, params, token, pos, cache, memory=None):
        logits, cache = decode(self, params, token, pos, cache, memory)
        return jnp.roll(logits, 1, axis=-1), cache
    return f


def half_left_out(decode):
    def f(self, params, token, pos, cache, memory=None):
        logits, cache = decode(self, params, token, pos, cache, memory)
        half = logits.shape[0] // 2
        return logits.at[half:].set(0.0), cache
    return f


@pytest.mark.parametrize("fault,want", [
    (None, True),
    (unchanged_state, False),
    (token_altered, False),
    (half_left_out, False),
])
def test_correct_follows_the_reference(monkeypatch, fault, want):
    from repro.models.model import Model
    if fault is not None:
        monkeypatch.setattr(Model, "decode_step", fault(Model.decode_step))
    res = run.run_cell(cell(), 2**31 + 3, 1.0, False,
                       spec.peaks("TPU v5 lite"), time.monotonic())
    assert res["correct"] is want, res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["metrics"]["out_tok_s"]["value"] > 0


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_control_fails_where_the_program_passes(seed):
    res = run.run_cell(cell(), seed, 1.0, False, spec.peaks("TPU v5 lite"),
                       time.monotonic(), control=True)
    limit = TRAFFIC["check"]["gap_max"]
    assert res["control"]["gap_max"] <= limit
    assert res["control"]["control"] == "fp8"
    assert res["control"]["control_gap_max"] > limit


def test_open_loop_after_a_warm_in_is_correct():
    traffic = dict(TRAFFIC, kind="open", rate=6.0, warm_s=1.0)
    del traffic["clients"], traffic["requests"]
    c = dict(cell(), traffic=traffic,
             end_to_end=[{"name": "itl_p95_ms", "unit": "ms"},
                         {"name": "setup_s", "unit": "s"}])
    res = run.run_cell(c, 2**31 + 5, 1.0, False, spec.peaks("TPU v5 lite"),
                       time.monotonic())
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["itl_p95_ms"]["value"] > 0
