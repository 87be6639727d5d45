"""Per-architecture smoke tests (reduced same-family configs, CPU).

For each assigned arch: one forward pass + one grad step asserting output
shapes and no NaNs, plus prefill+decode == full forward consistency.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs, smoke_config
from repro.models.model import Model, lm_loss

KEY = jax.random.PRNGKey(1)


def _batch(cfg, B=2, S=12):
    b = {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "encdec":
        b["frames"] = jax.random.normal(KEY, (B, cfg.n_frontend_tokens, cfg.d_model))
    if cfg.family == "vlm":
        b["patches"] = jax.random.normal(KEY, (B, cfg.n_frontend_tokens, cfg.d_model))
    return b


@pytest.fixture(scope="module", params=list_archs())
def arch_setup(request):
    cfg = smoke_config(request.param)
    m = Model(cfg)
    params = m.init(KEY)
    return request.param, cfg, m, params


class TestSmoke:
    def test_forward_shapes_no_nan(self, arch_setup):
        arch, cfg, m, params = arch_setup
        B, S = 2, 12
        batch = _batch(cfg, B, S)
        logits, aux = m.forward(params, batch)
        assert logits.shape == (B, S, cfg.vocab_size)
        assert not np.isnan(np.asarray(logits)).any()
        assert np.isfinite(float(aux))

    def test_train_step_grads_finite(self, arch_setup):
        arch, cfg, m, params = arch_setup
        batch = _batch(cfg)
        loss, metrics = lm_loss(m, params, batch)
        assert np.isfinite(float(loss))
        g = jax.grad(lambda p: lm_loss(m, p, batch)[0])(params)
        leaves = jax.tree_util.tree_leaves(g)
        assert leaves and all(np.isfinite(np.asarray(l)).all() for l in leaves)

    def test_decode_matches_forward(self, arch_setup):
        arch, cfg, m, params = arch_setup
        B, S = 2, 12
        batch = _batch(cfg, B, S)
        logits, _ = m.forward(params, batch)
        cache = m.init_cache(B, max_len=S + 4)
        pf = {**batch, "tokens": batch["tokens"][:, :S - 1]}
        lg_p, cache, memory = m.prefill(params, pf, cache)
        lg_d, cache = m.decode_step(
            params, batch["tokens"][:, S - 1],
            jnp.full((B,), S - 1, jnp.int32), cache, memory)
        scale = np.abs(np.asarray(logits)).max()
        assert np.max(np.abs(np.asarray(lg_p) - np.asarray(logits[:, S - 2]))) / scale < 2e-2
        assert np.max(np.abs(np.asarray(lg_d) - np.asarray(logits[:, S - 1]))) / scale < 2e-2


class TestFullConfigs:
    def test_param_counts_match_published(self):
        # analytic counts land near the published sizes
        expect = {
            "qwen3_moe_235b_a22b": 235e9, "mixtral_8x22b": 141e9,
            "recurrentgemma_9b": 9.6e9, "chatglm3_6b": 6.2e9,
            "qwen1_5_110b": 111e9, "internlm2_1_8b": 1.9e9,
            "yi_34b": 34.4e9, "seamless_m4t_medium": 0.7e9,
            "mamba2_130m": 0.13e9, "llama_3_2_vision_11b": 10.6e9,
        }
        for a, want in expect.items():
            got = get_config(a).param_count()
            assert abs(got - want) / want < 0.25, (a, got, want)

    def test_long_context_archs_are_subquadratic(self):
        # long_500k only runs for archs with bounded attention state
        for a in ("mixtral_8x22b", "recurrentgemma_9b", "mamba2_130m"):
            cfg = get_config(a)
            assert cfg.sliding_window is not None or cfg.family == "ssm"


class TestFlashAttention:
    def test_flash_equals_plain(self, rng):
        from repro.models.layers import _attn_flash, _attn_plain
        B, S, H, D = 2, 64, 6, 16
        q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
        qpos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        kpos = jnp.arange(S)
        for causal, window in [(True, None), (True, 16), (False, None)]:
            a = _attn_plain(q, k, v, qpos, kpos, causal=causal, window=window)
            b = _attn_flash(q, k, v, qpos, kpos, causal=causal,
                            window=window, chunk=16)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=2e-5)

    def test_flash_with_empty_slots(self, rng):
        from repro.models.layers import _attn_flash, _attn_plain
        B, S, H, D, T = 1, 4, 2, 8, 32
        q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
        kpos = jnp.where(jnp.arange(T) < 20, jnp.arange(T), -1)  # 12 empty
        qpos = jnp.broadcast_to(jnp.arange(16, 20)[None], (B, S))
        a = _attn_plain(q, k, v, qpos, kpos, causal=True, window=None)
        b = _attn_flash(q, k, v, qpos, kpos, causal=True, window=None, chunk=8)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)

    def test_gqa_repeat_equals_grouped(self, rng):
        # flat-head (repeated-kv) attention == reference grouped GQA math
        from repro.models.layers import _attn_core
        B, S, Hq, Hkv, D = 2, 16, 6, 2, 8
        q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
        qpos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        out = _attn_core(q, k, v, qpos, jnp.arange(S), causal=True, window=None)
        # reference grouped computation
        G = Hq // Hkv
        qg = np.asarray(q).reshape(B, S, Hkv, G, D)
        sc = np.einsum("bskgd,btkd->bkgst", qg, np.asarray(k)) / np.sqrt(D)
        mask = np.tril(np.ones((S, S), bool))
        sc = np.where(mask, sc, -1e30)
        w = np.exp(sc - sc.max(-1, keepdims=True))
        w = w / w.sum(-1, keepdims=True)
        ref = np.einsum("bkgst,btkd->bskgd", w, np.asarray(v)).reshape(B, S, Hq, D)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)

    @pytest.mark.parametrize("mask", ["causal", "window", "empty_slots"])
    @pytest.mark.parametrize("core", ["plain", "flash"])
    @pytest.mark.parametrize("G", [1, 2, 16, 32], ids=lambda g: f"G{g}")
    def test_grouped_core_equals_repeated_kv(self, rng, monkeypatch, G,
                                             core, mask):
        """`_attn_core` with G query heads per KV head (MHA, InternLM2,
        ChatGLM3, MQA at Hq = 32) equals an explicit repeat of K/V to the
        query heads followed by the flat-head core."""
        import functools
        from repro.models import layers
        B, S, T, Hq, D = 2, 8, 24, 32, 8
        Hkv = Hq // G
        if core == "flash":  # three 8-position chunks through the core
            monkeypatch.setattr(layers, "FLASH_MIN_ELEMS", 0)
            monkeypatch.setattr(layers, "_attn_flash", functools.partial(
                layers._attn_flash, chunk=8))
        flat = layers._attn_flash if core == "flash" else layers._attn_plain
        q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
        qpos = jnp.broadcast_to(jnp.arange(T - S, T)[None], (B, S))
        kpos, window = jnp.arange(T), (5 if mask == "window" else None)
        if mask == "empty_slots":  # per-lane caches, lane 1 ends at 19
            qpos = jnp.stack([jnp.arange(T - S, T), jnp.arange(12, 20)])
            kpos = jnp.stack([kpos, jnp.where(kpos < 20, kpos, -1)])
        out = layers._attn_core(q, k, v, qpos, kpos, causal=True,
                                window=window)
        ref = flat(q, jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2),
                   qpos, kpos, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("n_heads,n_kv_heads,model_size,grouped", [
        (32, 2, 1, True),    # one device or no model axis
        (32, 2, 2, True),    # 2 KV heads over a 2-way model axis
        (32, 2, 4, False),   # 2 KV heads cannot split 4 ways: repeat
        (32, 32, 1, False),  # MHA: nothing to group
    ])
    def test_grouped_gqa_path_choice(self, n_heads, n_kv_heads, model_size,
                                     grouped):
        from repro.models.layers import grouped_gqa
        assert grouped_gqa(n_heads, n_kv_heads, model_size) is grouped
