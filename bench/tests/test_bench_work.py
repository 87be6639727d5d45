"""bench/work.py against hand counts."""
import pytest

from bench import spec, work


def model(name):
    return spec.load_json(f"{spec.BENCH_DIR}/configs/{name}.json")["model"]


def test_internlm2_macs_per_token():
    m = model("internlm2_1_8b")
    layer = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 8192
    assert work.matmul_params(m) == 24 * layer + 2048 * 92544
    assert work.matmul_params(m) == pytest.approx(1.70e9, rel=0.005)


def test_chatglm3_gemm_shapes():
    m = model("chatglm3_6b")
    g = {n.rstrip("0123456789"): (M, K, N)
         for n, M, K, N in work.gemms(m, rows=8)}
    assert g == {"q": (8, 4096, 4096), "k": (8, 4096, 256),
                 "v": (8, 4096, 256), "o": (8, 4096, 4096),
                 "gate": (8, 4096, 13696), "up": (8, 4096, 13696),
                 "down": (8, 13696, 4096), "head": (8, 4096, 65024)}
    assert len(work.gemms(m, 8)) == 28 * 7 + 1


def test_attention_and_token_flops():
    m = model("internlm2_1_8b")
    assert work.attention_flops(m, 100) == 4 * 24 * 100 * 16 * 128
    assert work.token_flops(m, 1) == (2 * work.matmul_params(m)
                                      + work.attention_flops(m, 1))


def test_decode_gemv_is_hbm_bound_and_big_gemm_flops_bound():
    peaks = spec.peaks("TPU v5 lite")
    t, bound = work.gemm_least_time(8, 2048, 8192, peaks)
    assert bound == "hbm"
    assert t == pytest.approx(4 * (8 * 2048 + 2048 * 8192 + 8 * 8192)
                              / 819e9)
    assert work.gemm_least_time(4096, 4096, 4096, peaks)[1] == "flops"
