"""Operations and bytes of the model's work, from a configuration's sizes.

The same count holds whatever implements the work (a bf16 MXU GEMM or
the digit-serial olm array), so a roofline share or a utilization stays
comparable across numerics modes and across PRs. Vocabulary counts use
the real vocabulary, not the program's padded table.
"""
from __future__ import annotations


def gemms(m: dict, rows: int) -> list:
    """The weight GEMMs (name, M, K, N) of one forward step over `rows`
    token rows: q, k, v, o, gate, up, down per layer, then the output
    head."""
    d, H, Hkv, f = m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_ff"]
    hd = d // H
    per_layer = [("q", d, H * hd), ("k", d, Hkv * hd), ("v", d, Hkv * hd),
                 ("o", H * hd, d), ("gate", d, f), ("up", d, f),
                 ("down", f, d)]
    out = []
    for layer in range(m["n_layers"]):
        out += [(f"{n}{layer}", rows, k, n_) for n, k, n_ in per_layer]
    return out + [("head", rows, d, m["vocab_size"])]


def matmul_params(m: dict) -> int:
    """Weights that every token multiplies (MACs per token row)."""
    return sum(k * n for _, _, k, n in gemms(m, 1))


def attention_flops(m: dict, context: int) -> int:
    """Score and value products of one token attending over `context`
    positions, all layers: 2 * 2 * context * heads * head_dim each."""
    hd = m["d_model"] // m["n_heads"]
    return 4 * m["n_layers"] * context * m["n_heads"] * hd


def token_flops(m: dict, context: int) -> int:
    """Model FLOPs of one token at `context` positions of attention."""
    return 2 * matmul_params(m) + attention_flops(m, context)


def gemm_least_time(M: int, K: int, N: int, peaks: dict,
                    operand_bytes: int = 4) -> tuple:
    """(seconds, bound) of the least time the chip could take for an
    (M, K) x (K, N) GEMM: the larger of its operations over the bf16 peak
    and its operand and result bytes over HBM bandwidth."""
    t_flops = 2 * M * K * N / peaks["bf16_flops"]
    t_bytes = (operand_bytes * (M * K + K * N + M * N)
               / peaks["hbm_bytes_per_s"])
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")
