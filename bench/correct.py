"""What decides `correct`: served tokens against the plain reference.

Once the window has closed, a sample of the served requests, drawn from
the seed and always holding the one with the most served tokens, is run
through the reference (prompt plus served tokens, teacher-forced). For
every served token the gap is the reference's best logit minus the
reference's logit of that token: 0 where the program chose what the
reference would, small where rounding broke a near tie, large where the
program computed something else. `gap_max`, the widest gap over the
sample, is compared with the cell's limit.

The control reads the same gap for the token that the reference in the
lower precision named by the traffic file's `check.control` puts first
at each position (`reference.<name>`'s `numerics`), without decoding.
"""
from __future__ import annotations

import numpy as np

PAD = 256


def sample(records: list, seed: int, k: int) -> list:
    """Up to k served requests: finished ones first, then (when fewer
    finished) those still decoding, with their tokens so far. The one
    with the most served tokens is always in; the rest are drawn by the
    seed."""
    served = [r for r in records if r["req"].output and r["rid"] >= 0]
    fin = [r for r in served if r["req"].finish_reason is not None]
    pool = fin if len(fin) >= k else served
    pool = sorted(pool, key=lambda r: (-len(r["req"].output), r["rid"]))
    rng = np.random.default_rng(int(seed) + 1)
    rest = list(rng.permutation(len(pool) - 1) + 1)[:k - 1] if pool else []
    return [pool[0]] + [pool[i] for i in sorted(rest)] if pool else []


def batch(picked: list) -> tuple:
    """(tokens (B, S), pick (B, n), served (B, n), mask (B, n)) with
    each row's prompt plus served tokens but the last, right-padded to a
    multiple of PAD."""
    seqs, outs = [], []
    for r in picked:
        prompt = np.asarray(r["req"].prompt, np.int32)
        out = np.asarray(r["req"].output, np.int32)
        seqs.append(np.concatenate([prompt, out[:-1]]))
        outs.append((len(prompt), out))
    S = -(-max(len(s) for s in seqs) // PAD) * PAD
    n = max(len(o) for _, o in outs)
    tokens = np.zeros((len(seqs), S), np.int32)
    pick = np.zeros((len(seqs), n), np.int32)
    served = np.zeros((len(seqs), n), np.int32)
    mask = np.zeros((len(seqs), n), bool)
    for i, (s, (P, o)) in enumerate(zip(seqs, outs)):
        tokens[i, :len(s)] = s
        pick[i, :len(o)] = P - 1 + np.arange(len(o))
        served[i, :len(o)] = o
        mask[i, :len(o)] = True
    return tokens, pick, served, mask


def token_gaps(ref: np.ndarray, chosen: np.ndarray,
               mask: np.ndarray) -> np.ndarray:
    """Reference best logit minus the reference logit of each chosen
    token, over the masked positions."""
    V = ref.shape[-1]
    best = ref.max(axis=-1)
    got = np.take_along_axis(ref, np.clip(chosen, 0, V - 1)[..., None],
                             axis=-1)[..., 0]
    gap = np.where((chosen >= 0) & (chosen < V), best - got, np.inf)
    return gap[mask]


def readings(ref_mod, seed: int, model: dict, picked: list,
             control: str | None = None) -> dict:
    """The numbers compared: gap_max of the served tokens; with
    `control` (a reference `numerics`), also the control's gap_max on
    the same positions, as `control_gap_max`."""
    tokens, pick, served, mask = batch(picked)
    ref = ref_mod.logits_at(seed, model, tokens, pick)
    g = token_gaps(ref, served, mask)
    out = {"gap_max": float(g.max()), "tokens": int(mask.sum()),
           "mismatch": int((g > 0).sum())}
    if control:
        lg = ref_mod.logits_at(seed, model, tokens, pick, numerics=control)
        cg = token_gaps(ref, lg.argmax(axis=-1), mask)
        out.update(control=control, control_gap_max=float(cg.max()),
                   control_mismatch=int((cg > 0).sum()))
    return out
