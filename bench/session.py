"""The system under test, driven through its public serving API.

`Session` builds the model and `ServeEngine` of a cell, submits requests
and steps the engine, and keeps the harness's own records: the due time
of each request, the host time of each of its output tokens, the prompt
and decode tokens each step processed, and a span around every call
into the engine (`bench.step`, `bench.submit`, `bench.wait`), written
into the profiler's trace when one is taken.
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import numpy as np

from bench import weights


def model_config(m: dict):
    from repro.models.config import ModelConfig
    fields = {k: v for k, v in m.items()
              if k in {f.name for f in dataclasses.fields(ModelConfig)}}
    fields["block_pattern"] = tuple(fields.get("block_pattern", ("attn",)))
    return ModelConfig(**fields)


def pow2(n: int, lo: int = 1) -> int:
    return max(lo, 1 << max(0, math.ceil(math.log2(max(1, n)))))


class Session:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core.numerics import EngineSpec
        from repro.models.model import Model
        from repro.serving.engine import Request, ServeEngine
        m = config["model"]
        self.m = m
        self.traffic = traffic
        e = traffic["engine"]
        self.slots, self.max_len = e["slots"], e["max_len"]
        self.chunk = e.get("prefill_chunk")
        self.bucket_min = e.get("prefill_bucket_min", 8)
        params = weights.program_params(weights.root_key(seed), m)
        spec = {"mode": traffic["mode"]}
        if traffic.get("tiling"):
            spec["tiling"] = traffic["tiling"]
        self.engine = ServeEngine(
            Model(model_config(m)), params, slots=self.slots,
            max_len=self.max_len, kv_block_size=e["kv_block_size"],
            kv_blocks=e.get("kv_blocks"), prefill_chunk=self.chunk,
            prefill_bucket_min=self.bucket_min, engine=EngineSpec(**spec))
        del params
        self.done: list = []
        self.reqs: dict = {}         # rid -> record
        self.steps: list = []        # one record per engine step
        self._Request = Request

    # ---------------- driving ----------------
    def submit(self, r: dict, due: float) -> None:
        req = self._Request(rid=r["rid"], prompt=r["prompt"],
                            max_new_tokens=r["max_new"])
        with jax.profiler.TraceAnnotation("bench.submit"):
            self.engine.submit(req)
        self.reqs[r["rid"]] = {"rid": r["rid"], "req": req, "due": due,
                               "submitted": time.monotonic(),
                               "prompt_len": len(r["prompt"]),
                               "token_times": []}

    def busy(self) -> bool:
        e = self.engine
        return bool(e.queue or e.active or e.pending_chunk)

    def step(self) -> dict:
        """One engine step, with the output tokens it produced (stamped
        with the step's end) and the prompt and decode tokens it
        processed, with the attention context they covered."""
        e = self.engine
        pc = e.pending_chunk
        chunk = (pc["req"].rid, pc["next"], len(pc["seq"])) if pc else None
        live = [rec for rec in self.reqs.values()
                if rec["req"].finish_reason is None]
        before = [len(rec["req"].output) for rec in live]
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.step"):
            e.step(self.done)
        t1 = time.monotonic()
        out = {"t0": t0, "t1": t1, "prompt_tokens": 0, "prompt_ctx": 0,
               "decode_tokens": 0, "decode_ctx": 0}
        if chunk is not None:               # one chunk of a long prompt
            _, j, P = chunk
            s0, n = j * self.chunk, min(self.chunk, P - j * self.chunk)
            out["prompt_tokens"] += n
            out["prompt_ctx"] += n * s0 + n * (n + 1) // 2
        for rec, b in zip(live, before):
            a = len(rec["req"].output)
            rec["token_times"] += [t1] * (a - b)
            P = rec["prompt_len"]
            first = b
            if b == 0 and a > 0:            # activated: prefill's token
                first = 1
                if not (chunk and chunk[0] == rec["rid"]):
                    out["prompt_tokens"] += P
                    out["prompt_ctx"] += P * (P + 1) // 2
            # the k-th output token (k >= 1) is decoded at position
            # P + k - 1 and attends over P + k positions
            for k in range(first, a):
                out["decode_tokens"] += 1
                out["decode_ctx"] += P + k
        self.steps.append(out)
        return out

    def wait_until(self, t: float) -> None:
        dt = t - time.monotonic()
        if dt > 0:
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(dt)

    # ---------------- set-up ----------------
    def prefill_shapes(self, plo: int, phi: int) -> tuple:
        """The (rows, length) prefill buckets and the chunked-prefill
        lengths that prompts of plo..phi tokens can reach, by the
        engine's documented bucketing: rows and lengths rounded up to
        powers of two (lengths at least `prefill_bucket_min`), prompts
        longer than `prefill_chunk` split into whole chunks."""
        short = range(plo, (min(phi, self.chunk) if self.chunk else phi) + 1)
        lens = sorted({min(pow2(p, self.bucket_min), self.max_len)
                       for p in short})
        rows = sorted({pow2(k) for k in range(1, self.slots + 1)})
        chunks = []
        if self.chunk:
            chunks = sorted({-(-p // self.chunk) * self.chunk
                             for p in range(max(plo, self.chunk + 1),
                                            phi + 1)})
        return [(b, L) for b in rows for L in lens], chunks

    def warm_up(self, plo: int, phi: int) -> None:
        """Compile every program that prompts of plo..phi tokens can reach,
        by serving throw-away requests of each shape through the public
        API (one new token each), then one that decodes; then forget
        them."""
        buckets, chunks = self.prefill_shapes(plo, phi)
        rng = np.random.default_rng(0)
        rid = -1

        def serve(lengths, max_new=1):
            nonlocal rid
            for L in lengths:
                self.engine.submit(self._Request(
                    rid=rid, prompt=rng.integers(
                        0, self.m["vocab_size"], L, dtype=np.int32),
                    max_new_tokens=max_new))
                rid -= 1
            while self.busy():
                self.engine.step(self.done)

        for b, L in buckets:
            serve([min(L, self.max_len - 1)] * b)
        for total in chunks:
            serve([total - 1])
        serve([plo], max_new=2)
        self.done.clear()

    def drop(self) -> None:
        """Free the program's device state (weights and caches) now,
        whatever else still refers to the engine."""
        e, self.engine = self.engine, None
        for leaf in jax.tree.leaves((e.params, e.cache)):
            leaf.delete()
