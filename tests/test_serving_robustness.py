"""Fault-tolerance tests for the serving engine (Issue 9): deadlines,
admission backpressure, preemption-with-recompute, the tier-degradation
ladder, allocator integrity guards, the NaN/Inf numerics guard, and the
finish_reason lattice across layouts and quality tiers.

Token-identity assertions lean on the paged slot == position invariant:
a preempted lane re-prefilled from prompt + accumulated output must
resume bit-identically, so every recovery path is checked against an
unconstrained reference run of the same requests.
"""
import jax
import numpy as np
import pytest

from repro.core.numerics import DotEngine
from repro.models.config import ModelConfig
from repro.models.model import Model
from repro.serving.degrade import DegradeLadder
from repro.serving.engine import WORK_COUNTERS, Request, ServeEngine

VOCAB = 512


def _tiny_cfg(**over):
    base = dict(name="t", family="dense", n_layers=2, d_model=16,
                n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=VOCAB,
                param_dtype="float32", compute_dtype="float32")
    base.update(over)
    return ModelConfig(**base)


def _tiny_model(mode="native", **eng_over):
    model = Model(_tiny_cfg(), DotEngine(mode=mode, **eng_over))
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, n).astype(np.int32) for n in lens]


def _serve(model, params, prompts, *, max_new=4, eos_id=None,
           reqs=None, **kw):
    eng = ServeEngine(model, params, **kw)
    if reqs is None:
        reqs = [Request(rid=rid, prompt=p, max_new_tokens=max_new,
                        eos_id=eos_id) for rid, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    return eng, sorted(done, key=lambda r: r.rid)


@pytest.fixture(scope="module")
def tiny():
    return _tiny_model()


class TestDeadlines:
    @pytest.mark.parametrize("layout", ["paged", "contiguous"])
    def test_expires_while_queued(self, tiny, layout):
        model, params = tiny
        # slots=1: rid 1 waits behind an 8-token decode and its 2-step
        # budget expires in the queue — finished at the schedule
        # boundary, never activated
        reqs = [Request(rid=0, prompt=_prompts([4])[0], max_new_tokens=8),
                Request(rid=1, prompt=_prompts([4], seed=1)[0],
                        max_new_tokens=8, deadline_steps=2)]
        eng, done = _serve(model, params, None, reqs=reqs, slots=1,
                           max_len=16, kv_layout=layout, kv_block_size=4)
        assert done[0].finish_reason == "length"
        assert done[1].finish_reason == "deadline"
        assert done[1].output == []
        assert done[1].s_done == 2
        rep = ServeEngine.latency_report(done)
        assert rep["finish_reasons"] == {"length": 1, "deadline": 1}
        assert eng.counters["deadline"] == 1

    @pytest.mark.parametrize("layout", ["paged", "contiguous"])
    def test_expires_mid_decode_keeps_clean_prefix(self, tiny, layout):
        model, params = tiny
        kw = dict(slots=1, max_len=32, kv_layout=layout, kv_block_size=4)
        _, base = _serve(model, params, _prompts([5]), max_new=10, **kw)
        req = Request(rid=0, prompt=_prompts([5])[0], max_new_tokens=10,
                      deadline_steps=4)
        _, done = _serve(model, params, None, reqs=[req], **kw)
        assert done[0].finish_reason == "deadline"
        # never cut mid-token: the partial stream is a prefix of the
        # uninterrupted run
        n = len(done[0].output)
        assert 0 < n < 10
        assert done[0].output == base[0].output[:n]

    def test_deadline_validated(self, tiny):
        model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=16)
        with pytest.raises(ValueError, match="deadline_steps"):
            eng.submit(Request(rid=0, prompt=_prompts([4])[0],
                               deadline_steps=0))


class TestBackpressure:
    def test_overflow_sheds_rejected(self, tiny):
        model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=16,
                          max_queue=2, kv_block_size=4)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=3)
                for i, p in enumerate(_prompts([4, 4, 4, 4]))]
        admitted = [eng.submit(r) for r in reqs]
        assert admitted == [True, True, False, False]
        done = sorted(eng.run(), key=lambda r: r.rid)
        assert len(done) == 4               # sheds drain into done
        assert [r.finish_reason for r in done] == \
            ["length", "length", "rejected", "rejected"]
        assert all(r.output == [] and r.s_done is not None
                   for r in done[2:])
        rep = ServeEngine.latency_report(done)
        assert rep["finish_reasons"] == {"length": 2, "rejected": 2}
        assert eng.counters["rejected"] == 2

    def test_max_queue_validated(self, tiny):
        model, params = tiny
        with pytest.raises(ValueError, match="max_queue"):
            ServeEngine(model, params, slots=1, max_len=16, max_queue=0)


class TestPreemption:
    # Pool sized so two 8-token decodes genuinely collide: 5 usable
    # blocks, each lane peaks at 3 — the second grower gets evicted and
    # must recompute.
    KW = dict(slots=2, max_len=16, kv_block_size=4, kv_blocks=6)
    BIG = dict(slots=2, max_len=16, kv_block_size=4, kv_blocks=16)

    def test_recompute_is_bit_identical(self, tiny):
        model, params = tiny
        prompts = _prompts([4, 4])
        _, big = _serve(model, params, prompts, max_new=8, **self.BIG)
        eng, done = _serve(model, params, prompts, max_new=8, **self.KW)
        assert eng.counters["preempted"] >= 1
        assert sum(r.n_preempts for r in done) >= 1
        for r, b in zip(done, big):
            assert r.finish_reason == "length"
            assert r.output == b.output     # recompute invariant
        assert eng.free_blocks == eng.kv_blocks - 1
        assert eng.kv_report()["integrity_ok"]

    def test_victim_is_lowest_priority(self, tiny):
        model, params = tiny
        prompts = _prompts([4, 4])
        # rid 0 has the LOWER priority: it gets evicted even though the
        # tie-break (highest rid) would otherwise pick rid 1
        reqs = [Request(rid=0, prompt=prompts[0], max_new_tokens=8,
                        priority=0),
                Request(rid=1, prompt=prompts[1], max_new_tokens=8,
                        priority=1)]
        _, big = _serve(model, params, prompts, max_new=8, **self.BIG)
        _, done = _serve(model, params, None, reqs=reqs, **self.KW)
        assert done[0].n_preempts >= 1
        assert done[1].n_preempts == 0
        for r, b in zip(done, big):
            assert r.output == b.output

    def test_preempt_false_restores_terminal_cache_full(self, tiny):
        model, params = tiny
        _, done = _serve(model, params, _prompts([4]), max_new=6,
                         slots=1, max_len=16, kv_block_size=2,
                         kv_blocks=3, preempt=False)
        assert done[0].finish_reason == "cache_full"
        assert len(done[0].output) == 1
        assert done[0].n_preempts == 0

    def test_preempt_limit_bounds_pingpong(self, tiny):
        model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=16,
                          kv_block_size=4, preempt_limit=1)
        eng.submit(Request(rid=0, prompt=_prompts([3])[0],
                           max_new_tokens=8))
        done = []
        eng.step(done)
        req = eng.active[0]
        eng._preempt(0, req, done)          # 1st: requeue + recompute
        assert req.n_preempts == 1 and not done
        eng.step(done)                      # re-prefill
        eng._preempt(0, eng.active[0], done)  # 2nd: past the limit
        assert done and done[0].finish_reason == "cache_full"
        assert eng.counters["preempted"] == 1
        assert eng.counters["cache_full"] == 1


class TestAdmissionDeadlockGuard:
    def test_transient_hold_waits_instead_of_terminal(self, tiny):
        model, params = tiny
        # prompt needs 2 of 3 usable blocks — servable, but all three
        # are reserved out of the pool: the request must WAIT (the old
        # guard would have killed it as an idle-engine deadlock)
        eng = ServeEngine(model, params, slots=1, max_len=16,
                          kv_block_size=4, kv_blocks=4)
        held = eng.reserve_blocks(3)
        assert eng.free_blocks == 0
        assert eng.kv_report()["kv_blocks_held"] == 3
        eng.submit(Request(rid=0, prompt=_prompts([8])[0],
                           max_new_tokens=3))
        done = []
        for _ in range(4):
            eng.step(done)
        assert not done and len(eng.queue) == 1
        eng.release_blocks(held)
        done = eng.run()
        assert done[0].finish_reason == "length"
        assert eng.kv_report()["integrity_ok"]

    def test_unservable_prompt_still_terminal(self, tiny):
        model, params = tiny
        # 9 tokens need 3 blocks; the whole pool holds 2 — can never be
        # served, terminal cache_full (pre-existing semantics)
        _, done = _serve(model, params, _prompts([9]), max_new=4,
                         slots=1, max_len=16, kv_block_size=4,
                         kv_blocks=3)
        assert done[0].finish_reason == "cache_full"
        assert done[0].output == []

    def test_reserve_requires_paged(self, tiny):
        model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=16,
                          kv_layout="contiguous")
        with pytest.raises(ValueError, match="paged"):
            eng.reserve_blocks(1)


class TestDegradeLadder:
    def test_build_validation(self):
        with pytest.raises(ValueError, match=">= 2 rungs"):
            DegradeLadder.build(["native"], base_mode="native")
        with pytest.raises(ValueError, match="not registered"):
            DegradeLadder.build(["native", "olm7"], base_mode="native")
        with pytest.raises(ValueError, match="rung 0"):
            DegradeLadder.build(["olm8", "olm16"], base_mode="native")
        with pytest.raises(ValueError, match="duplicate"):
            DegradeLadder.build(["native", "olm8", "olm8"],
                                base_mode="native")
        lad = DegradeLadder.build(["native", "olm8"], base_mode="native")
        assert lad.rung_of("native") == 0
        assert lad.rung_of(None) == 0       # unladdered tiers start at 0
        assert lad.next_mode(0) == "olm8"
        assert lad.next_mode(1) is None
        assert lad.kv_pressure(1, 8)        # 1/8 < 0.25
        assert not lad.kv_pressure(4, 8)
        assert not lad.kv_pressure(0, 0)    # contiguous: no pool

    def test_overflow_downshift_matches_dedicated_deployment(self, tiny):
        model, params = tiny
        prompts = _prompts([4, 5, 6])
        eng = ServeEngine(model, params, slots=1, max_len=16,
                          kv_block_size=4, max_queue=1,
                          degrade_ladder=["native", "olm8"],
                          degrade_queue_headroom=1)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        assert eng.submit(reqs[0])          # fills the queue
        assert eng.submit(reqs[1])          # re-admitted one rung down
        assert not eng.submit(reqs[2])      # headroom spent: rejected
        done = sorted(eng.run(), key=lambda r: r.rid)
        assert done[0].finish_reason == "length"
        assert done[0].served_tier == "native" and done[0].degrade_rung == 0
        assert done[1].finish_reason == "length"
        assert done[1].served_tier == "olm8" and done[1].degrade_rung == 1
        assert done[2].finish_reason == "rejected"
        assert eng.counters["degraded"] == 1
        # the degraded request is served exactly as a dedicated olm8
        # deployment would serve it
        model8, params8 = _tiny_model("olm8")
        _, ded = _serve(model8, params8, [prompts[1]], max_new=4,
                        slots=1, max_len=16, kv_block_size=4)
        assert done[1].output == ded[0].output

    def test_preempt_downshift_under_kv_pressure(self, tiny):
        model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=16,
                          kv_block_size=4, kv_blocks=9,
                          degrade_ladder=["native", "olm8"],
                          # the evicted lane's own 2 blocks come back
                          # before the pressure check: 2/8 free must
                          # still count as pressure here
                          degrade_free_frac=0.5)
        eng.submit(Request(rid=0, prompt=_prompts([4])[0],
                           max_new_tokens=6))
        done = []
        eng.step(done)
        held = eng.reserve_blocks(eng.free_blocks)  # free/usable -> low
        eng._preempt(0, eng.active[0], done)
        eng.release_blocks(held)
        done += eng.run()
        assert done[0].finish_reason == "length"
        assert done[0].n_preempts == 1
        assert done[0].degrade_rung == 1
        assert done[0].served_tier == "olm8"

    def test_ladder_rung_collision_with_quality_tier(self, tiny):
        model, params = tiny
        with pytest.raises(ValueError, match="collides"):
            ServeEngine(model, params, slots=1, max_len=16,
                        quality_tiers={"olm8": "olm16"},
                        degrade_ladder=["native", "olm8"])


class TestIntegrityGuards:
    def test_double_free_raises(self, tiny):
        model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=16,
                          kv_block_size=4)
        eng.submit(Request(rid=0, prompt=_prompts([4])[0],
                           max_new_tokens=8))
        eng.step([])
        owned = eng.owned_blocks(0)
        assert owned
        eng._free_slot_blocks(0)
        eng._owned[0] = owned               # simulate corrupted shadow
        with pytest.raises(RuntimeError, match="double-free"):
            eng._free_slot_blocks(0)

    def test_corrupted_free_list_detected_at_alloc(self, tiny):
        model, params = tiny
        eng = ServeEngine(model, params, slots=2, max_len=16,
                          kv_block_size=4)
        eng.submit(Request(rid=0, prompt=_prompts([4])[0],
                           max_new_tokens=8))
        eng.step([])
        owned_bid = eng.owned_blocks(0)[0]
        eng._free.append(owned_bid)         # duplicate of a live block
        with pytest.raises(RuntimeError, match="free list corrupted"):
            eng._alloc_blocks(1, 1)

    def test_audit_repairs_active_lane_by_recompute(self, tiny):
        model, params = tiny
        kw = dict(slots=1, max_len=16, kv_block_size=4)
        _, base = _serve(model, params, _prompts([4]), max_new=8, **kw)
        eng = ServeEngine(model, params, integrity_audit=True, **kw)
        eng.submit(Request(rid=0, prompt=_prompts([4])[0],
                           max_new_tokens=8))
        done = []
        eng.step(done)
        eng.corrupt_table_entry(0, 0, eng.kv_blocks + 3)
        assert not eng.kv_report()["integrity_ok"]
        done += eng.run()
        assert eng.counters["table_repairs"] == 1
        assert done[0].n_preempts == 1
        assert done[0].finish_reason == "length"
        assert done[0].output == base[0].output  # recovered bit-identical
        assert eng.kv_report()["integrity_ok"]

    def test_audit_rebuilds_idle_lane_row(self, tiny):
        model, params = tiny
        eng = ServeEngine(model, params, slots=2, max_len=16,
                          kv_block_size=4, integrity_audit=True)
        eng.submit(Request(rid=0, prompt=_prompts([4])[0],
                           max_new_tokens=4))
        done = []
        eng.step(done)
        eng.corrupt_table_entry(1, 0, eng.kv_blocks + 3)  # idle lane
        done += eng.run()
        assert eng.counters["table_repairs"] == 1
        assert done[0].n_preempts == 0      # active lane untouched
        assert eng.kv_report()["integrity_ok"]


class TestNumericsGuard:
    def test_decode_nan_finishes_with_clean_prefix(self, tiny):
        model, params = tiny
        kw = dict(slots=1, max_len=32, kv_block_size=4)
        _, base = _serve(model, params, _prompts([5]), max_new=8, **kw)
        eng = ServeEngine(model, params, numerics_check=True, **kw)
        calls = []

        def tap(lg, phase, step):
            if phase == "decode":
                calls.append(step)
                if len(calls) == 3:
                    lg = lg.copy()
                    lg[min(eng.active), :] = np.nan
            return lg

        eng.logits_tap = tap
        eng.submit(Request(rid=0, prompt=_prompts([5])[0],
                           max_new_tokens=8))
        done = eng.run()
        assert done[0].finish_reason == "numerics"
        # the poisoned token is never appended: 1 prefill + 2 clean
        # decode tokens, a prefix of the healthy stream
        assert done[0].output == base[0].output[:3]
        assert eng.counters["numerics"] == 1
        assert eng.free_blocks == eng.kv_blocks - 1

    def test_prefill_nan_never_activates(self, tiny):
        model, params = tiny
        eng = ServeEngine(model, params, slots=2, max_len=16,
                          kv_block_size=4, numerics_check=True)

        def tap(lg, phase, step):
            if phase == "prefill":
                lg = lg.copy()
                lg[0, :] = np.inf
            return lg

        eng.logits_tap = tap
        eng.submit(Request(rid=0, prompt=_prompts([4])[0],
                           max_new_tokens=4))
        done = eng.run()
        assert done[0].finish_reason == "numerics"
        assert done[0].output == []
        assert done[0].t_first is None
        assert eng.free_blocks == eng.kv_blocks - 1
        assert eng.kv_report()["integrity_ok"]

    def test_off_by_default_streams_through(self, tiny):
        model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=16)
        assert eng.numerics_check is False and eng.logits_tap is None


class TestFinishReasonLattice:
    """One run producing eos/length/max_len/deadline/rejected together,
    across both KV layouts and across quality tiers; cache_full,
    numerics, and failed have dedicated tests above/in
    test_serving_faults.py. latency_report must count every reason."""

    @pytest.mark.parametrize("layout", ["paged", "contiguous"])
    @pytest.mark.parametrize("tier", [None, "fast"])
    def test_all_reasons_counted(self, tiny, layout, tier):
        model, params = tiny
        tiers = {"fast": "olm8"} if tier else None
        kw = dict(slots=1, max_len=16, kv_layout=layout, kv_block_size=4,
                  quality_tiers=tiers)
        prompts = _prompts([4, 5, 12, 4, 4, 4])
        # eos token must come from the tier actually serving the request
        _, probe = _serve(model, params, None, reqs=[
            Request(rid=0, prompt=prompts[1], max_new_tokens=6,
                    quality_tier=tier)], **kw)
        eos = probe[0].output[1]
        reqs = [
            Request(rid=0, prompt=prompts[0], max_new_tokens=3,
                    quality_tier=tier),                       # length
            Request(rid=1, prompt=prompts[1], max_new_tokens=6,
                    eos_id=eos, quality_tier=tier),           # eos
            Request(rid=2, prompt=prompts[2], max_new_tokens=20,
                    quality_tier=tier),                       # max_len
            Request(rid=3, prompt=prompts[3], max_new_tokens=3,
                    deadline_steps=2, quality_tier=tier),     # deadline
            Request(rid=4, prompt=prompts[4], max_new_tokens=3,
                    quality_tier=tier),                       # rejected
            Request(rid=5, prompt=prompts[5], max_new_tokens=3,
                    quality_tier=tier),                       # rejected
        ]
        eng, done = _serve(model, params, None, reqs=reqs,
                           max_queue=4, **kw)
        assert len(done) == 6
        by_rid = {r.rid: r.finish_reason for r in done}
        assert by_rid == {0: "length", 1: "eos", 2: "max_len",
                          3: "deadline", 4: "rejected", 5: "rejected"}
        rep = ServeEngine.latency_report(done)
        assert rep["finish_reasons"] == {
            "length": 1, "eos": 1, "max_len": 1, "deadline": 1,
            "rejected": 2}
        assert sum(rep["finish_reasons"].values()) == rep["n"]
        assert {k: v for k, v in eng.counters.items()
                if k not in WORK_COUNTERS} == rep["finish_reasons"]
        want_mode = "olm8" if tier else "native"
        served = [r for r in done if r.output]
        assert served and all(r.served_tier == want_mode for r in served)
