"""Continuous-batching serving engine with a paged KV cache and
disaggregated prefill/decode dispatch.

A fixed pool of `slots` decode lanes shares one jitted decode step; a
request queue feeds empty lanes. The two phases are dispatched through
separately-compiled entry points so the PR-4 shape-aware autotuner
(`dot_tiling="auto"`) buckets them independently:

  * **Prefill** is GEMM-shaped: waiting requests are batched together,
    their prompts right-padded to a shared pow2 length bucket and the
    batch row count padded to a pow2 bucket, so `model.prefill` compiles
    once per (batch, length) bucket instead of once per prompt length.
    Per-lane `last_index` picks each prompt's real final position out of
    the padded rows. A `prefill_chunk` knob splits long prompts into
    fixed-size chunks interleaved with decode steps, so one long prompt
    never stalls the running decode lanes.
  * **Decode** stays GEMV-shaped: one token per active lane per step.

KV memory defaults to the **paged** layout (`kv_layout="paged"`): each
full-attention layer holds a block pool `(num_blocks, block_size, H, D)`
plus per-lane block tables, so residency scales with live tokens instead
of `slots * max_len`, and finished lanes return their blocks to the free
list immediately. Block 0 is the shared trash block — padding rows and
idle lanes write there. Attention reads the pool through one gather of
its blocks by the block table (models/layers.py), and the paged decode is
bit-identical to the contiguous oracle (`kv_layout="contiguous"`), which
is kept both as the correctness reference and for sliding-window /
recurrent state (those layers always stay contiguous — their residency
is already bounded).

Finished lanes (EOS or max_tokens) are recycled immediately — the decode
batch never drains waiting for stragglers, which is the serving-side
analogue of the paper's pipeline never idling between vector elements
(Table III).

**Fault tolerance.** Resource pressure no longer has a single terminal
answer (`finish_reason="cache_full"`); the engine degrades instead:

  * **Deadlines** — `Request.deadline_steps` is a scheduler-step budget
    from submission; expired requests finish with
    `finish_reason="deadline"` at the schedule and decode boundaries
    (never mid-token), keeping whatever tokens they already produced.
  * **Backpressure** — `max_queue` bounds the admission queue; an
    overflowing submit is shed immediately with
    `finish_reason="rejected"` instead of growing the queue without
    bound (sheds are drained into the `run`/`step` done list).
  * **Preemption with recompute** — decode-time block exhaustion evicts
    the lowest-priority active lane (lowest `Request.priority`, then
    youngest activation): its paged blocks return to the free list, its
    table rows trash-reset, and it requeues at the head to re-prefill
    from prompt + already-generated tokens. The paged view's
    slot == position invariant makes the recomputed stream
    **token-identical** to an uninterrupted run. `preempt_limit` bounds
    ping-pong; `preempt=False` restores the old terminal behavior.
  * **Tier degradation** — `degrade_ladder` (serving/degrade.py) walks
    rejected/preempted requests down a ladder of registered DotEngine
    modes under queue/KV pressure; `Request.served_tier` records the
    mode actually served, whose `olm_error_bound` still holds.
  * **Integrity + numerics guards** — the block allocator validates
    every id it hands out (in-range, singly-owned) and detects
    double-frees loudly; `integrity_audit=True` additionally audits the
    lane tables each step and recovers corrupted lanes by
    preempt-and-recompute; `numerics_check=True` finishes a lane whose
    logits go NaN/Inf with `finish_reason="numerics"` rather than
    streaming garbage. Both off by default — the fast path is
    unchanged. `serving/faults.py` injects deterministic faults
    against all of this through the `reserve_blocks` /
    `corrupt_table_entry` / `logits_tap` / `prefill_fault` surfaces.
"""
from __future__ import annotations

import dataclasses
import math
import time
from collections import Counter, deque
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence, \
    Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.core.numerics import EngineSpec, resolve_engine
from repro.models.layers import TRASH_BLOCK, paged_scatter_rows
from repro.models.model import Model

from .degrade import DegradeLadder
from .faults import TransientPrefillError
from .report import ServeReport

__all__ = ["Request", "ServeEngine", "WORK_COUNTERS"]

# Keys of `ServeEngine.counters` that count work rather than events:
# real prompt tokens prefilled, and the tokens the prefill programs
# computed for them (row and length buckets, the last chunk's padding).
WORK_COUNTERS = ("prefill_tokens", "prefill_tokens_computed")

# Block kinds whose prefill is safe to right-pad: causal attention masks
# padded positions out, and later decode steps overwrite their cache
# slots position-for-position. Recurrent/SSM state advances on every
# token, so padded tails would corrupt it — those families fall back to
# exact-length single-request prefill.
_PAD_SAFE_KINDS = frozenset({"attn", "cross", "xdec"})


def _pow2_bucket(n: int, lo: int = 1) -> int:
    return max(lo, 1 << max(0, math.ceil(math.log2(max(1, n)))))


def _fetch(x, dtype=None) -> np.ndarray:
    """Blocking device-to-host read, under the `serve.sync` span."""
    with TraceAnnotation("serve.sync"):
        return np.asarray(x, dtype)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (P,) int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    # Per-request quality tier: a key of the engine's `quality_tiers`
    # mapping (None = the deployment's base numerics). The scheduler
    # keeps decode batches tier-homogeneous, so a request asking for a
    # truncated olm{n}t{p} tier decodes every token under that mode.
    quality_tier: Optional[str] = None
    # Scheduler-step budget from submission (None = no deadline): a
    # request still unfinished `deadline_steps` steps after submit
    # finishes with finish_reason="deadline", keeping its partial
    # output. Enforced at the schedule/decode boundaries, never
    # mid-token, so a deadlined stream is a prefix of the full stream.
    deadline_steps: Optional[int] = None
    # Preemption victim ordering: lower priority is evicted first when
    # the block pool runs dry (ties: youngest activation, then highest
    # rid). Priority does not reorder the FIFO admission queue.
    priority: int = 0
    # filled by the engine:
    output: List[int] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    t_queue: float = 0.0                # seconds waited before prefill
    # eos | length | max_len | cache_full | deadline | rejected |
    # numerics | failed
    finish_reason: Optional[str] = None
    # scheduler-step stamps: deterministic virtual-time analogues of the
    # wall-clock fields, used by the replay bench so its committed
    # baseline doesn't depend on host speed.
    s_submit: Optional[int] = None
    s_first: Optional[int] = None
    s_done: Optional[int] = None
    # robustness bookkeeping (filled by the engine):
    n_preempts: int = 0                 # times evicted + requeued
    n_retries: int = 0                  # transient prefill retries
    served_tier: Optional[str] = None   # DotEngine mode actually served
    degrade_rung: int = 0               # ladder rung actually served
    # engine-internal: effective tier after degradation (a key of the
    # engine's quality_tiers map; None = the request's own tier).
    eff_tier: Optional[str] = None


class ServeEngine:
    def __init__(self, model: Model, params, *, slots: int = 4,
                 max_len: int = 512, greedy: bool = True,
                 dot_mode: Optional[str] = None,
                 dot_tiling: Union[str, Dict[str, object], None] = None,
                 kv_layout: str = "paged",
                 kv_block_size: int = 16,
                 kv_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefill_bucket_min: int = 8,
                 quality_tiers: Optional[Dict[str, str]] = None,
                 max_queue: Optional[int] = None,
                 preempt: bool = True,
                 preempt_limit: int = 8,
                 numerics_check: bool = False,
                 integrity_audit: bool = False,
                 prefill_retries: int = 3,
                 prefill_backoff: int = 1,
                 degrade_ladder: Optional[Sequence[str]] = None,
                 degrade_free_frac: float = 0.25,
                 degrade_queue_headroom: Optional[int] = None,
                 engine: Optional[EngineSpec] = None,
                 mesh=None):
        # Per-deployment numerics override: serve the same checkpoint under
        # any registered DotEngine mode — every configs/olm_array
        # ARRAY_PRECISIONS width ("olm8" .. "olm32") routes decode GEMMs
        # through the fused inner-product array; the n = 24/32 modes
        # transparently use the wide (int64/two-limb) stream decode —
        # without touching the model config; the platform picks the
        # kernel path (kernels/common.model_use_pallas).
        # dot_tiling tunes the olm grid kernel per deployment:
        # the string "auto" (or {"tiling": "auto"}) turns on the
        # shape-aware autotuner so prefill GEMMs and decode GEMVs each
        # get their own (block_m, block_n) output tile — k_tile stays
        # at the numerics default, so auto never changes outputs;
        # explicit k_tile / block_m / block_n pins override it (e.g.
        # widen block_n for the fat decode GEMVs). Params are unchanged
        # — the digit modes quantize at use from the stored dtype.
        # EngineSpec front door: `engine=` is the unified declarative
        # form of the legacy dot_mode/dot_tiling/quality_tiers/
        # degrade_ladder kwargs (core.numerics.EngineSpec), resolved
        # against the model's engine. A user-supplied spec is taken as
        # written — no auto-clearing of block pins; say tiling="auto"
        # with unset blocks to mean "autotune". `mesh=` (or spec.mesh +
        # spec.shard) routes the olm GEMMs through the mesh-sharded
        # shard_map dispatch, tiers included. The legacy kwargs below
        # keep their exact historical semantics but now build an
        # EngineSpec internally — every construction path resolves
        # through core.numerics.resolve_engine.
        if engine is not None:
            if (dot_mode is not None or dot_tiling is not None
                    or quality_tiers is not None
                    or degrade_ladder is not None):
                raise ValueError(
                    "pass either engine= (EngineSpec) or the legacy "
                    "dot_mode/dot_tiling/quality_tiers/degrade_ladder "
                    "kwargs, not both")
            eng = resolve_engine(engine, base=model.eng, mesh=mesh)
            if eng != model.eng:
                model = Model(model.cfg, eng)
            if engine.quality_tiers is not None:
                quality_tiers = dict(engine.quality_tiers)
            if engine.degrade_ladder is not None:
                degrade_ladder = tuple(engine.degrade_ladder)
        else:
            if isinstance(dot_tiling, str):
                if dot_tiling != "auto":
                    raise ValueError(
                        f"unknown dot_tiling {dot_tiling!r}: the only "
                        "string form is 'auto' (or pass a dict of knobs)")
                dot_tiling = {"tiling": "auto"}
            override = dict(dot_tiling or {})
            if bad := set(override) - {"k_tile", "block_m", "block_n",
                                       "tiling"}:
                raise ValueError(f"unknown dot_tiling knobs: {sorted(bad)}")
            if override.get("tiling") == "auto":
                # Asking for the autotuner must actually engage it: clear
                # the block knobs the model's engine had pinned (explicit
                # knobs win over auto inside the engine, so stale static
                # pins would silently turn "auto" into a no-op). Blocks
                # are pure perf, so clearing them is safe; a pinned
                # k_tile is a numerics choice (quantization slice width /
                # tree depth) and survives — auto would supply the same
                # default anyway unless the model builder pinned it
                # deliberately. Knobs passed in this same dot_tiling dict
                # survive too. (An explicit None in the spec means
                # "clear the pin" — EngineSpec's _UNSET sentinel keeps
                # it distinct from "inherit".)
                for knob in ("block_m", "block_n"):
                    override.setdefault(knob, None)
            if dot_mode is not None and dot_mode != model.eng.mode:
                override["mode"] = dot_mode
            if override or mesh is not None:
                eng = resolve_engine(EngineSpec(**override),
                                     base=model.eng, mesh=mesh)
                if eng != model.eng:
                    model = Model(model.cfg, eng)
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.greedy = greedy
        # quality_tiers maps tier name -> DotEngine mode: one checkpoint
        # served at several numerics levels (e.g. {"fast": "olm32t20"}
        # as a truncated throughput tier next to the base olm32).
        # Params are shared — digit modes quantize at use — so a tier is
        # just a Model view with a replaced engine plus its own jitted
        # prefill/decode entry points; the scheduler keeps batches
        # tier-homogeneous (below). Tier None is the base deployment.
        self.quality_tiers = dict(quality_tiers or {})

        # Tier-degradation ladder: rungs 1.. are registered as internal
        # quality tiers keyed by their mode name, so a degraded request
        # rides the existing tier-homogeneous scheduler unchanged and is
        # served exactly as a dedicated deployment at that mode would
        # serve it.
        self.degrade: Optional[DegradeLadder] = None
        if degrade_ladder is not None:
            headroom = (max(1, slots) if degrade_queue_headroom is None
                        else degrade_queue_headroom)
            self.degrade = DegradeLadder.build(
                degrade_ladder, base_mode=model.eng.mode,
                free_frac=degrade_free_frac, queue_headroom=headroom)
            for m in self.degrade.ladder[1:]:
                if self.quality_tiers.setdefault(m, m) != m:
                    raise ValueError(
                        f"degrade_ladder rung {m!r} collides with a "
                        f"quality tier of the same name mapped to mode "
                        f"{self.quality_tiers[m]!r}")
        self._active_tier: Optional[str] = None
        self._tier_models: Dict[Optional[str], Model] = {}
        self._tier_fns: Dict[Optional[str], tuple] = {}

        if max_queue is not None and max_queue < 1:
            raise ValueError("max_queue must be >= 1 (or None: unbounded)")
        if preempt_limit < 1:
            raise ValueError("preempt_limit must be >= 1")
        if prefill_retries < 0 or prefill_backoff < 0:
            raise ValueError("prefill_retries/prefill_backoff must be >= 0")
        self.max_queue = max_queue
        self.preempt = preempt
        self.preempt_limit = preempt_limit
        self.numerics_check = numerics_check
        self.integrity_audit = integrity_audit
        self.prefill_retries = prefill_retries
        self.prefill_backoff = prefill_backoff
        # Robustness event counters (recoveries; terminal finish_reason
        # counts also land here, keyed by the reason string) and the
        # WORK_COUNTERS.
        self.counters: Counter = Counter()
        # Requests shed at submit (finish_reason="rejected"); drained
        # into the done list at the next step()/run() boundary.
        self.shed: Deque[Request] = deque()
        # Fault-injection / instrumentation surfaces (serving/faults.py):
        # logits_tap(lg_np, phase, step) -> lg_np runs host-side on the
        # raw logits; prefill_fault(step, reqs) may raise
        # TransientPrefillError to exercise the retry/backoff path.
        self.logits_tap: Optional[Callable] = None
        self.prefill_fault: Optional[Callable] = None
        self._prefill_backoff_until = 0

        cfg = model.cfg
        kinds = tuple(cfg.block_pattern) + tuple(cfg.remainder_blocks)
        # pow2 prompt bucketing needs right-padding to be harmless; see
        # _PAD_SAFE_KINDS. Sliding-window models are excluded too: a pad
        # tail longer than the window would wrap the ring and overwrite
        # still-in-window positions. Both degrade to exact-length
        # per-request prefill (the pre-bucketing behavior).
        self._bucketed = (all(k in _PAD_SAFE_KINDS for k in kinds)
                          and cfg.sliding_window is None)
        self.prefill_bucket_min = prefill_bucket_min

        if prefill_chunk is not None:
            if not self._bucketed:
                raise ValueError(
                    "prefill_chunk requires an attention-only block "
                    "pattern (recurrent/SSM state can't be chunk-padded)")
            if cfg.sliding_window is not None:
                raise ValueError(
                    "prefill_chunk is not supported with sliding_window "
                    "(ring caches can't take chunked writes)")
            if prefill_chunk < 1 or max_len % prefill_chunk != 0:
                raise ValueError(
                    f"prefill_chunk must divide max_len ({max_len}); "
                    f"got {prefill_chunk}")
        self.prefill_chunk = prefill_chunk

        if kv_layout not in ("paged", "contiguous"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        self.kv_layout = kv_layout
        self.kv_block_size = kv_block_size
        self._table: Optional[np.ndarray] = None
        self._table_dirty = False
        if kv_layout == "paged":
            bs = kv_block_size
            if bs < 1:
                raise ValueError("kv_block_size must be >= 1")
            mbl = -(-max_len // bs)        # blocks per lane at max_len
            self.blocks_per_lane = mbl
            if kv_blocks is None:
                # usable default: every lane can reach half depth at once,
                # and any single lane can reach full max_len (so slots=1
                # engines can never hit cache_full) — plus the trash block
                kv_blocks = 1 + max(mbl, -(-slots * mbl // 2))
            if kv_blocks < 2:
                raise ValueError("kv_blocks must be >= 2 (trash + 1 usable)")
            self.kv_blocks = kv_blocks
            self.cache = model.init_cache(
                slots, max_len,
                paged={"num_blocks": kv_blocks, "block_size": bs})
            # host-side allocator: block ids 1..kv_blocks-1 are usable
            # (0 is the trash block); LIFO free list so tests can observe
            # block reuse deterministically
            self._free: List[int] = list(range(kv_blocks - 1, 0, -1))
            self._owned: Dict[int, List[int]] = {s: [] for s in range(slots)}
            self._table = np.full((slots, mbl), TRASH_BLOCK, np.int32)
            self.blocks_peak_used = 0
            # Integrity shadow state: every usable block is in exactly
            # one of {free, owned-by-one-lane, held}. _owner/_free_set
            # let alloc/free validate ids in O(1) and detect double
            # frees loudly; _held tracks blocks reserved out of the pool
            # (fault injection / future prefix-cache pinning).
            self._owner: Dict[int, int] = {}
            self._free_set = set(self._free)
            self._held: set = set()
        else:
            self.kv_blocks = 0
            self.blocks_per_lane = 0
            self.blocks_peak_used = 0
            self._owner = {}
            self._free_set = set()
            self._held = set()
            self.cache = model.init_cache(slots, max_len)
        self.active: Dict[int, Request] = {}       # slot -> request
        self.pos = np.zeros((slots,), np.int32)
        self.last_tok = np.zeros((slots,), np.int32)
        self.queue: Deque[Request] = deque()
        self.memory = None                          # encdec/vlm stub memory
        self.step_count = 0
        self.pending_chunk: Optional[Dict[str, Any]] = None

        # Compile counters: the wrapped bodies bump the counter at trace
        # time, i.e. exactly once per compiled input signature — this is
        # what the prefill-bucket compile-count test observes.
        self.prefill_traces = 0
        self.decode_traces = 0

        def _make_fns(m: Model):
            def _decode_fn(p, t, ps, c, mem):
                self.decode_traces += 1
                return m.decode_step(p, t, ps, c, mem)

            def _prefill_fn(p, b, c, li):
                self.prefill_traces += 1
                return m.prefill(p, b, c, last_index=li)

            def _chunk_fn(p, b, c, st, li):
                self.prefill_traces += 1
                return m.prefill_chunk(p, b, c, st, last_index=li)

            return (jax.jit(_decode_fn), jax.jit(_prefill_fn),
                    jax.jit(_chunk_fn))

        # Tiers naming the base mode share the base Model and its jitted
        # entry points, so adding a redundant tier costs no compiles.
        by_mode: Dict[str, tuple] = {}
        for tier, mode in ([(None, model.eng.mode)]
                           + sorted(self.quality_tiers.items())):
            if mode not in by_mode:
                m = model if mode == model.eng.mode else Model(
                    model.cfg, dataclasses.replace(model.eng, mode=mode))
                by_mode[mode] = (m, _make_fns(m))
            self._tier_models[tier], self._tier_fns[tier] = by_mode[mode]
        self._scatter = jax.jit(self._scatter_fn)

    # The jitted entry points of whichever tier currently owns the
    # lanes; tier switches only happen in _schedule_prefill while the
    # engine is idle, so every decode batch is tier-homogeneous.
    @property
    def _decode(self):
        return self._tier_fns[self._active_tier][0]

    @property
    def _prefill(self):
        return self._tier_fns[self._active_tier][1]

    @property
    def _prefill_chunk(self):
        return self._tier_fns[self._active_tier][2]

    # ------------- client API -------------
    def submit(self, req: Request):
        P = len(req.prompt)
        if P < 1 or P > self.max_len - 1:
            raise ValueError(
                f"prompt length {P} outside [1, max_len-1={self.max_len - 1}]")
        if req.quality_tier is not None \
                and req.quality_tier not in self.quality_tiers:
            raise ValueError(
                f"unknown quality_tier {req.quality_tier!r}; configured "
                f"tiers: {sorted(self.quality_tiers) or 'none'}")
        if req.deadline_steps is not None and req.deadline_steps < 1:
            raise ValueError(
                f"deadline_steps must be >= 1, got {req.deadline_steps}")
        req.t_submit = time.monotonic()
        req.s_submit = self.step_count
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            # Backpressure: past the hard bound, try re-admitting one
            # ladder rung down (bounded extra headroom); otherwise shed
            # with finish_reason="rejected" — never grow without bound.
            if (self.degrade is not None
                    and len(self.queue)
                    < self.max_queue + self.degrade.queue_headroom
                    and self._downshift(req)):
                self.queue.append(req)
                return True
            self._finish(None, req, "rejected", self.shed)
            return False
        self.queue.append(req)
        return True

    def run(self, *, max_steps: int = 10_000) -> List[Request]:
        done: List[Request] = []
        steps = 0
        while (self.queue or self.active or self.pending_chunk) \
                and steps < max_steps:
            self.step(done)
            steps += 1
        self._drain_shed(done)
        return done

    def step(self, done: List[Request]):
        """One scheduler iteration: advance/admit prefill work, then one
        batched decode step for every active lane. Exposed so drivers
        (the traffic-replay bench) can interleave submissions.

        Under an active profiler each phase is a host span on the
        device's clock: `serve.step` holds `serve.schedule` (with
        `serve.prefill` or `serve.chunk` inside) and `serve.decode`;
        every blocking read of logits is a `serve.sync`."""
        with StepTraceAnnotation("serve.step", step_num=self.step_count):
            self._drain_shed(done)
            if self.integrity_audit and self.kv_layout == "paged":
                self._audit_tables(done)
            with TraceAnnotation("serve.schedule"):
                self._schedule_prefill(done)
            if self.active:
                with TraceAnnotation("serve.decode", lanes=len(self.active)):
                    self._decode_step(done)
            self.step_count += 1

    def _drain_shed(self, done: List[Request]):
        while self.shed:
            done.append(self.shed.popleft())

    # ------------- block allocator (paged layout) -------------
    @property
    def free_blocks(self) -> int:
        return len(self._free) if self.kv_layout == "paged" else 0

    def owned_blocks(self, slot: int) -> List[int]:
        return list(self._owned[slot]) if self.kv_layout == "paged" else []

    def _note_usage(self):
        used = (self.kv_blocks - 1) - len(self._free)
        self.blocks_peak_used = max(self.blocks_peak_used, used)

    def _alloc_blocks(self, slot: int, n: int) -> bool:
        """Give `slot` its next n blocks; all-or-nothing. Every id the
        free list yields is validated (in-range, not currently owned)
        before it can reach a lane table."""
        if len(self._free) < n:
            return False
        for _ in range(n):
            bid = self._free.pop()
            self._free_set.discard(bid)
            if not 1 <= bid < self.kv_blocks or bid in self._owner:
                raise RuntimeError(
                    f"block-allocator integrity: free list yielded block "
                    f"{bid} (usable range [1, {self.kv_blocks}), owner "
                    f"{self._owner.get(bid)!r}) — free list corrupted")
            self._owner[bid] = slot
            j = len(self._owned[slot])
            self._owned[slot].append(bid)
            self._table[slot, j] = bid
        self._table_dirty = True
        self._note_usage()
        return True

    def _free_slot_blocks(self, slot: int):
        owned = self._owned[slot]
        if owned:
            for bid in owned:
                if bid in self._free_set or self._owner.get(bid) != slot:
                    why = ("already in the free list" if bid in self._free_set
                           else f"owned by lane {self._owner.get(bid)!r}")
                    raise RuntimeError(
                        f"double-free: lane {slot} freeing block {bid} "
                        f"which is {why} — allocator state corrupted")
                del self._owner[bid]
            self._free.extend(reversed(owned))
            self._free_set.update(owned)
            self._owned[slot] = []
            self._table[slot, :] = TRASH_BLOCK
            self._table_dirty = True

    def reserve_blocks(self, n: int) -> List[int]:
        """Take up to n blocks out of the free pool (fault injection /
        future prefix-cache pinning); they count as used until
        release_blocks returns them. Returns the reserved ids."""
        if self.kv_layout != "paged":
            raise ValueError("reserve_blocks requires kv_layout='paged'")
        ids: List[int] = []
        for _ in range(min(n, len(self._free))):
            bid = self._free.pop()
            self._free_set.discard(bid)
            self._held.add(bid)
            ids.append(bid)
        self._note_usage()
        return ids

    def release_blocks(self, ids: Sequence[int]):
        """Return blocks taken by reserve_blocks to the free pool."""
        for bid in ids:
            if bid not in self._held:
                raise RuntimeError(
                    f"release_blocks: block {bid} was not reserved")
            self._held.discard(bid)
            self._free.append(bid)
            self._free_set.add(bid)

    def corrupt_table_entry(self, slot: int, j: int, bid: int):
        """FAULT-INJECTION surface: overwrite one host block-table entry
        (and flush it to the device) bypassing the allocator guards,
        simulating table corruption. The integrity audit
        (integrity_audit=True) detects and recovers it."""
        if self.kv_layout != "paged":
            raise ValueError("corrupt_table_entry requires kv_layout='paged'")
        self._table[slot, j] = bid
        self._table_dirty = True
        self._flush_tables()

    def _audit_tables(self, done: List[Request]):
        """Step-boundary integrity audit + recovery: a lane whose table
        row disagrees with the allocator's owned list (foreign or
        out-of-range id, lost entry) is repaired — an active lane is
        preempted and recomputes from its accumulated tokens (which the
        paged slot==position invariant makes bit-identical), an idle
        lane's row is rebuilt from the allocator's truth. Faults inject
        at the step boundary and the audit runs at step start, so a
        corrupted entry is never used for a cache write or read."""
        mbl = self.blocks_per_lane
        for slot in range(self.slots):
            owned = self._owned[slot]
            want = owned + [TRASH_BLOCK] * (mbl - len(owned))
            if list(self._table[slot]) == want:
                continue
            self.counters["table_repairs"] += 1
            req = self.active.get(slot)
            if req is not None:
                self._preempt(slot, req, done)
            else:
                self._table[slot, :] = TRASH_BLOCK
                self._table[slot, :len(owned)] = owned
                self._table_dirty = True

    def _integrity_ok(self) -> bool:
        """Self-check: usable blocks partition into free/owned/held with
        no duplicates, shadow maps agree, and every lane table row is
        its owned list followed by trash padding."""
        if self.kv_layout != "paged":
            return True
        free, held = set(self._free), set(self._held)
        owned_all = [b for blks in self._owned.values() for b in blks]
        owned = set(owned_all)
        if len(free) != len(self._free) or len(owned) != len(owned_all):
            return False  # duplicate ids inside one class
        if (free & owned) or (free & held) or (owned & held):
            return False  # a block in two classes at once
        if free | owned | held != set(range(1, self.kv_blocks)):
            return False  # lost or out-of-range blocks
        if free != self._free_set:
            return False
        if any(self._owner.get(b) != s
               for s, blks in self._owned.items() for b in blks) \
                or len(self._owner) != len(owned):
            return False
        mbl = self.blocks_per_lane
        return all(
            list(self._table[s]) == self._owned[s]
            + [TRASH_BLOCK] * (mbl - len(self._owned[s]))
            for s in range(self.slots))

    def _flush_tables(self):
        """Push the host-side block tables into the device cache pytree.
        Must run before any decode step that follows an alloc/free: a
        freed lane's stale table row would route its idle-lane writes
        into blocks now owned by someone else."""
        if not self._table_dirty:
            return
        t = jnp.asarray(self._table)

        def walk(node):
            if isinstance(node, dict):
                if "kpool" in node:
                    tt = t if node["table"].ndim == 2 else \
                        jnp.broadcast_to(t[None], node["table"].shape)
                    return {**node, "table": tt}
                return {k: walk(v) for k, v in node.items()}
            if isinstance(node, tuple):
                return tuple(walk(v) for v in node)
            if isinstance(node, list):
                return [walk(v) for v in node]
            return node

        self.cache = walk(self.cache)
        self._table_dirty = False

    # ------------- robustness helpers -------------
    def _req_tokens(self, req: Request) -> np.ndarray:
        """Tokens to prefill for a request: the prompt, plus — after a
        preemption — everything it already generated, so the recomputed
        lane resumes at exactly the pre-eviction position (the paged
        slot==position invariant makes the resumed stream
        bit-identical to an uninterrupted run)."""
        if not req.output:
            return np.asarray(req.prompt, np.int32)
        return np.concatenate([np.asarray(req.prompt, np.int32),
                               np.asarray(req.output, np.int32)])

    def _tier_of(self, req: Request) -> Optional[str]:
        """Effective scheduling tier: the degraded tier if the ladder
        downshifted this request, else its own quality_tier."""
        return req.eff_tier if req.eff_tier is not None else req.quality_tier

    def _tier_mode(self, tier: Optional[str]) -> str:
        return self._tier_models[tier].eng.mode

    def _downshift(self, req: Request) -> bool:
        """Move a request one ladder rung down (tracked via eff_tier, a
        mode-named internal quality tier). False at the bottom rung."""
        if self.degrade is None:
            return False
        rung = self.degrade.rung_of(self._tier_mode(self._tier_of(req)))
        nxt = self.degrade.next_mode(rung)
        if nxt is None:
            return False
        req.eff_tier = nxt
        req.degrade_rung = rung + 1
        self.counters["degraded"] += 1
        return True

    def _expired(self, req: Request) -> bool:
        return (req.deadline_steps is not None
                and req.s_submit is not None
                and self.step_count - req.s_submit >= req.deadline_steps)

    def _purge_queue_deadlines(self, done: List[Request]):
        if not any(r.deadline_steps is not None for r in self.queue):
            return
        kept: Deque[Request] = deque()
        for req in self.queue:
            if self._expired(req):
                self._finish(None, req, "deadline", done)
            else:
                kept.append(req)
        self.queue = kept

    def _pick_victim(self) -> Tuple[int, Request]:
        """Deterministic preemption victim among active lanes: lowest
        priority first, then youngest activation, then highest rid."""
        return min(self.active.items(),
                   key=lambda kv: (kv[1].priority,
                                   -(kv[1].s_first or 0), -kv[1].rid))

    def _preempt(self, slot: int, req: Request, done: List[Request]):
        """Evict an active lane: free its paged blocks (trash-resetting
        its table row), requeue it at the head to re-prefill from its
        accumulated tokens. Past preempt_limit the eviction becomes
        terminal (cache_full) to bound ping-pong. Under KV pressure a
        requeued request downshifts one degrade-ladder rung."""
        self.active.pop(slot, None)
        self.pos[slot] = 0
        self.last_tok[slot] = 0
        if self.kv_layout == "paged":
            self._free_slot_blocks(slot)
        if req.n_preempts >= self.preempt_limit:
            self._finish(None, req, "cache_full", done)
            return
        req.n_preempts += 1
        self.counters["preempted"] += 1
        if self.degrade is not None and self.degrade.kv_pressure(
                self.free_blocks, self.kv_blocks - 1):
            self._downshift(req)
        self.queue.appendleft(req)

    # ------------- prefill scheduling -------------
    def _schedule_prefill(self, done: List[Request]):
        self._purge_queue_deadlines(done)
        if self.pending_chunk is not None:
            self._advance_chunk(done)
            return
        if self.step_count < self._prefill_backoff_until:
            return  # backing off after a transient prefill failure
        free = [s for s in range(self.slots) if s not in self.active]
        if not free or not self.queue:
            return
        head = self.queue[0]
        # Tier-homogeneous batching: lanes decode under one tier's
        # jitted step, so a head asking for a different tier waits for
        # the running lanes to drain (strict FIFO — later same-tier
        # requests don't jump it); an idle engine adopts the head's
        # tier for the next wave.
        if self.active and self._tier_of(head) != self._active_tier:
            return
        if not self.active:
            self._active_tier = self._tier_of(head)
        if self.prefill_chunk \
                and len(self._req_tokens(head)) > self.prefill_chunk:
            self._start_chunk(free[0], done)
            return
        batch: List[Tuple[int, Request]] = []
        for slot in free:
            if not self.queue:
                break
            req = self.queue[0]
            if self._tier_of(req) != self._active_tier:
                break  # tier boundary: next wave, after lanes drain
            toks = self._req_tokens(req)
            if self.prefill_chunk and len(toks) > self.prefill_chunk:
                break  # long prompt: chunked on a later step, alone
            if self.kv_layout == "paged":
                need = -(-len(toks) // self.kv_block_size)
                if not self._alloc_blocks(slot, need):
                    if not batch and not self.active \
                            and need > self.kv_blocks - 1:
                        # the whole pool can't hold this prompt even
                        # when idle: it can never be served (transient
                        # shortfalls — reserved blocks, other lanes —
                        # wait instead)
                        self.queue.popleft()
                        self._finish(None, req, "cache_full", done)
                        continue
                    break  # wait for blocks to come back
            self.queue.popleft()
            batch.append((slot, req))
            if not self._bucketed:
                break  # exact-length prefill: one request per call
        if batch:
            self._prefill_batch(batch, done)

    def _prefill_batch(self, batch: List[Tuple[int, Request]],
                       done: List[Request]):
        """One batched GEMM-shaped prefill over up to len(free-slots)
        waiting requests, padded to pow2 (rows, length) buckets."""
        with TraceAnnotation("serve.prefill") as span:
            t_start = time.monotonic()
            if self.prefill_fault is not None:
                try:
                    self.prefill_fault(self.step_count, [r for _, r in batch])
                except TransientPrefillError:
                    self._prefill_retry(batch, done)
                    return
            seqs = [self._req_tokens(r) for _, r in batch]
            lens = [len(s) for s in seqs]
            n = len(batch)
            if self._bucketed:
                Sb = min(_pow2_bucket(max(lens), self.prefill_bucket_min),
                         self.max_len)
                Bp = _pow2_bucket(n)
            else:
                Sb, Bp = max(lens), n
            span.set_metadata(rows=n, rows_computed=Bp, tokens=sum(lens),
                              tokens_computed=Bp * Sb)
            self.counters["prefill_tokens"] += sum(lens)
            self.counters["prefill_tokens_computed"] += Bp * Sb
            tokens = np.zeros((Bp, Sb), np.int32)
            last_idx = np.zeros((Bp,), np.int32)
            slot_ids = np.zeros((Bp,), np.int32)
            valid = np.zeros((Bp,), bool)
            for i, (slot, req) in enumerate(batch):
                tokens[i, :lens[i]] = seqs[i]
                last_idx[i] = lens[i] - 1
                slot_ids[i] = slot
                valid[i] = True
            row_cache = self.model.init_cache(Bp, Sb)
            logits, row_cache, _mem = self._prefill(
                self.params, {"tokens": jnp.asarray(tokens)}, row_cache,
                jnp.asarray(last_idx))
            if self.logits_tap is not None or self.numerics_check:
                lg = _fetch(logits)
                if self.logits_tap is not None:
                    lg = self.logits_tap(lg, "prefill", self.step_count)
                if self.numerics_check:
                    finite = np.isfinite(lg).all(axis=-1)
                    for i, (slot, req) in enumerate(batch):
                        if not finite[i]:
                            # bad row: never scattered, never activated
                            valid[i] = False
                            if self.kv_layout == "paged":
                                self._free_slot_blocks(slot)
                            self._finish(None, req, "numerics", done)
                with np.errstate(invalid="ignore"):
                    toks = lg.argmax(axis=-1).astype(np.int32)
            else:
                toks = _fetch(jnp.argmax(logits, axis=-1), np.int32)
            self._scatter_rows(row_cache, slot_ids, valid, Sb)
            now = time.monotonic()
            for i, (slot, req) in enumerate(batch):
                if not valid[i]:
                    continue  # finished above (numerics)
                req.t_queue = t_start - req.t_submit
                self._activate(slot, req, int(toks[i]), lens[i], now, done)

    def _prefill_retry(self, batch: List[Tuple[int, Request]],
                       done: List[Request]):
        """Transient prefill failure: release the batch's blocks, return
        it to the queue head in arrival order, and back off
        exponentially (prefill_backoff * 2**(attempt-1) steps). A
        request past prefill_retries finishes with reason "failed"."""
        self.counters["prefill_retries"] += 1
        for slot, req in reversed(batch):
            if self.kv_layout == "paged":
                self._free_slot_blocks(slot)
            req.n_retries += 1
            if req.n_retries > self.prefill_retries:
                self._finish(None, req, "failed", done)
            else:
                self.queue.appendleft(req)
        attempt = max(r.n_retries for _, r in batch)
        self._prefill_backoff_until = (
            self.step_count + self.prefill_backoff * (1 << (attempt - 1)))

    def _activate(self, slot: int, req: Request, first_tok: int, P: int,
                  now: float, done: List[Request]):
        req.output.append(first_tok)
        if req.t_first is None:
            # a preempted request's TTFT is its *first* activation
            req.t_first = now
            req.s_first = self.step_count
        req.served_tier = self._tier_mode(self._active_tier)
        self.last_tok[slot] = first_tok
        self.pos[slot] = P
        self.active[slot] = req
        reason = self._finish_reason(req, first_tok, P)
        if reason:
            self._finish(slot, req, reason, done)

    def _scatter_rows(self, row_cache, slot_ids, valid, Sb):
        """Scatter a fresh (Bp, Sb) row cache into the lane pool. Paged
        attention layers take the block route (padding and dummy rows land
        in the trash block); everything else (contiguous k/v, SWA rings,
        recurrent state) is written per-lane with a validity guard."""
        blk_tables = None
        if self.kv_layout == "paged":
            bs = self.kv_block_size
            nb = -(-Sb // bs)
            bt = np.full((len(slot_ids), nb), TRASH_BLOCK, np.int32)
            for i, slot in enumerate(slot_ids):
                if valid[i]:
                    owned = self._owned[int(slot)]
                    take = min(len(owned), nb)
                    bt[i, :take] = owned[:take]
            blk_tables = jnp.asarray(bt)
        self.cache = self._scatter(
            self.cache, row_cache, jnp.asarray(slot_ids),
            jnp.asarray(valid), blk_tables)
        if self.kv_layout == "paged":
            self._flush_tables()

    def _scatter_fn(self, pool_cache, row_cache, slot_ids, valid,
                    blk_tables):
        """Jitted structural scatter of row_cache rows into pool_cache
        lanes. Leaves under {"scan"} carry a leading pattern-group axis
        (batch axis 1), {"rem"} leaves don't (batch axis 0); "len"
        scalars max-combine; paged layers get the block-pool scatter."""
        Bp = slot_ids.shape[0]

        def put(pool, row, axis):
            zero = jnp.zeros((), slot_ids.dtype)
            for i in range(Bp):
                ri = jax.lax.dynamic_slice_in_dim(row, i, 1, axis)
                start = [zero] * pool.ndim
                start[axis] = slot_ids[i]
                cur = jax.lax.dynamic_slice(pool, tuple(start), ri.shape)
                upd = jnp.where(valid[i], ri.astype(pool.dtype), cur)
                pool = jax.lax.dynamic_update_slice(pool, upd, tuple(start))
            return pool

        def walk(pn, rn, stacked):
            if pn is None:
                return None
            if isinstance(pn, dict):
                if "kpool" in pn:
                    f = paged_scatter_rows
                    if stacked:
                        f = jax.vmap(f, in_axes=(0, 0, None))
                    return {"kpool": f(pn["kpool"], rn["k"], blk_tables),
                            "vpool": f(pn["vpool"], rn["v"], blk_tables),
                            "table": pn["table"],
                            "len": jnp.maximum(pn["len"], rn["len"])}
                return {k: (jnp.maximum(pn[k], rn[k]) if k == "len"
                            else walk(pn[k], rn[k], stacked)) for k in pn}
            return put(pn, rn, 1 if stacked else 0)

        return {
            "scan": tuple(walk(a, b, True) for a, b in
                          zip(pool_cache["scan"], row_cache["scan"])),
            "rem": [walk(a, b, False) for a, b in
                    zip(pool_cache["rem"], row_cache["rem"])],
        }

    # ------------- chunked prefill -------------
    def _start_chunk(self, slot: int, done: List[Request]):
        req = self.queue[0]
        seq = self._req_tokens(req)
        P = len(seq)
        chunk = self.prefill_chunk
        nchunks = -(-P // chunk)
        total = nchunks * chunk            # <= max_len: chunk | max_len
        if self.kv_layout == "paged":
            need = -(-P // self.kv_block_size)
            if not self._alloc_blocks(slot, need):
                if not self.active and need > self.kv_blocks - 1:
                    self.queue.popleft()
                    self._finish(None, req, "cache_full", done)
                return
        self.queue.popleft()
        req.t_queue = time.monotonic() - req.t_submit
        self.pending_chunk = {
            "req": req, "slot": slot, "seq": seq,
            "next": 0, "nchunks": nchunks,
            "row_cache": self.model.init_cache(1, total),
        }

    def _abort_chunk(self) -> Dict[str, Any]:
        """Tear down the in-flight chunk state (deadline / transient
        failure), releasing the lane's blocks; nothing was activated or
        scattered yet, so dropping the row cache loses nothing."""
        c = self.pending_chunk
        self.pending_chunk = None
        if self.kv_layout == "paged":
            self._free_slot_blocks(c["slot"])
        return c

    def _advance_chunk(self, done: List[Request]):
        """Run one prompt chunk; decode lanes keep stepping in between."""
        with TraceAnnotation("serve.chunk") as span:
            c = self.pending_chunk
            req, slot, chunk = c["req"], c["slot"], self.prefill_chunk
            if self._expired(req):
                self._abort_chunk()
                self._finish(None, req, "deadline", done)
                return
            if self.prefill_fault is not None:
                try:
                    self.prefill_fault(self.step_count, [req])
                except TransientPrefillError:
                    # restart from chunk 0 after backoff (fresh row cache,
                    # so the retried prefill is deterministic)
                    self._abort_chunk()
                    self._prefill_retry([(slot, req)], done)
                    return
            seq = c["seq"]
            P = len(seq)
            s0 = c["next"] * chunk
            piece = np.zeros((1, chunk), np.int32)
            real = seq[s0:s0 + chunk]
            piece[0, :len(real)] = real
            span.set_metadata(tokens=len(real), tokens_computed=chunk)
            self.counters["prefill_tokens_computed"] += chunk
            is_last = c["next"] == c["nchunks"] - 1
            li = np.asarray([(P - 1 - s0) if is_last else chunk - 1], np.int32)
            logits, c["row_cache"] = self._prefill_chunk(
                self.params, {"tokens": jnp.asarray(piece)}, c["row_cache"],
                jnp.asarray(s0, jnp.int32), jnp.asarray(li))
            c["next"] += 1
            if not is_last:
                return
            # the prompt's real tokens count once, when its last chunk
            # has run: a retry restarts from chunk 0 and recomputes them
            self.counters["prefill_tokens"] += P
            self.pending_chunk = None
            lg = _fetch(logits[0])
            if self.numerics_check and not np.isfinite(lg).all():
                if self.kv_layout == "paged":
                    self._free_slot_blocks(slot)
                self._finish(None, req, "numerics", done)
                return
            self._scatter_rows(c["row_cache"], np.asarray([slot], np.int32),
                               np.asarray([True]), c["nchunks"] * chunk)
            tok = int(lg.argmax())
            self._activate(slot, req, tok, P, time.monotonic(), done)

    # ------------- decode -------------
    def _finish_reason(self, req: Request, tok: int, pos: int
                       ) -> Optional[str]:
        if req.eos_id is not None and tok == req.eos_id:
            return "eos"
        if len(req.output) >= req.max_new_tokens:
            return "length"
        if pos >= self.max_len - 1:
            return "max_len"
        return None

    def _finish(self, slot: Optional[int], req: Request, reason: str,
                done: List[Request]):
        req.finish_reason = reason
        req.t_done = time.monotonic()
        req.s_done = self.step_count
        self.counters[reason] += 1
        done.append(req)
        if slot is not None:
            self.active.pop(slot, None)
            self.pos[slot] = 0
            self.last_tok[slot] = 0
            if self.kv_layout == "paged":
                self._free_slot_blocks(slot)

    def _ensure_decode_blocks(self, done: List[Request]):
        """Pre-step block allocation: a lane about to write position p
        needs block p // bs. When the pool is dry, preempt the
        lowest-priority active lane (possibly the needy lane itself)
        instead of terminating — preempt=False keeps the old terminal
        cache_full behavior."""
        bs = self.kv_block_size
        for slot, req in sorted(self.active.items()):
            if slot not in self.active:
                continue  # preempted earlier in this pass
            while int(self.pos[slot]) // bs >= len(self._owned[slot]):
                if self._alloc_blocks(slot, 1):
                    break
                if not self.preempt:
                    self._finish(slot, req, "cache_full", done)
                    break
                vslot, vreq = self._pick_victim()
                self._preempt(vslot, vreq, done)
                if vslot == slot:
                    break  # the needy lane itself was evicted

    def _decode_step(self, done: List[Request]):
        for slot, req in list(self.active.items()):
            if self._expired(req):
                self._finish(slot, req, "deadline", done)
        if not self.active:
            return
        if self.kv_layout == "paged":
            self._ensure_decode_blocks(done)
            self._flush_tables()
            if not self.active:
                return
        toks = jnp.asarray(self.last_tok)
        pos = jnp.asarray(self.pos)
        logits, self.cache = self._decode(
            self.params, toks, pos, self.cache, self.memory)
        if self.logits_tap is not None or self.numerics_check:
            lg = _fetch(logits)
            if self.logits_tap is not None:
                lg = self.logits_tap(lg, "decode", self.step_count)
            if self.numerics_check:
                finite = np.isfinite(lg).all(axis=-1)
                for slot, req in list(self.active.items()):
                    if not finite[slot]:
                        # the poisoned token is never appended: the
                        # stream stays a clean prefix
                        self._finish(slot, req, "numerics", done)
            with np.errstate(invalid="ignore"):
                nxt = lg.argmax(axis=-1).astype(np.int32)
        else:
            nxt = _fetch(jnp.argmax(logits, axis=-1), np.int32)
        for slot, req in list(self.active.items()):
            t = int(nxt[slot])
            req.output.append(t)
            self.pos[slot] += 1
            self.last_tok[slot] = t
            reason = self._finish_reason(req, t, int(self.pos[slot]))
            if reason:
                self._finish(slot, req, reason, done)

    # ------------- metrics -------------
    @staticmethod
    def latency_report(done: List[Request]) -> ServeReport:
        """Wall-clock latency summary: mean/p50/p99 TTFT and end-to-end,
        queue wait, and aggregate tokens/s over the span of the batch.
        Returns a ServeReport (empty when nothing finished); see
        serving/report.py for the unified key surface and
        ServeReport.collect for the full deployment summary."""
        if not done:
            return ServeReport()

        def pcts(vals):
            if not vals:
                nan = float("nan")
                return nan, nan, nan
            return (float(np.mean(vals)),
                    float(np.percentile(vals, 50)),
                    float(np.percentile(vals, 99)))

        ttft = [r.t_first - r.t_submit for r in done if r.t_first]
        e2e = [r.t_done - r.t_submit for r in done if r.t_done]
        queue = [r.t_queue for r in done]
        ttft_mean, ttft_p50, ttft_p99 = pcts(ttft)
        e2e_mean, e2e_p50, e2e_p99 = pcts(e2e)
        new_tokens = sum(len(r.output) for r in done)
        t0 = min(r.t_submit for r in done)
        t1 = max((r.t_done for r in done if r.t_done), default=t0)
        span = max(t1 - t0, 1e-9)
        return ServeReport({
            "n": len(done),
            "finish_reasons": ServeReport.finish_reasons(done),
            "ttft_mean_s": ttft_mean,
            "ttft_p50_s": ttft_p50,
            "ttft_p99_s": ttft_p99,
            "e2e_mean_s": e2e_mean,
            "e2e_p50_s": e2e_p50,
            "e2e_p99_s": e2e_p99,
            "queue_wait_mean_s": float(np.mean(queue)),
            "new_tokens": new_tokens,
            "tokens_per_s": new_tokens / span,
        })

    def kv_report(self) -> ServeReport:
        """KV residency accounting: bytes actually resident for attention
        K/V storage under the current layout vs what the contiguous
        `slots * max_len` layout would pin. Deterministic (pure shape
        math), so the replay bench baselines it exactly."""
        kv_keys = {"k", "v", "kpool", "vpool"}

        def nbytes(tree) -> int:
            total = 0

            def walk(node):
                nonlocal total
                if isinstance(node, dict):
                    for key, val in node.items():
                        if key in kv_keys:
                            total += int(np.prod(val.shape)) * val.dtype.itemsize
                        else:
                            walk(val)
                elif isinstance(node, (tuple, list)):
                    for val in node:
                        walk(val)

            walk(tree)
            return total

        resident = nbytes(self.cache)
        contiguous = nbytes(jax.eval_shape(
            lambda: self.model.init_cache(self.slots, self.max_len)))
        return ServeReport({
            "kv_layout": self.kv_layout,
            "kv_bytes_resident": resident,
            "kv_bytes_contiguous": contiguous,
            "kv_block_size": self.kv_block_size if self.kv_layout == "paged" else 0,
            "kv_blocks_usable": max(self.kv_blocks - 1, 0),
            "kv_blocks_free": self.free_blocks,
            "kv_blocks_held": len(self._held),
            "kv_blocks_peak_used": self.blocks_peak_used,
            "integrity_ok": self._integrity_ok(),
        })
