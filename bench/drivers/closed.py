"""Closed loop: `clients` clients, each sending its next request as soon
as its last one finishes (at a step boundary). With `prefill_in_setup`
the clients' first requests are prefilled during set-up, so the window
only decodes. With `warm_finished` set-up goes on serving until that
many requests have finished, so the window opens on clients at
different points of their requests rather than all at their first
prefill."""
from __future__ import annotations

import time
from collections import deque

from bench.drivers.open import enough

SETTLE_LIMIT_S = 60.0


def _refill(sess, st) -> None:
    now = time.monotonic()
    for c, rid in enumerate(st["clients"]):
        if rid is None or sess.reqs[rid]["req"].finish_reason is not None:
            r = st["todo"].popleft()
            sess.submit(r, now)
            st["clients"][c] = r["rid"]


def finished(sess) -> int:
    return sum(1 for r in sess.reqs.values()
               if r["rid"] >= 0 and r["req"].finish_reason is not None)


def setup(sess, traffic, reqs) -> dict:
    st = {"todo": deque(reqs), "clients": [None] * traffic["clients"]}
    if traffic.get("prefill_in_setup"):
        _refill(sess, st)
        sess.step()            # the prefill and the first decode compile
    else:
        sess.warm_up(traffic["prompt"].get("lo", 1),
                     traffic["prompt"].get("hi", sess.max_len - 1))
    while finished(sess) < traffic.get("warm_finished", 0):
        _refill(sess, st)
        sess.step()
    return st


def window(sess, st, seconds: float) -> dict:
    open_t = time.monotonic()
    while True:
        _refill(sess, st)
        t1 = sess.step()["t1"]
        if t1 >= open_t + seconds:
            return {"open": open_t, "close": t1}


def settle(sess, st, close: float) -> None:
    """Step on, sending nothing new, until every request sent by the
    close has its first token (at most a minute)."""
    limit = time.monotonic() + SETTLE_LIMIT_S
    while any(not r["token_times"] for r in sess.reqs.values()
              if r["rid"] >= 0) and time.monotonic() < limit:
        sess.step()


def more(sess, st, steps: int, prefill: bool = False) -> None:
    """As `drivers.open.more`: `steps` engine steps, and with `prefill`
    on until one of them has prefilled (at most 4 x `steps`)."""
    first = len(sess.steps)
    while not enough(sess.steps[first:], steps, prefill):
        _refill(sess, st)
        sess.step()
