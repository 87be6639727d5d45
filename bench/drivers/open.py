"""Open loop: requests are submitted when due (the traffic file's
Poisson `rate`), whether or not earlier ones have finished. A request's
latency counts from its due time, so a stall delays every later one.

With `warm_s` the arrivals start that many seconds before the window
opens, in set-up, so the window opens on an engine already holding
about as many requests as it holds at that rate, and it continues the
same stream of arrivals."""
from __future__ import annotations

import math
import time
from collections import deque

SETTLE_LIMIT_S = 60.0


def setup(sess, traffic, reqs) -> dict:
    sess.warm_up(traffic["prompt"]["lo"], traffic["prompt"]["hi"])
    st = {"todo": deque(reqs), "t0": None}
    if traffic.get("warm_s"):
        st["t0"] = time.monotonic()
        run_until(sess, st, st["t0"] + traffic["warm_s"])
    return st


def _submit_due(sess, st) -> None:
    now = time.monotonic()
    while st["todo"] and st["t0"] + st["todo"][0]["due"] <= now:
        r = st["todo"].popleft()
        sess.submit(r, st["t0"] + r["due"])


def _turn(sess, st, cap: float = math.inf) -> float:
    """Submit what is due, then step the engine, or, when it is idle,
    wait for the next arrival (but not past `cap`). Returns the time
    the turn ended."""
    _submit_due(sess, st)
    if sess.busy():
        return sess.step()["t1"]
    nxt = st["t0"] + st["todo"][0]["due"] if st["todo"] else cap
    sess.wait_until(min(nxt, cap))
    return time.monotonic()


def run_until(sess, st, end: float) -> float:
    """Serve arrivals until the first turn that ends at or after `end`
    (a step is never cut); returns the time it ended."""
    while True:
        t = _turn(sess, st, cap=end)
        if t >= end:
            return t


def window(sess, st, seconds: float) -> dict:
    open_t = time.monotonic()
    if st["t0"] is None:
        st["t0"] = open_t
    return {"open": open_t, "close": run_until(sess, st, open_t + seconds)}


def settle(sess, st, close: float) -> None:
    """Keep serving, arrivals and all, until every request due in the
    window has its first token (at most a minute past the close)."""
    owed = [r for r in sess.reqs.values() if r["due"] <= close]
    limit = time.monotonic() + SETTLE_LIMIT_S
    while any(not r["token_times"] for r in owed) \
            and time.monotonic() < limit:
        _turn(sess, st)


def more(sess, st, steps: int, prefill: bool = False) -> None:
    """Keep the loop going for `steps` engine steps and, with `prefill`,
    on until one of them has prefilled (at most 4 x `steps`)."""
    first = len(sess.steps)
    while not enough(sess.steps[first:], steps, prefill):
        _turn(sess, st)


def enough(steps: list, want: int, prefill: bool) -> bool:
    """Whether a traced stretch of `steps` is long enough: `want` steps,
    and with `prefill` one that prefilled prompt tokens, unless it has
    reached 4 x `want` steps."""
    if len(steps) >= 4 * want:
        return True
    return len(steps) >= want and (
        not prefill or any(s["prompt_tokens"] for s in steps))
