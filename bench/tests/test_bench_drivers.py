"""The loop drivers close the window at a step boundary, never inside a
step, an idle open loop closes at the window's length, and a warm-in in
set-up leaves the window to continue the same traffic."""
import types
from collections import deque

import pytest

from bench.drivers import closed, open as open_loop


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class FakeSession:
    """Steps take `step_s` of the fake clock; a request finishes after
    `lasts` steps (never, by default)."""

    def __init__(self, clock, step_s, lasts=None):
        self.clock, self.step_s, self.lasts = clock, step_s, lasts
        self.reqs, self.step_ends = {}, []

    def submit(self, r, due):
        self.reqs[r["rid"]] = {"rid": r["rid"], "due": due,
                               "token_times": [],
                               "req": types.SimpleNamespace(
                                   finish_reason=None)}

    def busy(self):
        return any(r["req"].finish_reason is None
                   for r in self.reqs.values())

    def step(self):
        t0 = self.clock.t
        self.clock.t += self.step_s
        self.step_ends.append(self.clock.t)
        for r in self.reqs.values():
            if r["req"].finish_reason is None:
                r["token_times"].append(self.clock.t)
                if len(r["token_times"]) == self.lasts:
                    r["req"].finish_reason = "length"
        return {"t0": t0, "t1": self.clock.t}

    def wait_until(self, t):
        self.clock.t = max(self.clock.t, t)


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    for mod in (closed, open_loop):
        monkeypatch.setattr(mod.time, "monotonic", c)
    return c


def test_closed_loop_closes_after_the_step_that_crosses(clock):
    sess = FakeSession(clock, 0.4)
    st = {"todo": deque(
        {"rid": i} for i in range(4)), "clients": [None, None]}
    win = closed.window(sess, st, 1.0)
    assert win == {"open": 0.0, "close": pytest.approx(1.2)}
    assert len(sess.reqs) == 2                  # one request per client


def test_open_loop_submits_when_due_and_closes_on_a_step(clock):
    sess = FakeSession(clock, 0.4)
    st = {"todo": deque(
        [{"rid": 0, "due": 0.3}, {"rid": 1, "due": 5.0}]), "t0": None}
    win = open_loop.window(sess, st, 1.0)
    assert sess.reqs[0]["due"] == pytest.approx(0.3)
    assert 1 not in sess.reqs
    assert win["close"] == pytest.approx(1.1)   # 0.3 + 2 steps of 0.4
    assert win["close"] in sess.step_ends


def test_idle_open_loop_closes_at_its_length(clock):
    sess = FakeSession(clock, 0.4)
    st = {"todo": deque(
        [{"rid": 0, "due": 2.0}]), "t0": None}
    assert open_loop.window(sess, st, 1.0) == {"open": 0.0, "close": 1.0}
    assert sess.step_ends == []


def test_open_loop_warm_in_goes_on_into_the_window(clock):
    sess = FakeSession(clock, 0.5, lasts=3)
    sess.warm_up = lambda lo, hi: None
    reqs = [{"rid": i, "due": 0.7 * i} for i in range(20)]
    traffic = {"prompt": {"lo": 1, "hi": 2}, "warm_s": 3.0}
    st = open_loop.setup(sess, traffic, reqs)
    assert st["t0"] == 0.0 and clock.t >= 3.0
    warm = set(sess.reqs)
    win = open_loop.window(sess, st, 2.0)
    assert win["open"] == clock.t - (win["close"] - win["open"])
    new = set(sess.reqs) - warm
    # the window's arrivals are the stream's next ones, due on its clock
    assert min(new) == max(warm) + 1
    assert all(win["open"] - 0.5 <= sess.reqs[i]["due"] <= win["close"]
               for i in new)


def test_closed_loop_warm_round_finishes_requests_in_setup(clock):
    sess = FakeSession(clock, 0.25, lasts=4)
    sess.warm_up = lambda lo, hi: None
    sess.max_len = 64
    reqs = deque({"rid": i} for i in range(40))
    traffic = {"clients": 3, "prompt": {"lo": 1, "hi": 2},
               "warm_finished": 3}
    st = closed.setup(sess, traffic, list(reqs))
    assert closed.finished(sess) == 3 and len(sess.reqs) == 3
    win = closed.window(sess, st, 1.0)
    # the window refills the clients whose requests finished in set-up
    assert len(sess.reqs) == 6
    assert all(sess.reqs[i]["due"] == win["open"] for i in (3, 4, 5))


@pytest.mark.parametrize("n,prefilled_at,prefill,want", [
    (2, None, False, True),     # enough steps, no prefill asked for
    (2, None, True, False),     # a prefill asked for and none yet
    (3, 2, True, True),         # one of them prefilled
    (8, None, True, True),      # four times the steps: stop looking
    (1, 0, True, False),        # too few steps
])
def test_traced_stretch_goes_on_until_a_prefill(n, prefilled_at, prefill,
                                                want):
    steps = [{"prompt_tokens": 64 if i == prefilled_at else 0}
             for i in range(n)]
    assert open_loop.enough(steps, 2, prefill) is want
