"""The traffic generator: seeded, with the same work for every seed."""
import numpy as np
import pytest

from bench import spec, traffic


def chat():
    return spec.load_json(f"{spec.BENCH_DIR}/traffic/chat_native.json")


def test_same_seed_same_requests():
    a = traffic.requests(chat(), 1000, 2**31 + 11, 40)
    b = traffic.requests(chat(), 1000, 2**31 + 11, 40)
    assert all(np.array_equal(x["prompt"], y["prompt"])
               and x["due"] == y["due"] for x, y in zip(a, b))


def test_every_seed_offers_the_same_mix():
    counts = {}
    for seed in (1, 2, 3 * 2**31):
        reqs = traffic.requests(chat(), 1000, seed, 64)
        counts[seed] = (sorted(len(r["prompt"]) for r in reqs),
                        sorted(r["max_new"] for r in reqs),
                        round(reqs[-1]["due"], 9))
    assert len(set(map(str, counts.values()))) == 1


def test_lengths_stay_in_their_clip():
    t = chat()
    for r in traffic.requests(t, 1000, 5, 64):
        assert t["prompt"]["lo"] <= len(r["prompt"]) <= t["prompt"]["hi"]
        assert t["output"]["lo"] <= r["max_new"] <= t["output"]["hi"]


def test_poisson_gaps_have_the_rate():
    t = chat()
    reqs = traffic.requests(t, 1000, 5, 16 * 40)
    mean_gap = reqs[-1]["due"] / (len(reqs) - 1)
    assert mean_gap == pytest.approx(1.0 / t["rate"], rel=0.05)


@pytest.mark.parametrize("dist,expect", [
    ({"dist": "fixed", "value": 7}, [7] * 4),
    ({"dist": "uniform", "lo": 4, "hi": 7}, [4, 5, 6, 7]),
])
def test_quantile_grid(dist, expect):
    assert traffic.quantiles(dist, 4) == expect


def test_same_order_keeps_the_lengths_and_changes_the_tokens():
    t = spec.load_json(f"{spec.BENCH_DIR}/traffic/longdoc_native.json")
    assert t["same_order"]
    a = traffic.requests(t, 1000, 3, 40)
    b = traffic.requests(t, 1000, 2**31 + 3, 40)
    assert [(len(r["prompt"]), r["max_new"]) for r in a] == \
        [(len(r["prompt"]), r["max_new"]) for r in b]
    assert not np.array_equal(a[0]["prompt"], b[0]["prompt"])
