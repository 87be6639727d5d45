"""The trace reduction on a small synthetic trace."""
import pytest

from bench import trace as tr


def test_union_merges_overlaps_and_touching():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                             (3, 4)]


def test_covered_clips_to_window():
    busy = [(0, 1), (2, 4)]
    assert tr.covered(busy, 0.5, 3) == pytest.approx(1.5)
    assert tr.covered(busy, 4, 5) == 0


@pytest.fixture
def synthetic():
    ops = {"/device:TPU:0": [("fusion.1", 0.0, 1.0), ("fusion.1", 0.5, 1.5),
                             ("olm_matmul_fused_pallas.1", 2.0, 3.0),
                             ("fusion.2", 3.0, 3.5), ("late", 9.0, 9.5)]}
    modules = [("jit__decode_fn(1)", 0.0, 1.5),
               ("jit__decode_fn(1)", 2.0, 3.5),
               ("jit__chunk_fn(7)", 3.5, 3.5)]
    spans = [("bench.step", 0.0, 1.6), ("bench.submit", 1.6, 1.95),
             ("bench.step", 1.95, 4.0)]
    return ops, modules, spans


def test_reduce_busy_idle_and_names(synthetic):
    red = tr.reduce(*synthetic)
    assert (red["t0"], red["t1"]) == (0.0, 4.0)
    assert red["window_s"] == 4.0
    assert red["busy_s"] == pytest.approx(1.5 + 1.5)   # late op excluded
    assert red["ops"]["fusion.1"] == [2, pytest.approx(2.0)]
    assert "late" not in red["ops"]
    assert tr.module_time(red, "_decode_fn") == (2, pytest.approx(3.0))
    assert tr.op_time(red, "olm_matmul_fused") == (1, pytest.approx(1.0))


def test_idle_gaps_longest_first_and_labelled(synthetic):
    red = tr.reduce(*synthetic)
    names = [n for n, _ in red["gaps"]]
    secs = [s for _, s in red["gaps"]]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) == pytest.approx(4.0 - 3.0)
    # the gap 1.5-2.0 falls mostly in the submit span
    assert ("bench.submit", pytest.approx(0.5)) in [
        (n, s) for n, s in red["gaps"]]
    assert names.count("bench.step") == 1                 # 3.5-4.0


def test_breakdown_lists_at_most_ten(synthetic):
    red = tr.reduce(*synthetic)
    b = tr.breakdown(red)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(2.0)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_reduce_refuses_a_trace_without_steps():
    with pytest.raises(ValueError):
        tr.reduce({"/device:TPU:0": [("x", 0, 1)]}, [], [])
