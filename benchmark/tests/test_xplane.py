"""xplane.py on the small recorded trace beside it (data/small.xplane.pb,
written by make_small_trace.py, whose docstring works the numbers)."""

import pytest

import make_small_trace
from harness import xplane


def test_committed_trace_is_what_the_generator_writes():
    with open(make_small_trace.PATH, "rb") as f:
        assert f.read() == make_small_trace.build()


def test_reduction_gives_the_known_numbers():
    r = xplane.reduce_file(make_small_trace.PATH)
    us = 1e-6
    assert r["busy_s"] == pytest.approx(6750 * us)
    assert r["window_s"] == pytest.approx(10000 * us)
    assert r["window_from"] == "annotation"
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.325)
    assert r["collective_s"] == pytest.approx(750 * us)
    assert [p["busy_s"] for p in r["planes"]] == pytest.approx([7500 * us, 6000 * us])
    ops = dict(r["device_ops"])
    # self time, averaged over the two planes: the while keeps only its own
    # 1000 us, its body's ops keep theirs; the module line is not read
    assert ops["fusion.3"] == pytest.approx((4000 + 4000) / 2 * us)
    assert ops["fusion.1"] == pytest.approx((1000 + 2000) / 2 * us)
    assert ops["while.2"] == pytest.approx(1000 / 2 * us)
    assert ops["all-reduce.4"] == pytest.approx(1000 / 2 * us)
    assert "jit_step" not in ops
    assert r["device_ops"][0][0] == "fusion.3"
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.update / $reward.py:1 grade"] == pytest.approx(1000 * us)
    assert gaps["bench.update"] == pytest.approx(1000 * us)
    assert r["gaps_total_s"] == pytest.approx(2000 * us)


def test_a_trace_without_a_device_plane_reduces_to_nothing():
    class Plane:
        name, lines = "/host:CPU", []

    class Data:
        planes = [Plane()]

    assert xplane.reduce_profile(Data()) is None


def test_self_times_and_union():
    total, merged = xplane.union_seconds([(0, 10), (5, 20), (30, 40)])
    assert total == pytest.approx(30e-9) and merged == [[0, 20], [30, 40]]
    selfs = xplane.self_times([("a", 0, 100), ("b", 10, 20), ("b", 50, 10)])
    assert selfs == pytest.approx({"a": 70e-9, "b": 30e-9})
