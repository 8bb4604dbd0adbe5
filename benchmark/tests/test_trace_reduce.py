"""The reduction from a profiler trace to per-layer numbers, on a small
trace recorded on a TPU v5e (PHOLD at 1,024 hosts, one simulated
second, one simulation in the window; data/phold_small.xplane.pb.gz)
and on hand-made intervals.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmark import kernel_bytes, run, trace_reduce  # noqa: E402

TRACE = pathlib.Path(__file__).resolve().parent / "data" \
    / "phold_small.xplane.pb.gz"


def test_overlapping_ops_count_once():
    assert trace_reduce.union([(0, 10), (5, 15), (20, 30), (30, 31)]) \
        == [(0, 15), (20, 31)]


def test_nested_ops_are_charged_their_self_time():
    evs = [("w", 0, 100), ("a", 10, 20), ("b", 30, 60), ("c", 40, 50),
           ("d", 100, 110)]
    assert dict(trace_reduce.self_times(evs)) == {
        "w": 60, "a": 10, "b": 20, "c": 10, "d": 10}


def test_window_and_gaps_on_made_planes():
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            (run.SPAN_WINDOW, 100.0, 200.0),
            (run.SPAN_FETCH, 140.0, 160.0)]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ("%while.1 = s32[] while(x)", 90.0, 140.0),
            ("%fusion.2 = s32[] fusion(y)", 100.0, 120.0),
            ("%fusion.3 = s32[] fusion(z)", 160.0, 190.0)]}]},
    ]
    r = trace_reduce.reduce_planes(planes, window_span=run.SPAN_WINDOW,
                                   host_spans=run.HOST_SPANS)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(70e-9)       # 100-140, 160-190
    assert r["idle_share"] == pytest.approx(0.3)
    assert r["idle_gaps"] == [[run.SPAN_FETCH, pytest.approx(20e-9)],
                              ["none", pytest.approx(10e-9)]]
    assert r["op_s"]["while.1"][0] == pytest.approx(20e-9)


@pytest.fixture(scope="module")
def chip_trace():
    return trace_reduce.reduce_planes(
        trace_reduce.load_planes(TRACE), window_span=run.SPAN_WINDOW,
        host_spans=run.HOST_SPANS)


def test_chip_trace_busy_is_a_union_inside_the_window(chip_trace):
    r = chip_trace
    assert r["devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 <= r["idle_share"] < 1
    # self times partition the busy time: nothing counted twice
    total = sum(t for t, _ in r["op_s"].values())
    assert total == pytest.approx(r["busy_s"], rel=1e-6)
    tops = [t for _, t in r["top_ops"]]
    assert tops == sorted(tops, reverse=True) and len(tops) <= 10
    labels = {g[0] for g in r["idle_gaps"]}
    assert labels <= set(run.HOST_SPANS) | {"none"}


def test_chip_trace_has_the_mailbox_kernel(chip_trace):
    secs, calls, texts = trace_reduce.kernel_time(chip_trace,
                                                  "mailbox_gather")
    assert secs > 0 and calls >= 1
    assert {kernel_bytes.mailbox_window(t) for t in texts} == {(1024, 32)}
    assert chip_trace["collective_s"] == 0
