"""Device time by layer scope (trace_layers): the charging rule on
made planes, and the protobuf reader and the reduction on traces
recorded on a TPU v5e (PHOLD at 1,024 hosts, one simulated second,
one simulation in the window): data/phold_small.xplane.pb.gz from a
program without layer scopes, data/phold_small_scoped.xplane.pb.gz
from one with them.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import gzip
import pathlib
import shutil
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmark import run, trace_layers, trace_reduce  # noqa: E402
from benchmark.metrics import (  # noqa: E402
    bulk_ms_per_window,
    route_ms_per_window,
    serial_ms_per_window,
)

DATA = pathlib.Path(__file__).resolve().parent / "data"
UNSCOPED_TRACE = DATA / "phold_small.xplane.pb.gz"
SCOPED_TRACE = DATA / "phold_small_scoped.xplane.pb.gz"
TOP_LAYERS = ("shadow_window", "shadow_bulk", "shadow_serial",
              "shadow_route", "shadow_barrier")


@pytest.mark.parametrize("path,layer", [
    ("jit(_go)/while/body/shadow_bulk/add", "shadow_bulk"),
    ("jit(_go)/while/body/shadow_window/shadow_barrier/reduce_min",
     "shadow_barrier"),
    ("jit(_go)/while/body/shadow_route/cond/branch_1_fun/sort/sort",
     "shadow_route/sort"),
    ("jit(_go)/while/body/shadow_route/cond/branch_1_fun/mailbox/"
     "jit(mailbox_gather)/pallas_call", "shadow_route/mailbox"),
    # a primitive that shares a step's name is no scope
    ("jit(_go)/while/body/shadow_route/cond/branch_0_fun/scatter",
     "shadow_route"),
    ("jit(_go)/while/body/shadow_route/shadow_exchange/all_to_all",
     "shadow_exchange"),
    ("jit(_go)/while", "other"),
    ("sim.net.in_src_ip:", "other"),
    ("", "unscoped"),
])
def test_layer_of(path, layer):
    assert trace_layers.layer_of(path) == layer


def _made_planes():
    paths = {1: "jit(_go)/while",
             2: "jit(_go)/while/body/shadow_bulk/fusion",
             3: "jit(_go)/while/body/shadow_route/permute/gather",
             4: ""}
    names = {0: run.SPAN_WINDOW, 1: "%while.1 = s32[] while(x)",
             2: "%fusion.2 = s32[] fusion(y)",
             3: "%fusion.3 = s32[] fusion(z)", 4: "%copy.4 = s32[] copy(w)"}
    host = {"name": "/host:CPU", "names": names, "paths": {},
            "lines": [{"name": "python", "events": [(0, 100.0, 200.0)]}]}
    dev = {"name": "/device:TPU:0", "names": names, "paths": paths,
           "lines": [{"name": trace_reduce.OPS_LINE, "events": [
               (1, 90.0, 150.0), (2, 100.0, 120.0), (3, 130.0, 140.0),
               (4, 160.0, 210.0)]}]}
    return [host, dev]


def test_layers_charge_self_time_and_partition_busy_time():
    planes = _made_planes()
    r = trace_layers.reduce_layers(planes, window_span=run.SPAN_WINDOW)
    assert r["layer_s"] == pytest.approx({
        "other": 20e-9,                 # the loop less its two ops
        "shadow_bulk": 20e-9,
        "shadow_route/permute": 10e-9,
        "unscoped": 40e-9})             # clipped at the window's end
    # the same events as trace_reduce reads them, by instruction text
    as_read = [{"name": p["name"], "lines": [
        {"name": ln["name"],
         "events": [(p["names"][m], a, b) for m, a, b in ln["events"]]}
        for ln in p["lines"]]} for p in planes]
    busy = trace_reduce.reduce_planes(as_read,
                                      window_span=run.SPAN_WINDOW)["busy_s"]
    assert r["busy_s"] == pytest.approx(busy)
    assert r["top"]["unscoped"] == [["copy.4", pytest.approx(40e-9)]]
    assert trace_layers.layer_total(r["layer_s"], "shadow_route") \
        == pytest.approx(10e-9)


@pytest.fixture(scope="module", params=[UNSCOPED_TRACE, SCOPED_TRACE],
                ids=["unscoped", "scoped"])
def chip_trace(request):
    if not request.param.exists():
        pytest.skip(f"{request.param.name} not recorded")
    return request.param


def test_wire_reader_sees_what_profile_data_sees(chip_trace):
    mine = trace_layers.load_planes(chip_trace)
    ref = trace_reduce.load_planes(chip_trace)
    assert [p["name"] for p in mine] == [p["name"] for p in ref]
    for pm, pr in zip(mine, ref):
        assert [ln["name"] for ln in pm["lines"]] \
            == [ln["name"] for ln in pr["lines"]]
        for lm, lr in zip(pm["lines"], pr["lines"]):
            assert [(pm["names"][m], a, b) for m, a, b in lm["events"]] \
                == lr["events"]


def test_chip_trace_layers_partition_busy_time(chip_trace):
    r = trace_layers.reduce_layers(trace_layers.load_planes(chip_trace),
                                   window_span=run.SPAN_WINDOW)
    busy = trace_reduce.reduce_planes(
        trace_reduce.load_planes(chip_trace),
        window_span=run.SPAN_WINDOW)["busy_s"]
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(busy, rel=1e-6)
    scoped = {k for k in r["layer_s"]
              if k not in (trace_layers.OTHER, trace_layers.UNSCOPED)}
    if chip_trace == UNSCOPED_TRACE:
        assert not scoped
        return
    for layer in TOP_LAYERS:
        assert trace_layers.layer_total(r["layer_s"], layer) > 0, layer
    for step in ("sort", "permute", "count", "sweep", "mailbox"):
        assert r["layer_s"].get(f"shadow_route/{step}", 0) > 0, step
    assert r["layer_s"].get(trace_layers.OTHER, 0) < 0.02 * r["busy_s"]


@pytest.fixture
def traced(tmp_path, monkeypatch, chip_trace):
    """The benchmark's trace directory holding `chip_trace`, and the
    record its traced run would give the metric readers."""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(chip_trace) as f, open(d / "t.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path)
    busy = trace_reduce.reduce(tmp_path, window_span=run.SPAN_WINDOW)
    return {"trace": busy, "totals": {"windows": 21}}


def test_readers(traced, chip_trace):
    readers = (route_ms_per_window, bulk_ms_per_window, serial_ms_per_window)
    got = [m.read(traced) for m in readers]
    if chip_trace == UNSCOPED_TRACE:
        assert got == [None, None, None]     # a program without scopes
        return
    r = trace_layers.for_record(traced)
    for value, layer in zip(got, ("shadow_route", "shadow_bulk",
                                  "shadow_serial")):
        assert value == pytest.approx(
            1e3 * trace_layers.layer_total(r["layer_s"], layer) / 21)
        assert value > 0


def test_reader_refuses_a_trace_that_is_not_the_records(traced):
    traced["trace"] = dict(traced["trace"],
                           busy_s=2 * traced["trace"]["busy_s"])
    with pytest.raises(ValueError, match="busy time"):
        route_ms_per_window.read(traced)
