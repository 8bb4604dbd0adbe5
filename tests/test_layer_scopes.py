"""The window step's layers named on the device program, and the
bulk-commit counter.

Each layer runs under a jax.named_scope (shadow_window, shadow_bulk,
shadow_serial, shadow_route with its steps, shadow_barrier,
shadow_exchange), so a profile of the compiled program splits device
time by layer (docs/3-observability.md). EngineStats.bulk_events
counts the events the bulk window pass committed; the serial fixpoint
committed the rest of events_processed. The run manifest and the
Prometheus file carry it, and the lint holds it to at most
events_processed.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import load_tool
from jax.sharding import Mesh

from shadow_tpu.apps import phold
from shadow_tpu.core import simtime
from shadow_tpu.core.engine import EngineStats
from shadow_tpu.net.build import HostSpec, build, make_runner
from shadow_tpu.net.state import NetConfig

GRAPH = """<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
  <key attr.name="latency" attr.type="double" for="edge" id="lat" />
  <key attr.name="bandwidthup" attr.type="int" for="node" id="up" />
  <key attr.name="bandwidthdown" attr.type="int" for="node" id="dn" />
  <graph edgedefault="undirected">
    <node id="v0"><data key="up">102400</data><data key="dn">102400</data>
    </node>
    <edge source="v0" target="v0"><data key="lat">50.0</data></edge>
  </graph>
</graphml>"""

H, LOAD, SEED = 16, 3, 5
# The insert's steps on the CPU path (events._insert_impl: "sort");
# sweep and mailbox are the accelerator's select sweep
# (tests/test_chip_compile.py).
CPU_ROUTE_STEPS = ("sort", "permute", "count", "scatter")


def _build(hosts=H, load=LOAD):
    cap = max(32, 4 * load)
    cfg = NetConfig(num_hosts=hosts, tcp=False, end_time=simtime.ONE_SECOND,
                    seed=SEED, event_capacity=cap, outbox_capacity=cap,
                    router_ring=cap, in_ring=max(8, 2 * load))
    hosts_ = [HostSpec(name=f"peer{i}", proc_start_time=0)
              for i in range(hosts)]
    b = build(cfg, GRAPH, hosts_)
    b.sim = phold.setup(b.sim, load=load)
    return b


def _op_paths(lowered) -> set[str]:
    """The op_name paths in a lowered program's locations (a shard_map
    body's are relative to it); file names left out."""
    text = lowered.as_text(debug_info=True)
    return {p for p in re.findall(r'loc\("([^"]*)"', text)
            if "/" in p and not p.endswith(".py")}


def _in_scope(paths, scope: str, step: str | None = None) -> bool:
    for p in paths:
        names = p.split("/")[:-1]
        if scope in names:
            rest = names[names.index(scope) + 1:]
            if step is None or step in rest:
                return True
    return False


@pytest.fixture(scope="module")
def whole_run_paths():
    b = _build(hosts=64, load=2)
    runner = make_runner(b, app_handlers=(phold.handler,),
                         app_bulk=phold.BULK)
    return _op_paths(runner.lower(b.sim))


@pytest.mark.parametrize("scope", ["shadow_window", "shadow_bulk",
                                   "shadow_serial", "shadow_barrier"])
def test_whole_run_names_the_layer(whole_run_paths, scope):
    assert _in_scope(whole_run_paths, scope), scope


@pytest.mark.parametrize("step", CPU_ROUTE_STEPS)
def test_whole_run_names_the_route_step(whole_run_paths, step):
    assert _in_scope(whole_run_paths, "shadow_route", step), step


def test_barrier_sits_in_the_window_layer(whole_run_paths):
    assert any("shadow_window/shadow_barrier/" in p
               for p in whole_run_paths)


def test_sharded_window_names_the_exchange():
    from shadow_tpu.net.bulk import make_bulk_fn
    from shadow_tpu.net.step import make_step_fn
    from shadow_tpu.parallel.shard import make_sharded_window

    b = _build()
    mesh = Mesh(np.array(jax.devices()[:4]), ("hosts",))
    fn = make_sharded_window(
        mesh, "hosts", b.sim, b.cfg, make_step_fn(b.cfg, (phold.handler,)),
        bulk_fn=make_bulk_fn(b.cfg, phold.BULK))
    t = simtime.DTYPE
    paths = _op_paths(fn.lower(b.sim, jnp.asarray(0, t),
                               jnp.asarray(50_000_000, t)))
    assert _in_scope(paths, "shadow_exchange")
    # the exchange sits inside the route, whose insert keeps its steps
    assert _in_scope(paths, "shadow_route", "shadow_exchange")
    assert _in_scope(paths, "shadow_route", "sort")


@pytest.fixture(scope="module")
def serial_and_bulk():
    b1 = _build()
    _, serial = make_runner(b1, app_handlers=(phold.handler,))(b1.sim)
    b2 = _build()
    sim, bulked = make_runner(b2, app_handlers=(phold.handler,),
                              app_bulk=phold.BULK)(b2.sim)
    return serial.as_dict(), bulked.as_dict(), (b2.cfg, sim, bulked)


def test_bulk_events_and_serial_events_sum_to_events_processed(
        serial_and_bulk):
    serial, bulked, _ = serial_and_bulk
    assert bulked["events_processed"] == serial["events_processed"]
    assert 0 < bulked["bulk_events"] < bulked["events_processed"]
    # PHOLD's time-0 round runs serially, one event per host per
    # micro-step (PROC_START, then the chained injections); the bulk
    # pass commits every later window
    assert bulked["micro_steps"] == LOAD
    assert (bulked["events_processed"] - bulked["bulk_events"]
            == bulked["micro_steps"] * H)


def test_bulk_events_zero_without_bulk_pass(serial_and_bulk):
    serial, _, _ = serial_and_bulk
    assert serial["bulk_events"] == 0
    assert serial["events_processed"] > 0


def test_sharded_bulk_events_equal_serial(serial_and_bulk):
    from shadow_tpu.parallel import run_sharded

    _, bulked, _ = serial_and_bulk
    b = _build()
    mesh = Mesh(np.array(jax.devices()[:4]), ("hosts",))
    _, st = run_sharded(b, mesh, "hosts", app_handlers=(phold.handler,),
                        app_bulk=phold.BULK)
    got = st.as_dict()
    for k in ("bulk_events", "events_processed", "windows"):
        assert got[k] == bulked[k], k


def test_manifest_and_metrics_carry_bulk_events(serial_and_bulk):
    from shadow_tpu.telemetry.export import prometheus_text, run_manifest

    _, bulked, (cfg, sim, stats) = serial_and_bulk
    man = run_manifest(cfg=cfg, seed=SEED, shards=1, sim=sim, stats=stats)
    ctr = man["counters"]
    assert ctr["bulk_events"] == bulked["bulk_events"]
    assert ctr["events_processed"] == bulked["events_processed"]
    assert (f"shadow_tpu_bulk_events {bulked['bulk_events']}\n"
            in prometheus_text(ctr))
    errors, _ = load_tool("telemetry_lint").lint_manifest_obj(man)
    assert not errors, errors


@pytest.mark.parametrize("bad", ["over", -1, "7"])
def test_lint_rejects_bad_bulk_events(serial_and_bulk, bad):
    from shadow_tpu.telemetry.export import run_manifest

    _, _, (cfg, sim, stats) = serial_and_bulk
    man = run_manifest(cfg=cfg, seed=SEED, shards=1, sim=sim, stats=stats)
    ctr = man["counters"]
    ctr["bulk_events"] = ctr["events_processed"] + 1 if bad == "over" else bad
    errors, _ = load_tool("telemetry_lint").lint_manifest_obj(man)
    assert any("bulk_events" in e for e in errors), errors


def test_engine_stats_carry_bulk_events():
    d = {"events_processed": 10, "micro_steps": 2, "windows": 3,
         "fastpath_hit": 0, "fastpath_miss": 0, "bulk_events": 7}
    s = EngineStats.from_dict(d)
    assert s.as_dict() == d
    assert s.add(s).as_dict()["bulk_events"] == 14
    # a record written before the counter existed reads 0
    old = {k: v for k, v in d.items() if k != "bulk_events"}
    assert EngineStats.from_dict(old).as_dict()["bulk_events"] == 0
