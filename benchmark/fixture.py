"""What the configurations share: the one-vertex topology they run on,
and the counters every whole-run entry returns."""

from __future__ import annotations


def one_vertex_graphml(topo: dict) -> str:
    """GraphML of one vertex whose self-loop every hop takes: latency
    `latency_ms`, node bandwidth `bandwidth_kibps`, lossless."""
    if topo["vertices"] != 1 or float(topo["reliability"]) != 1.0:
        raise ValueError("only the lossless one-vertex fixture is built")
    bw = int(topo["bandwidth_kibps"])
    return f"""<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
  <key attr.name="latency" attr.type="double" for="edge" id="lat" />
  <key attr.name="bandwidthup" attr.type="int" for="node" id="up" />
  <key attr.name="bandwidthdown" attr.type="int" for="node" id="dn" />
  <graph edgedefault="undirected">
    <node id="poi"><data key="up">{bw}</data><data key="dn">{bw}</data>
    </node>
    <edge source="poi" target="poi"><data key="lat">{float(topo["latency_ms"])}</data></edge>
  </graph>
</graphml>"""


def engine_scalars(out) -> dict:
    """The counters the window fetches after each simulation: events,
    windows and micro-steps (EngineStats) and every overflow latch,
    each as the entry returned it. Combining them here would be an op
    queued on the device behind the next simulation, and the fetch
    would wait for that simulation too."""
    sim, stats = out
    return {"events": stats.events_processed, "windows": stats.windows,
            "micro_steps": stats.micro_steps,
            "overflow": (sim.events.overflow, sim.outbox.overflow,
                         sim.net.rq_overflow)}
