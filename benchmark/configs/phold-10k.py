"""phold-10k: PHOLD over 10,240 hosts on the one-vertex 50 ms fixture,
UDP, through the simulator's whole-run entry (net.build.make_runner).

The build is bench.py's `_build_phold`, copied: the deployment's
sizes come from configs/phold-10k.json and the traffic file, and
one build serves every seed, since the seed reaches a run only
through the hosts' random streams (net.state: rng_keys).
"""

from __future__ import annotations

import numpy as np

from benchmark import fixture

NS_PER_S = 1_000_000_000
# The queue's empty-slot time (core/simtime.INVALID).
EMPTY = np.iinfo(np.int64).max
# The largest of these that a sound run may read: the comparison is
# exact.
LIMITS = {"hosts_off": 0, "events_off": 0}


class Deployment:
    def __init__(self, config: dict, traffic: dict, chips: int):
        if chips != 1:
            raise ValueError("phold-10k runs on one chip")
        if traffic["kind"] != "phold":
            raise ValueError(f"phold-10k cannot run {traffic['kind']} traffic")
        from shadow_tpu.apps import phold
        from shadow_tpu.compile import specialize
        from shadow_tpu.net.build import HostSpec, build, make_runner
        from shadow_tpu.net.state import NetConfig

        self.hosts = H = int(config["hosts"])
        self.load = int(traffic["load"])
        self.sim_seconds = float(traffic["sim_seconds"])
        self.end_ns = int(traffic["sim_seconds"]) * NS_PER_S
        self.latency_ns = int(config["topology"]["latency_ms"]) * 1_000_000
        start_ns = int(round(float(traffic["start_s"]) * NS_PER_S))
        if start_ns != 0:
            raise ValueError("the PHOLD reference starts every host at 0")
        cfg = NetConfig(num_hosts=H, tcp=False, end_time=self.end_ns,
                        seed=0,
                        event_capacity=int(config["event_capacity"]),
                        outbox_capacity=int(config["outbox_capacity"]),
                        router_ring=int(config["router_ring"]),
                        in_ring=int(config["in_ring"]))
        hosts = [HostSpec(name=f"peer{i}", proc_start_time=start_ns)
                 for i in range(H)]
        b = build(cfg, fixture.one_vertex_graphml(config["topology"]), hosts)
        b.sim = phold.setup(b.sim, load=self.load)
        kw = dict(app_handlers=(phold.handler,), app_bulk=phold.BULK)
        b = specialize.apply(b, mode="auto", **kw)
        self.bundle = b
        self.run = make_runner(b, **kw)
        self.shapes = {"event_words": int(b.sim.events.words.shape[2])}

    def input(self, seed: int):
        """The built simulation with the hosts' streams of `seed`."""
        from shadow_tpu.core import rng

        sim = self.bundle.sim
        return sim.replace(net=sim.net.replace(
            rng_keys=rng.host_streams(seed, self.hosts)))

    scalars = staticmethod(fixture.engine_scalars)

    @staticmethod
    def kept(out):
        """What the check reads of one simulation's final state."""
        sim, _ = out
        return {"rcvd": sim.app.rcvd, "sent": sim.app.sent,
                "rng_ctr": sim.net.rng_ctr, "time": sim.events.time}

    def observe(self, kept: dict, scalars: dict) -> dict:
        """The program's final state in the reference's terms."""
        t = np.asarray(kept["time"])
        due = (self.end_ns // self.latency_ns + 1) * self.latency_ns
        return {"rcvd": np.asarray(kept["rcvd"]),
                "sent": np.asarray(kept["sent"]),
                "rng_ctr": np.asarray(kept["rng_ctr"]),
                # in flight at the end: every one due at the next beat
                "pending": (t != EMPTY).sum(axis=1),
                "pending_due": (t == due).sum(axis=1),
                "events": int(scalars["events"])}

    def expected(self, seed: int, control: bool = False) -> dict:
        """The plain reference's final state; `control` loses one
        message halfway through (the lossless guarantee broken)."""
        from benchmark.references import phold as ref

        rounds = self.end_ns // self.latency_ns
        out = ref.run(seed, hosts=self.hosts, load=self.load,
                      end_ns=self.end_ns, latency_ns=self.latency_ns,
                      lose_one_in_round=rounds // 2 if control else -1)
        out["pending_due"] = out["pending"]
        return out

    @staticmethod
    def complete(got: dict) -> bool:
        """Every host received."""
        return bool((got["rcvd"] > 0).all())

    @staticmethod
    def compare(got: dict, exp: dict) -> dict:
        """Hosts whose state differs from the reference anywhere, and
        the gap in events committed."""
        off = np.zeros(exp["rcvd"].shape, bool)
        for k in ("rcvd", "sent", "rng_ctr", "pending", "pending_due"):
            off |= np.asarray(got[k]).astype(np.int64) \
                != np.asarray(exp[k]).astype(np.int64)
        return {"hosts_off": int(off.sum()),
                "events_off": abs(int(got["events"]) - int(exp["events"]))}


def prepare(config: dict, traffic: dict, chips: int) -> Deployment:
    return Deployment(config, traffic, chips)
