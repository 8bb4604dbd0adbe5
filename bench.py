"""Benchmark: events/sec/chip on the flagship workload.

Default workload is PHOLD (the PDES-scheduler stress benchmark the
reference also uses, src/test/phold/): every host keeps `load`
messages circulating, so all lanes stay busy and the committed-events
rate measures raw engine throughput. Env knobs:

  BENCH_WORKLOAD=phold|pingpong   workload shape (BASELINE.json)
  BENCH_HOSTS=N                   host count (default 10240 on TPU)
  BENCH_SIM_SECONDS=N             simulated seconds (default 5)
  BENCH_LOAD=N                    PHOLD messages per host (default 8)
  BENCH_PLATFORM=cpu              run on the CPU backend. Without it
                                  the run needs an accelerator and
                                  fails when JAX finds none
  BENCH_SHARDS=N                  run under shard_map over an N-device
                                  mesh (CPU: N virtual devices are
                                  forced; accelerator: needs N chips)
  BENCH_REPLICAS=R                ensemble mode: R independent
                                  replicas of the H-host sim in one
                                  device program (aggregate ev/s)
  BENCH_TOPO=one|ref|mix          'ref' = the reference's real
                                  183-vertex Internet graph instead of
                                  the single-vertex 50 ms fixture;
                                  'mix' = the 3-vertex heterogeneous
                                  ~1-3 ms fixture (MIX_VERTICES) whose
                                  dense event times make the
                                  small-window dispatch-bound shape
  BENCH_FAULTS=plan.json          same as --faults: run the workload
                                  on a degraded network (injected
                                  loss / flaps / latency spikes; see
                                  examples/faultplan_degraded.json)
  BENCH_TELEMETRY=0               disable the window telemetry ring
                                  for the phold runs (default on; the
                                  ring rides the timed program, so
                                  on-vs-off is the honest overhead
                                  comparison — acceptance: <2%)
  BENCH_FLOW_SAMPLE=N             attach the per-flow latency ring
                                  (telemetry/flows.py) to the timed
                                  program: deterministic 1-in-N packet
                                  sampling at the window barrier. The
                                  row grows a "flows" block (sampled/
                                  harvested counts + per-lane latency)
  BENCH_FLOW_OVERHEAD=1           A/B the flow ring's cost: rebuild
                                  the SAME workload without the ring,
                                  time it, and record
                                  flow_overhead_pct = (off-on)/off —
                                  acceptance: <=5% at the default
                                  1-in-64 sampling (requires
                                  BENCH_FLOW_SAMPLE)
  BENCH_CAUSALITY=N               attach the causal lineage recorder
                                  (telemetry/causality.py) to the
                                  timed program: deterministic 1-in-N
                                  event sampling plus per-window
                                  advance attribution. The row grows a
                                  "causality" block (sampled/harvested
                                  counts + binding-cause histogram)
                                  and the embedded manifest carries
                                  the full block for tools/critpath.py
  BENCH_CAUSALITY_OVERHEAD=1      A/B the lineage recorder's cost:
                                  rebuild the SAME workload without
                                  the causality planes, time it, and
                                  record causality_overhead_pct =
                                  (off-on)/off — acceptance: <=5% at
                                  the default 1-in-64 sampling
                                  (requires BENCH_CAUSALITY; gated by
                                  tools/bench_regress.py)
  BENCH_SENTINEL=1                attach the cross-shard integrity
                                  sentinel (parallel/elastic.py) to
                                  the timed program: per-barrier
                                  replicated-state digest + pmax/pmin
                                  compare. The row gains a "sentinel"
                                  block (checks/trips/verified
                                  frontier) and banks under its own
                                  _sentinel metric name
  BENCH_SENTINEL_OVERHEAD=1       A/B the sentinel's cost: rebuild
                                  the SAME workload with the sentinel
                                  detached, time it, and record
                                  sentinel_overhead_pct = (off-on)/off
                                  — acceptance: <5% (design goal <2%);
                                  gated by tools/bench_regress.py
                                  (requires BENCH_SENTINEL=1)
  BENCH_PROFILE_DIR=path          capture a jax.profiler trace of one
                                  EXTRA (unscored) run after the timed
                                  one — tracing costs wall time, so it
                                  must never touch the scored number;
                                  the row records {"profile": {"dir":
                                  ...}} so the artifact is discoverable
  BENCH_ACTIVE=N                  sparse PHOLD shape: only the first N
                                  hosts inject load (phold.setup
                                  active_hosts) — the census/compaction
                                  benchmark geometry. Disables the bulk
                                  pass (bulk consumes whole windows
                                  before the fixpoint, which would
                                  starve the fast path being measured).
  BENCH_SPARSE_LANES=S            compact-lane budget (cfg.sparse_lanes;
                                  unset = engine default 256, 0 =
                                  fast path off — the A/B lever for
                                  the sparse-window speedup claim)
  BENCH_SPECIALIZE=1              compile-time specialization A/B
                                  (compile/specialize.py): the timed
                                  program is the capability-trimmed
                                  variant (the metric name gains
                                  _spec so the row banks separately)
                                  and an unspecialized twin of the
                                  same workload is timed for the
                                  specialize_speedup field =
                                  rate_trimmed / rate_full. Plain
                                  PHOLD runner only.
  BENCH_SUPERVISE=1               route PHOLD through the supervised
                                  host-driven window loop
                                  (faults.run_supervised) instead of
                                  the all-on-device engine.run — the
                                  dispatch-amortization A/B subject
  BENCH_CHUNK_WINDOWS=K           windows_per_dispatch for the
                                  supervised loop (K windows per host
                                  barrier; requires BENCH_SUPERVISE=1)
  BENCH_ADAPTIVE_JUMP=1           live-table window span instead of
                                  the static min_jump (requires
                                  BENCH_SUPERVISE=1)
  BENCH_MIN_JUMP_MS=M             LOWER the window span to M ms (only
                                  lowers — a raise would break the
                                  conservative window invariant): the
                                  small-window shape that makes
                                  per-dispatch overhead dominate.
                                  Scenario knob — applies to both the
                                  supervised loop and engine.run
  BENCH_CHECKPOINT_WINDOWS=N      supervised checkpoint cadence in
                                  windows (default: effectively never,
                                  so the timed loop measures dispatch,
                                  not npz writes)
  BENCH_INJECT_TRACE=path         open-system injection scenario:
                                  replay this trace file
                                  (inject/trace.py format; see
                                  tools/trace_gen.py) into a tgen-app
                                  run through the supervised window
                                  loop — measures the streamed
                                  host->device on-ramp end to end
                                  (staging refills + device merge +
                                  UDP delivery)
  BENCH_INJECT_RATE=R             synthesize the trace instead of
                                  replaying one: R events/s aggregate,
                                  round-robin source, each a datagram
                                  to the next host, for the whole run.
                                  Exclusive with BENCH_INJECT_TRACE;
                                  both imply the supervised loop and
                                  accept BENCH_CHUNK_WINDOWS
  BENCH_WARM=1                    warm-rerun scoring: serve dispatch
                                  programs from the persistent AOT
                                  store (shadow_tpu/compile/). The
                                  warm-up call compiles-and-stores on
                                  miss; the timed call re-resolves the
                                  SAME config against the store, so
                                  the row's "compile" block records
                                  the cached cost (hit=true, load_s)
                                  next to the fresh cost
                                  (compile.warmup: lower_s/compile_s)
                                  — cached-vs-fresh in one banked row.
                                  Equivalent to SHADOW_WARM_PROGRAMS=1
  BENCH_BUCKETED=1/0              quantize the capacity knobs to their
                                  power-of-two buckets before building
                                  (compile/buckets.py; recorded under
                                  compile.buckets). Default follows
                                  warm serving — bucketing is what
                                  makes nearby configs share one
                                  stored program
  BENCH_SWEEP=1                   counterfactual-sweep mode
                                  (shadow_tpu/sweep): a small 3-axis
                                  lattice (seed x load x
                                  event_capacity) through the sweep
                                  driver on a 2-worker fleet. The
                                  warm-up sweep pays every distinct
                                  program's compile; the scored sweep
                                  re-runs the same lattice in a fresh
                                  dir on the warm pool and banks
                                  points/s plus the prewarm hit rate
                                  ("sweep" block: lattice_conserved,
                                  distinct_programs, prewarm hits/
                                  compiled) for the regression gate.
                                  Exclusive with the other loop shapes
  BENCH_RESIDENT=R                resident-program mode
                                  (fleet/admission.py): R heterogeneous
                                  PHOLD tenants lease lanes of ONE warm
                                  packed program, with one mid-run
                                  operator eviction so the scored wall
                                  includes admission-barrier churn. The
                                  row banks under its own metric name
                                  and carries the lease-table roll-up
                                  ("resident" block: program_key_stable,
                                  retraces, admission_events) so the
                                  regression gate tracks continuous-
                                  admission throughput, not just static
                                  ensembles. Exclusive with the other
                                  workload shapes; BENCH_HOSTS is the
                                  per-tenant host count

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"backend", ...}. `backend` records where the run actually executed;
a CPU run happens only when BENCH_PLATFORM=cpu asks for one.
vs_baseline compares against BASELINE.json's published events_per_sec
at the same scale; 0.0 until measured. With telemetry on, the line
also carries per-window stats from the ring (events_per_window
percentiles, wallclock_per_window_ms) and the run manifest
(telemetry/export.py run_manifest: config hash, seed, final counters).
"""

from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

_SHARDS = int(os.environ.get("BENCH_SHARDS", "0"))

ONE_VERTEX = """<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
  <key attr.name="latency" attr.type="double" for="edge" id="lat" />
  <key attr.name="bandwidthup" attr.type="int" for="node" id="up" />
  <key attr.name="bandwidthdown" attr.type="int" for="node" id="dn" />
  <graph edgedefault="undirected">
    <node id="poi"><data key="up">102400</data><data key="dn">102400</data>
    </node>
    <edge source="poi" target="poi"><data key="lat">50.0</data></edge>
  </graph>
</graphml>"""

# Heterogeneous small-latency fixture (BENCH_TOPO=mix): three vertices
# whose pairwise latencies are mutually incommensurate milliseconds, so
# PHOLD arrival times — sums of random hop picks — smear densely over
# sim-time instead of synchronizing on one 50 ms beat the way the
# single-vertex fixture does. min pair latency 1.1 ms => ~1.1 ms
# conservative windows, hundreds of windows per simulated second: the
# SMALL-WINDOW shape where per-dispatch host overhead dominates and
# chunked dispatch (BENCH_CHUNK_WINDOWS) has something to amortize.
MIX_VERTICES = """<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
  <key attr.name="latency" attr.type="double" for="edge" id="lat" />
  <key attr.name="bandwidthup" attr.type="int" for="node" id="up" />
  <key attr.name="bandwidthdown" attr.type="int" for="node" id="dn" />
  <graph edgedefault="undirected">
    <node id="v0"><data key="up">102400</data><data key="dn">102400</data>
    </node>
    <node id="v1"><data key="up">102400</data><data key="dn">102400</data>
    </node>
    <node id="v2"><data key="up">102400</data><data key="dn">102400</data>
    </node>
    <edge source="v0" target="v0"><data key="lat">1.1</data></edge>
    <edge source="v1" target="v1"><data key="lat">1.7</data></edge>
    <edge source="v2" target="v2"><data key="lat">2.3</data></edge>
    <edge source="v0" target="v1"><data key="lat">1.3</data></edge>
    <edge source="v0" target="v2"><data key="lat">1.9</data></edge>
    <edge source="v1" target="v2"><data key="lat">2.9</data></edge>
  </graph>
</graphml>"""

# The reference's real Internet-derived topology (183 vertices, 16.8k
# edges) — the graph every real Shadow experiment runs on and BASELINE
# config #2's explicit input. Overridable for installs without the
# reference tree mounted.
REF_TOPOLOGY = os.environ.get(
    "SHADOW_REF_TOPOLOGY",
    "/root/reference/resource/topology.graphml.xml.xz")


def ref_topology_text() -> str:
    import lzma

    if REF_TOPOLOGY.endswith(".xz"):
        with lzma.open(REF_TOPOLOGY, "rt") as f:
            return f.read()
    with open(REF_TOPOLOGY) as f:
        return f.read()


def _bench_flow_sample() -> int:
    """BENCH_FLOW_SAMPLE: 1-in-N flow-latency sampling on the timed
    program (0 = off). The ring rides the timed inputs, same honesty
    rule as BENCH_TELEMETRY."""
    v = os.environ.get("BENCH_FLOW_SAMPLE")
    return int(v) if v else 0


def _attach_flow_ring(sims: list, flow_sample: int) -> list:
    if flow_sample <= 0:
        return sims
    from shadow_tpu import telemetry

    return [telemetry.attach_flows(s, sample_period=flow_sample)
            for s in sims]


def _bench_causality_sample() -> int:
    """BENCH_CAUSALITY: 1-in-N event-lineage sampling + window-advance
    attribution on the timed program (0 = off). Same honesty rule as
    the other rings: the planes ride the timed inputs."""
    v = os.environ.get("BENCH_CAUSALITY")
    return int(v) if v else 0


def _attach_causality_ring(sims: list, causality_sample: int) -> list:
    if causality_sample <= 0:
        return sims
    from shadow_tpu import telemetry

    return [telemetry.attach_causality(s,
                                       sample_period=causality_sample)
            for s in sims]


def _bench_sentinel() -> bool:
    """BENCH_SENTINEL=1: attach the cross-shard integrity sentinel
    (parallel/elastic.py attach_sentinel) to the timed program — the
    per-barrier replicated-state digest plus the pmax/pmin compare.
    Same honesty rule as the rings: the sentinel rides the timed
    inputs, so on-vs-off is the real cost of the SDC screen."""
    return os.environ.get("BENCH_SENTINEL", "0") == "1"


def _attach_sentinel(sims: list, on: bool) -> list:
    if not on:
        return sims
    from shadow_tpu.parallel import elastic

    return [elastic.attach_sentinel(s) for s in sims]


def _bench_bucketed() -> bool:
    """Quantize capacities to power-of-two buckets? Explicit
    BENCH_BUCKETED wins; unset follows warm serving (a warm store
    keyed on exact capacities would fragment across nearby configs)."""
    from shadow_tpu.compile import serve

    v = os.environ.get("BENCH_BUCKETED")
    if v is None:
        return serve.warm_enabled(False)
    return v != "0"


def _bench_specialize() -> bool:
    """BENCH_SPECIALIZE=1: time the capability-trimmed program
    (compile/specialize.py) and an unspecialized twin of the same
    workload for the specialize_speedup A/B field."""
    return os.environ.get("BENCH_SPECIALIZE", "0") == "1"


def _spec_block(caps, sim):
    """Manifest specialization block of the timed run (None when the
    program was not specialized) — telemetry_lint validates it."""
    from shadow_tpu.compile import specialize

    return specialize.specialization_block(caps, sim)


def _build_phold(H: int, load: int, sim_s: int, seed: int = 1,
                 cap: int | None = None, graph: str | None = None,
                 replica_size: int | None = None, fault_records=None,
                 active_hosts: int | None = None,
                 sparse_lanes: int | None = None,
                 bucketed: bool = False):
    from shadow_tpu.apps import phold
    from shadow_tpu.core import simtime
    from shadow_tpu.net.build import HostSpec, build
    from shadow_tpu.net.state import NetConfig

    # Tight capacity: per-host in-window arrivals are ~Poisson(load),
    # and the window cost is linear in capacity (every pass moves the
    # whole [H,K] SoA), so oversizing K directly divides events/s.
    # The max-over-hosts tail grows with host-window count: 3x load is
    # clean at <=4k hosts but measured overflows (a few events) at
    # 10k/100k, so larger runs start at 6x. _phold_runner still
    # escalates on counted overflow either way.
    if cap is None:
        cap = max(16, 3 * load) if H <= 4096 else 6 * load
    cfg = NetConfig(num_hosts=H, tcp=False,
                    end_time=sim_s * simtime.ONE_SECOND, seed=seed,
                    event_capacity=cap, outbox_capacity=cap,
                    router_ring=cap, in_ring=max(16, 2 * load),
                    sparse_lanes=sparse_lanes)
    bucket_plan = None
    if bucketed:
        from shadow_tpu.compile.buckets import bucket_config

        cfg, bucket_plan = bucket_config(cfg)
    hosts = [HostSpec(name=f"peer{i}", proc_start_time=0) for i in range(H)]
    b = build(cfg, graph or ONE_VERTEX, hosts)
    b.bucket_plan = bucket_plan
    b.sim = phold.setup(b.sim, load=load, replica_size=replica_size,
                        active_hosts=active_hosts)
    if replica_size and H > replica_size \
            and os.environ.get("BENCH_LANE_ISOLATION", "0") != "0":
        # packed ensemble rows carry lane-scoped health latches so the
        # bench measures the blast-radius machinery's true overhead
        # (attach BEFORE telemetry — the ring sizes its per-lane
        # planes off sim.lanes)
        from shadow_tpu.core import lanes as lanes_mod

        b.sim = lanes_mod.attach(b.sim, H // replica_size)
    if fault_records:
        # degraded-network scenario: the plan rides the bundle, so the
        # same runner factories apply it on 1 shard and N shards alike
        from shadow_tpu import faults

        faults.install(b, fault_records)
    return b


def make_shard_aware_runner(b, shards: int, **kw):
    """make_runner, or make_sharded_runner over a `shards`-device mesh
    when shards > 1 (shared by bench.py and tools/scale_run.py —
    keep the selection logic in one place). kw: app_handlers,
    app_bulk."""
    from shadow_tpu.net.build import make_runner

    if shards > 1:
        from shadow_tpu.parallel.shard import make_sharded_runner

        mesh = jax.make_mesh((shards,), ("hosts",))
        return make_sharded_runner(b, mesh, "hosts", **kw)
    return make_runner(b, **kw)


def _make_phold_fn(b, shards: int, use_bulk: bool = True,
                   compile_info: dict | None = None):
    from shadow_tpu.apps import phold

    return make_shard_aware_runner(
        b, shards, app_handlers=(phold.handler,),
        app_bulk=phold.BULK if use_bulk else None,
        compile_info=compile_info)


def _phold_runner(H, load, sim_s, seed=1, shards: int = 0,
                  graph: str | None = None,
                  replica_size: int | None = None, fault_records=None,
                  active_hosts: int | None = None,
                  sparse_lanes: int | None = None,
                  min_jump_ns: int | None = None,
                  flow_sample: int | None = None,
                  causality_sample: int | None = None,
                  specialize: bool | None = None,
                  sentinel: bool | None = None):
    """Returns a zero-arg callable running the workload through ONE
    reused jitted program (the timed call must hit the jit dispatch
    fast path, not re-trace the netstack). Each call runs a DIFFERENT
    seed: re-executing a jitted program on bit-identical inputs can be
    served from an execution-result cache by the device runtime, which
    would make the timed iteration measure nothing.

    Queue capacity starts tight (3*load) and doubles on overflow —
    events are counted when dropped, never silently lost, so a clean
    overflow==0 run at a tight capacity is sound AND fast."""
    state = {"n": 0, "cap": None, "fn": None, "sims": None,
             "bundle": None, "cinfo": None}
    telem_on = os.environ.get("BENCH_TELEMETRY", "1") != "0"
    fs = _bench_flow_sample() if flow_sample is None else flow_sample
    cs = (_bench_causality_sample() if causality_sample is None
          else causality_sample)
    bucketed = _bench_bucketed()
    sp = _bench_specialize() if specialize is None else specialize
    sn = _bench_sentinel() if sentinel is None else sentinel

    def build_at(cap):
        b = _build_phold(H, load, sim_s, seed, cap, graph, replica_size,
                         fault_records, active_hosts, sparse_lanes,
                         bucketed=bucketed)
        if min_jump_ns is not None:
            b.min_jump = min(b.min_jump, int(min_jump_ns))
        # pre-build distinct-seed inputs so the timed call measures
        # only the device program, not host-side setup (each carries
        # its own seeded fault wakeups)
        sims = [b.sim] + [_build_phold(H, load, sim_s, seed + i, cap,
                                       graph, replica_size,
                                       fault_records, active_hosts,
                                       sparse_lanes,
                                       bucketed=bucketed).sim
                          for i in (1, 2)]
        if telem_on:
            # ring attached to the TIMED inputs, on purpose: the
            # overhead claim (<2% vs BENCH_TELEMETRY=0) is only honest
            # if the measured program carries the ring writes
            from shadow_tpu import telemetry

            sims = [telemetry.attach(s) for s in sims]
            b.sim = sims[0]
        # flow + causality rings on the TIMED inputs too — same
        # honesty rule
        sims = _attach_flow_ring(sims, fs)
        sims = _attach_causality_ring(sims, cs)
        sims = _attach_sentinel(sims, sn)
        b.sim = sims[0]
        if sp:
            # specialize AFTER every attachment (the analysis reads
            # the final sim composition); the specialized program
            # expects the guard leaves in its input pytree, so every
            # timed input gets them
            from shadow_tpu.apps import phold
            from shadow_tpu.compile import specialize as spec_mod

            b = spec_mod.apply(b, (phold.handler,),
                               app_bulk=phold.BULK
                               if active_hosts is None else None)
            if getattr(b.sim, "guard", None) is not None:
                sims = [b.sim] + [s.replace(guard=b.sim.guard)
                                  for s in sims[1:]]
        # sparse shape: bulk would consume whole windows before the
        # fixpoint ever ran, starving the compaction fast path the
        # shape exists to exercise
        cinfo: dict = {}
        fn = _make_phold_fn(b, shards, use_bulk=active_hosts is None,
                            compile_info=cinfo)
        for s in sims:
            jax.block_until_ready(s.net.rng_keys)
        state.update(cap=cap, fn=fn, sims=sims, bundle=b, cinfo=cinfo)

    build_at(max(16, 3 * load))

    def go():
        go.escalated = False
        while True:
            sim0 = state["sims"][state["n"] % len(state["sims"])]
            state["n"] += 1
            sim, stats = state["fn"](sim0)
            stats = jax.device_get(stats)
            overflow = (int(jax.device_get(sim.events.overflow))
                        + int(jax.device_get(sim.outbox.overflow)))
            if overflow:
                build_at(state["cap"] * 2)   # recompile, re-run clean
                go.escalated = True
                continue
            assert int(jax.device_get(sim.app.rcvd.sum())) > 0
            go.last_sim = sim
            go.last_stats = stats
            go.last_compile = dict(state["cinfo"] or {})
            go.bucket_plan = getattr(state["bundle"], "bucket_plan",
                                     None)
            return int(stats.events_processed)

    go.escalated = False
    go.last_sim = None
    go.last_stats = None
    go.last_compile = None
    go.bucket_plan = None
    go.state = state
    return go


def _phold_supervised_runner(H, load, sim_s, seed=1, shards: int = 0,
                             graph: str | None = None,
                             fault_records=None,
                             chunk_windows: int | None = None,
                             adaptive_jump: bool = False,
                             min_jump_ns: int | None = None,
                             checkpoint_windows: int | None = None,
                             flow_sample: int | None = None,
                             causality_sample: int | None = None,
                             sentinel: bool | None = None):
    """PHOLD through faults.run_supervised — the host-driven window
    loop with health checks at every dispatch barrier. This is the
    dispatch-amortization A/B subject: at windows_per_dispatch=1 every
    window pays a host round-trip; at K the loop stays on device for K
    windows per barrier. `min_jump_ns` LOWERS the bundle's window span
    (never raises it — larger would break the conservative-window
    invariant) to manufacture the small-window shape where dispatch
    overhead dominates. Capacity escalates by doubling on counted
    overflow, exactly like _phold_runner."""
    import tempfile

    from shadow_tpu import faults, telemetry

    state = {"n": 0, "cap": None, "bundle": None, "sims": None,
             "mesh": None}
    telem_on = os.environ.get("BENCH_TELEMETRY", "1") != "0"
    fs = _bench_flow_sample() if flow_sample is None else flow_sample
    cs = (_bench_causality_sample() if causality_sample is None
          else causality_sample)
    bucketed = _bench_bucketed()
    sn = _bench_sentinel() if sentinel is None else sentinel
    every = checkpoint_windows or (1 << 30)   # default: never fires
    ckdir = tempfile.mkdtemp(prefix="bench_sup_")

    def build_at(cap):
        from shadow_tpu.apps import phold

        b = _build_phold(H, load, sim_s, seed, cap, graph, None,
                         fault_records, bucketed=bucketed)
        # same bulk pass the unsupervised megakernel gets — the
        # supervised loop honors bundle.app_bulk (checkpoint.run_windows)
        b.app_bulk = phold.BULK
        if min_jump_ns is not None:
            b.min_jump = min(b.min_jump, int(min_jump_ns))
        sims = [b.sim] + [_build_phold(H, load, sim_s, seed + i, cap,
                                       graph, None, fault_records,
                                       bucketed=bucketed).sim
                          for i in (1, 2)]
        if telem_on:
            # production-default ring, grown only when a chunk would
            # overrun it: the supervised loop drains once per dispatch
            # (telemetry/ring.py), and every K must carry the SAME
            # ring the per-window baseline does for an honest A/B.
            # Ring capacity shapes the program, so it is quantized to
            # its bucket like every other capacity knob — nearby chunk
            # sizes share one stored program (compile/buckets.py)
            from shadow_tpu.compile.buckets import quantize_pow2
            from shadow_tpu.telemetry.ring import DEFAULT_CAPACITY

            W = quantize_pow2(max(DEFAULT_CAPACITY,
                                  2 * (chunk_windows or 1)))
            sims = [telemetry.attach(s, capacity=W) for s in sims]
        sims = _attach_flow_ring(sims, fs)
        sims = _attach_causality_ring(sims, cs)
        sims = _attach_sentinel(sims, sn)
        b.sim = sims[0]
        mesh = (jax.make_mesh((shards,), ("hosts",))
                if shards > 1 else None)
        for s in sims:
            jax.block_until_ready(s.net.rng_keys)
        state.update(cap=cap, bundle=b, sims=sims, mesh=mesh)

    build_at(max(16, 3 * load))

    def go():
        go.escalated = False
        while True:
            b = state["bundle"]
            b.sim = state["sims"][state["n"] % len(state["sims"])]
            state["n"] += 1
            h = telemetry.Harvester()
            from shadow_tpu.apps import phold

            result = faults.run_supervised(
                b, app_handlers=(phold.handler,),
                checkpoint_path=os.path.join(ckdir, "ck"),
                checkpoint_every_windows=every,
                harvester=h, mesh=state["mesh"],
                windows_per_dispatch=chunk_windows,
                adaptive_jump=adaptive_jump or None)
            sim = result.sim
            overflow = (int(jax.device_get(sim.events.overflow))
                        + int(jax.device_get(sim.outbox.overflow)))
            if overflow:
                build_at(state["cap"] * 2)
                go.escalated = True
                continue
            assert int(jax.device_get(sim.app.rcvd.sum())) > 0
            go.last_sim = sim
            go.last_stats = jax.device_get(result.stats)
            go.last_result = result
            go.last_compile = dict(getattr(result, "compile_info",
                                           None) or {})
            go.bucket_plan = getattr(b, "bucket_plan", None)
            go.harvester = h
            return int(result.stats.events_processed)

    go.escalated = False
    go.last_sim = None
    go.last_stats = None
    go.last_result = None
    go.last_compile = None
    go.bucket_plan = None
    go.harvester = None
    go.state = state
    return go


def _rate_trace(H: int, rate: float, sim_s: int) -> list:
    """Synthesized uniform injection trace: aggregate `rate` events/s,
    round-robin source host, each a KIND_TGEN datagram to the next
    host. Pure arithmetic — no RNG — so the trace is a function of
    (H, rate, sim_s) alone."""
    from shadow_tpu.apps.tgen import KIND_TGEN
    from shadow_tpu.core import simtime

    period = max(1, int(simtime.ONE_SECOND / rate))
    end = sim_s * simtime.ONE_SECOND
    events = []
    t, i = period, 0
    while t < end:
        src = i % H
        events.append({"t_ns": t, "host": src, "kind": KIND_TGEN,
                       "payload": [(src + 1) % H, 9100, 64]})
        i += 1
        t += period
    return events


def _inject_runner(H, sim_s, seed=1, shards: int = 0,
                   graph: str | None = None,
                   trace_path: str | None = None,
                   rate: float | None = None,
                   fault_records=None,
                   chunk_windows: int | None = None,
                   adaptive_jump: bool = False,
                   min_jump_ns: int | None = None,
                   checkpoint_windows: int | None = None,
                   flow_sample: int | None = None,
                   causality_sample: int | None = None,
                   sentinel: bool | None = None):
    """Open-system injection scenario: the tgen app (every host binds
    a UDP socket; injected KIND_TGEN events fire datagrams) driven by
    a streamed trace through the supervised window loop — the feeder
    refills the device staging buffer at every dispatch barrier, so
    the measured rate covers the whole on-ramp, not just the engine.
    Capacity escalates by doubling on counted overflow like the other
    runners; injection drops are accounted (never silent) but a bench
    run that drops trace events is resized rather than reported."""
    import tempfile

    from shadow_tpu import faults, telemetry
    from shadow_tpu.apps import tgen
    from shadow_tpu.core import simtime
    from shadow_tpu.inject import Feeder, read_trace
    from shadow_tpu.net.build import HostSpec, build
    from shadow_tpu.net.state import NetConfig

    if trace_path is not None:
        n_ev = sum(1 for _ in read_trace(trace_path))
        mem_events = None
    else:
        mem_events = _rate_trace(H, rate, sim_s)
        n_ev = len(mem_events)
    lanes = tgen.lanes_for(n_ev)
    state = {"n": 0, "cap": None, "bundle": None, "sims": None,
             "mesh": None}
    telem_on = os.environ.get("BENCH_TELEMETRY", "1") != "0"
    fs = _bench_flow_sample() if flow_sample is None else flow_sample
    cs = (_bench_causality_sample() if causality_sample is None
          else causality_sample)
    bucketed = _bench_bucketed()
    sn = _bench_sentinel() if sentinel is None else sentinel
    every = checkpoint_windows or (1 << 30)
    ckdir = tempfile.mkdtemp(prefix="bench_inj_")

    def build_one(cap, s):
        cfg = NetConfig(num_hosts=H, tcp=False,
                        end_time=sim_s * simtime.ONE_SECOND, seed=s,
                        event_capacity=cap, outbox_capacity=cap,
                        router_ring=cap, in_ring=16,
                        inject_lanes=lanes)
        bucket_plan = None
        if bucketed:
            from shadow_tpu.compile.buckets import bucket_config

            cfg, bucket_plan = bucket_config(cfg)
        hosts = [HostSpec(name=f"peer{i}", proc_start_time=0)
                 for i in range(H)]
        b = build(cfg, graph or ONE_VERTEX, hosts)
        b.bucket_plan = bucket_plan
        b.sim = tgen.setup(b.sim)
        if fault_records:
            faults.install(b, fault_records)
        if min_jump_ns is not None:
            b.min_jump = min(b.min_jump, int(min_jump_ns))
        return b

    def build_at(cap):
        b = build_one(cap, seed)
        sims = [b.sim] + [build_one(cap, seed + i).sim for i in (1, 2)]
        if telem_on:
            # quantized like every capacity knob — see the supervised
            # runner's attach site
            from shadow_tpu.compile.buckets import quantize_pow2
            from shadow_tpu.telemetry.ring import DEFAULT_CAPACITY

            W = quantize_pow2(max(DEFAULT_CAPACITY,
                                  2 * (chunk_windows or 1)))
            sims = [telemetry.attach(s, capacity=W) for s in sims]
        sims = _attach_flow_ring(sims, fs)
        sims = _attach_causality_ring(sims, cs)
        sims = _attach_sentinel(sims, sn)
        b.sim = sims[0]
        mesh = (jax.make_mesh((shards,), ("hosts",))
                if shards > 1 else None)
        for s in sims:
            jax.block_until_ready(s.net.rng_keys)
        state.update(cap=cap, bundle=b, sims=sims, mesh=mesh)

    build_at(64)

    def go():
        go.escalated = False
        while True:
            b = state["bundle"]
            b.sim = state["sims"][state["n"] % len(state["sims"])]
            state["n"] += 1
            # a fresh feeder per run: every timed iteration replays
            # the trace from position 0 against a t=0 sim
            feeder = Feeder(trace_path if trace_path is not None
                            else list(mem_events))
            h = telemetry.Harvester()
            result = faults.run_supervised(
                b, app_handlers=(tgen.handler,),
                checkpoint_path=os.path.join(ckdir, "ck"),
                checkpoint_every_windows=every,
                harvester=h, mesh=state["mesh"],
                windows_per_dispatch=chunk_windows,
                adaptive_jump=adaptive_jump or None,
                feeder=feeder)
            sim = result.sim
            overflow = (int(jax.device_get(sim.events.overflow))
                        + int(jax.device_get(sim.outbox.overflow))
                        + int(jax.device_get(sim.inject.dropped)))
            if overflow:
                build_at(state["cap"] * 2)
                go.escalated = True
                continue
            assert int(jax.device_get(sim.app.rcvd.sum())) > 0
            go.last_sim = sim
            go.last_stats = jax.device_get(result.stats)
            go.last_result = result
            go.last_compile = dict(getattr(result, "compile_info",
                                           None) or {})
            go.bucket_plan = getattr(b, "bucket_plan", None)
            go.last_feeder = feeder
            go.harvester = h
            return int(result.stats.events_processed)

    go.escalated = False
    go.last_sim = None
    go.last_stats = None
    go.last_result = None
    go.last_compile = None
    go.bucket_plan = None
    go.last_feeder = None
    go.harvester = None
    go.state = state
    return go


def _pingpong_runner(H, sim_s):
    from __graft_entry__ import _build
    from shadow_tpu.apps import pingpong
    from shadow_tpu.net.build import make_runner

    b = _build(num_hosts=H, end_time_s=sim_s, count=20, tcp=False)
    fn = make_runner(b, app_handlers=(pingpong.handler,))
    state = {"n": 0}

    def go():
        # perturb per-host RNG streams so repeat executions differ
        # (see _phold_runner on result caching); pingpong traffic is
        # RNG-independent so the workload is unchanged
        state["n"] += 1
        import jax.numpy as jnp

        net = b.sim.net
        sim0 = b.sim.replace(net=net.replace(
            rng_ctr=net.rng_ctr + jnp.uint32(state["n"])))
        sim, stats = fn(sim0)
        stats = jax.device_get(stats)
        rcvd = np.asarray(jax.device_get(sim.app.rcvd))[: H // 2]
        assert (rcvd == 20).all(), f"workload incomplete: {rcvd[:8].tolist()}"
        return int(stats.events_processed)

    return go


def select_platform(cpu: bool, shards: int = 0) -> None:
    """Choose the backend before anything starts one (shared by
    bench.py and tools/scale_run.py). cpu=True: the CPU backend, with
    `shards` virtual devices for an n-shard mesh. Otherwise the run
    needs an accelerator with at least `shards` devices and fails
    when JAX finds fewer — it never falls back to the CPU."""
    if cpu:
        jax.config.update("jax_platforms", "cpu")
        if shards > 1:
            jax.config.update("jax_num_cpu_devices", shards)
        return
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise SystemExit(
            "no accelerator found; ask for the CPU explicitly "
            "(BENCH_PLATFORM=cpu for bench.py, --cpu for "
            "tools/scale_run.py)")
    if shards > 1 and len(devs) < shards:
        raise SystemExit(f"{shards} shards need {shards} devices; "
                         f"JAX found {len(devs)} {devs[0].platform} "
                         "device(s)")


def _cache_files() -> set | None:
    """Recursive file-set snapshot of the persistent compile cache
    (None = cache disabled or the directory does not exist yet). The
    fresh-vs-cached call is a before/after diff: new files appeared
    during the warm call means XLA actually compiled and wrote an
    executable; an unchanged set means the call was served from the
    cache (load+execute only)."""
    d = jax.config.jax_compilation_cache_dir
    if not d or not os.path.isdir(d):
        return None
    out = set()
    for root, _, files in os.walk(d):
        for f in files:
            out.add(os.path.join(root, f))
    return out


def _resident_row(H: int, load: int, sim_s: int, lanes: int) -> dict:
    """BENCH_RESIDENT=R: throughput of one warm packed program whose
    lane population churns at window barriers (fleet/admission.py).
    R heterogeneous PHOLD tenants are admitted at t=0, one is evicted
    and re-admitted mid-run — two extra admission barriers inside the
    scored wall — and the program drains. The warm-up trial pays the
    compile; the timed trial re-resolves the same program. The row
    carries the lease-table roll-up so the regression gate also sees a
    broken zero-retrace contract (program_key_stable=false or
    retraces>0) on the banked line, not only a throughput drop."""
    import shutil
    import tempfile

    from shadow_tpu.fleet import admission as adm_mod
    from shadow_tpu.fleet.spec import JobSpec

    specs = [JobSpec(id=f"tenant-{k}", kind="scenario", seed=1000 + k,
                     hosts=H, load=max(1, load - (k % 2)), sim_s=sim_s)
             for k in range(lanes)]

    def trial(workdir):
        rp = adm_mod.ResidentProgram(
            specs, workdir=workdir, lanes=lanes,
            horizon_s=2 * sim_s + 1, checkpoint_every_events=0,
            fsync=False)
        try:
            for s in specs:
                rp.admit(s.id)
            rp.advance(until_ns=(sim_s * 1_000_000_000) // 2)
            rp.evict(specs[-1].id, reason="bench churn")
            rp.admit(specs[-1].id)
            rp.drain()
        finally:
            rp.close()
        return rp

    root = tempfile.mkdtemp(prefix="bench_resident_")
    try:
        cache_before = _cache_files()
        t0 = time.perf_counter()
        trial(os.path.join(root, "warm"))      # pays the compile
        compile_s = time.perf_counter() - t0
        cache_after = _cache_files()
        compile_fresh = (cache_before is None
                         or bool((cache_after or set()) - cache_before))
        t0 = time.perf_counter()
        rp = trial(os.path.join(root, "timed"))
        wall = time.perf_counter() - t0
    finally:
        shutil.rmtree(root, ignore_errors=True)
    baseline = 0.0
    try:
        with open(os.path.join(os.path.dirname(__file__),
                               "BASELINE.json")) as f:
            baseline = float(json.load(f)["published"]
                             .get("events_per_sec", 0.0))
    except Exception:
        pass
    value = rp.events / wall
    blk = rp.manifest_block()
    return {
        "metric": (f"events_per_sec_per_chip@{H}hosts_resident"
                   f"_x{lanes}lanes_churn"),
        "value": round(value, 1),
        "unit": "events/s",
        "vs_baseline": round(value / baseline, 3) if baseline else 0.0,
        "backend": jax.default_backend(),
        "compile_s": round(compile_s, 3),
        "compile_cache": "fresh" if compile_fresh else "cached",
        "wall_seconds": round(wall, 3),
        "windows": rp.windows,
        "dispatches": rp.dispatches,
        "resident": {k: blk.get(k) for k in
                     ("lanes", "admitted", "completed", "evicted",
                      "quarantined", "resident", "deferred",
                      "program_key", "program_key_stable",
                      "admission_events", "retraces", "lane_width",
                      "degrade_level")},
    }


def _sweep_row(H: int, load: int, sim_s: int) -> dict:
    """BENCH_SWEEP=1: the fleet as a query service. One small 3-axis
    lattice (seed x load x event_capacity — the capacity values share
    a pow2 bucket at the default load, so the census stays small)
    through the sweep driver (shadow_tpu/sweep) twice: the warm-up
    sweep pays every distinct program's compile into the AOT store,
    the scored sweep re-runs the identical lattice in a fresh dir and
    must find every program warm (prewarm_hit_rate 1.0 — the gate
    fails the row otherwise). The banked value is completed points
    per second of the scored sweep."""
    import shutil
    import tempfile

    from shadow_tpu.sweep import driver as sweep_driver
    from shadow_tpu.sweep import plan as plan_mod

    spec_obj = {
        "sweep": {"id": "bench",
                  "objective": {"metric": "events", "goal": "max"},
                  "search": {"strategy": "grid"}},
        "fleet": {"max_attempts": 2},
        "template": {"kind": "scenario", "hosts": H, "sim_s": sim_s},
        "axes": [
            {"field": "seed", "values": [1, 2]},
            {"field": "load", "values": [load, load + 1]},
            {"field": "event_capacity",
             "values": [3 * load, 4 * load]},
        ],
    }
    root = tempfile.mkdtemp(prefix="bench_sweep_")
    try:
        t0 = time.perf_counter()
        warm = sweep_driver.SweepDriver(
            os.path.join(root, "warm"),
            plan_mod.SweepSpec.from_obj(spec_obj), workers=2,
            fsync=False)
        rc_warm = warm.run()
        warm_s = time.perf_counter() - t0
        warm_block = warm.report()
        t0 = time.perf_counter()
        timed = sweep_driver.SweepDriver(
            os.path.join(root, "timed"),
            plan_mod.SweepSpec.from_obj(spec_obj), workers=2,
            fsync=False)
        rc_timed = timed.run()
        wall = time.perf_counter() - t0
        block = timed.report()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    pts = block["points"]
    conserved = (pts["expanded"] == pts["completed"] + pts["failed"]
                 + pts["quarantined"] + pts["pruned"]
                 + pts["pending"]) and pts["pending"] == 0
    pw = block.get("prewarm") or {"hits": 0, "compiled": 0}
    warmed = pw["hits"] + pw["compiled"]
    hit_rate = (pw["hits"] / warmed) if warmed else 0.0
    value = pts["completed"] / wall if wall > 0 else 0.0
    return {
        "metric": (f"sweep_points_per_sec@{pts['expanded']}points"
                   f"_{block['census']['distinct']}programs"
                   f"_x2workers"),
        "value": round(value, 3),
        "unit": "points/s",
        "vs_baseline": 0.0,
        "backend": jax.default_backend(),
        "compile_s": round(warm_s, 3),
        "compile_cache": ("cached" if (warm_block.get("prewarm")
                                       or {}).get("compiled", 1) == 0
                          else "fresh"),
        "wall_seconds": round(wall, 3),
        "sweep": {
            "exit_warm": rc_warm,
            "exit_timed": rc_timed,
            "lattice": block["lattice"],
            "points": pts,
            "lattice_conserved": bool(conserved),
            "distinct_programs": block["census"]["distinct"],
            "prewarm_hits": pw["hits"],
            "prewarm_compiled": pw["compiled"],
            "prewarm_hit_rate": round(hit_rate, 3),
            "best": block["best"],
        },
    }


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(
        description="shadow-tpu throughput benchmark (env knobs in "
                    "the module docstring)")
    ap.add_argument("--faults", default=os.environ.get("BENCH_FAULTS"),
                    help="JSON fault plan (faults.plan.records_from_json "
                    "format): measure throughput on a degraded network "
                    "(injected loss / link flaps / latency spikes)")
    args = ap.parse_args(argv)
    fault_records = None
    if args.faults:
        from shadow_tpu import faults as faults_mod

        with open(args.faults) as f:
            fault_records = faults_mod.records_from_json(f.read())
    if os.environ.get("BENCH_WARM") == "1":
        # warm-rerun scoring: the runners resolve their dispatch
        # programs through the persistent AOT store (compile/serve.py)
        os.environ.setdefault("SHADOW_WARM_PROGRAMS", "1")
    from shadow_tpu.utils.compcache import enable_compile_cache

    enable_compile_cache()
    cpu = os.environ.get("BENCH_PLATFORM") == "cpu"
    if cpu:
        select_platform(True, _SHARDS)
    workload = os.environ.get("BENCH_WORKLOAD", "phold")
    topo = os.environ.get("BENCH_TOPO", "one")
    # Default scale per backend, each compared against the measured
    # baseline AT THAT SCALE (below): the accelerator streams the
    # [H,K] state from HBM and wants lanes, so bigger is better; the
    # 1-core CPU run is cache-bound and 1k's working set fits L3.
    default_h = "1024" if cpu else "10240"
    H = int(os.environ.get("BENCH_HOSTS", default_h))
    sim_s = int(os.environ.get("BENCH_SIM_SECONDS", "5"))
    load = int(os.environ.get("BENCH_LOAD", "8"))
    graph = (ref_topology_text() if topo == "ref"
             else MIX_VERTICES if topo == "mix" else None)

    # BENCH_SWEEP=1: the counterfactual-sweep scenario is its own
    # workload — a small lattice through the sweep driver on a warm
    # 2-worker pool — and banks its own row (points/s + prewarm hit
    # rate), so the gate tracks query-service latency independently
    if os.environ.get("BENCH_SWEEP") == "1":
        if (any(os.environ.get(k) for k in
                ("BENCH_REPLICAS", "BENCH_SUPERVISE", "BENCH_ACTIVE",
                 "BENCH_SPARSE_LANES", "BENCH_INJECT_TRACE",
                 "BENCH_INJECT_RATE", "BENCH_CHUNK_WINDOWS",
                 "BENCH_SHARDS", "BENCH_FLOW_OVERHEAD",
                 "BENCH_FLOW_SAMPLE", "BENCH_CAUSALITY",
                 "BENCH_CAUSALITY_OVERHEAD", "BENCH_SENTINEL",
                 "BENCH_SENTINEL_OVERHEAD", "BENCH_RESIDENT"))
                or workload != "phold" or topo != "one"
                or fault_records):
            raise SystemExit(
                "BENCH_SWEEP is its own scenario (a job lattice "
                "through the sweep driver on a warm worker pool); it "
                "does not combine with the other workload/loop "
                "shapes")
        row = _sweep_row(H, load, sim_s)
        if not cpu:
            # checked only now: the fleet workers hold the chip, so
            # this process must not start a backend before they exit
            select_platform(False)
        print(json.dumps(row))
        return
    if not cpu:
        select_platform(False, _SHARDS)

    # BENCH_RESIDENT=R: the continuous-admission scenario is its own
    # workload — a resident packed program with churn — and banks its
    # own row, so the regression gate tracks it independently of the
    # static-ensemble numbers
    resident = int(os.environ.get("BENCH_RESIDENT", "0") or "0")
    if resident:
        if (any(os.environ.get(k) for k in
                ("BENCH_REPLICAS", "BENCH_SUPERVISE", "BENCH_ACTIVE",
                 "BENCH_SPARSE_LANES", "BENCH_INJECT_TRACE",
                 "BENCH_INJECT_RATE", "BENCH_CHUNK_WINDOWS",
                 "BENCH_SHARDS", "BENCH_FLOW_OVERHEAD",
                 "BENCH_FLOW_SAMPLE", "BENCH_CAUSALITY",
                 "BENCH_CAUSALITY_OVERHEAD", "BENCH_SENTINEL",
                 "BENCH_SENTINEL_OVERHEAD"))
                or workload != "phold" or topo != "one"
                or fault_records):
            raise SystemExit(
                "BENCH_RESIDENT is its own scenario (one warm packed "
                "program, tenant leases, mid-run churn); it does not "
                "combine with the other workload/loop shapes")
        if resident < 2:
            raise SystemExit("BENCH_RESIDENT needs >= 2 lanes (churn "
                             "on a 1-lane program has no undisturbed "
                             "tenant to protect)")
        print(json.dumps(_resident_row(H, load, sim_s, resident)))
        return

    # BENCH_REPLICAS=R: run R independent replicas of the H-host sim
    # in one device program (ensemble mode) — small configs alone
    # cannot fill the TPU's lanes; R replicas report AGGREGATE
    # events/s per chip, the honest per-chip throughput for the
    # seed-ensemble use case.
    replicas = int(os.environ.get("BENCH_REPLICAS", "1"))
    active = os.environ.get("BENCH_ACTIVE")
    active = int(active) if active else None
    sparse = os.environ.get("BENCH_SPARSE_LANES")
    sparse = int(sparse) if sparse is not None else None
    supervise = os.environ.get("BENCH_SUPERVISE") == "1"
    chunk = os.environ.get("BENCH_CHUNK_WINDOWS")
    chunk = int(chunk) if chunk else None
    adaptive = os.environ.get("BENCH_ADAPTIVE_JUMP") == "1"
    mjms = os.environ.get("BENCH_MIN_JUMP_MS")
    min_jump_ns = None
    if mjms:
        from shadow_tpu.core import simtime as _st

        min_jump_ns = int(float(mjms) * _st.ONE_MILLISECOND)
    ck_w = os.environ.get("BENCH_CHECKPOINT_WINDOWS")
    ck_w = int(ck_w) if ck_w else None
    inj_trace = os.environ.get("BENCH_INJECT_TRACE")
    inj_rate = os.environ.get("BENCH_INJECT_RATE")
    inj_rate = float(inj_rate) if inj_rate else None
    inject_on = bool(inj_trace or inj_rate)
    if inj_trace and inj_rate:
        raise SystemExit("BENCH_INJECT_TRACE and BENCH_INJECT_RATE "
                         "are mutually exclusive (replay xor "
                         "synthesize)")
    if (chunk or adaptive or ck_w) and not (supervise or inject_on):
        raise SystemExit(
            "BENCH_CHUNK_WINDOWS / BENCH_ADAPTIVE_JUMP / "
            "BENCH_CHECKPOINT_WINDOWS shape the supervised window "
            "loop; set BENCH_SUPERVISE=1 (the unsupervised engine.run "
            "megakernel has no dispatch boundaries to amortize). "
            "BENCH_MIN_JUMP_MS is a scenario knob and applies to both "
            "paths.")
    if supervise and workload != "phold":
        raise SystemExit("BENCH_SUPERVISE=1 is only wired for "
                         "BENCH_WORKLOAD=phold")
    if inject_on:
        # the injection scenario is its own workload: the tgen app
        # under the supervised loop (streaming needs the host-driven
        # barrier), so the loop-shaping knobs apply but the PHOLD
        # shapes do not
        if workload != "phold":
            raise SystemExit("BENCH_INJECT_* defines its own "
                             "scenario; leave BENCH_WORKLOAD unset")
        if supervise or replicas > 1 or active is not None \
                or sparse is not None:
            raise SystemExit(
                "BENCH_INJECT_* does not combine with "
                "BENCH_SUPERVISE / BENCH_REPLICAS / BENCH_ACTIVE / "
                "BENCH_SPARSE_LANES — it is already a supervised "
                "tgen scenario")
        runner = _inject_runner(
            H, sim_s, shards=_SHARDS, graph=graph,
            trace_path=inj_trace, rate=inj_rate,
            fault_records=fault_records, chunk_windows=chunk,
            adaptive_jump=adaptive, min_jump_ns=min_jump_ns,
            checkpoint_windows=ck_w)
        name = f"events_per_sec_per_chip@{H}hosts_inject"
        name += "_trace" if inj_trace else f"_rate{int(inj_rate)}"
        name += f"_chunk{chunk or 1}"
        if adaptive:
            name += "_adaptive"
        if mjms:
            name += f"_mj{mjms}ms"
    elif workload == "phold":
        if active is not None and replicas > 1:
            raise SystemExit("BENCH_ACTIVE and BENCH_REPLICAS are "
                             "mutually exclusive PHOLD shapes")
        if supervise:
            if replicas > 1 or active is not None:
                raise SystemExit("BENCH_SUPERVISE=1 does not combine "
                                 "with BENCH_REPLICAS/BENCH_ACTIVE")
            runner = _phold_supervised_runner(
                H, load, sim_s, shards=_SHARDS, graph=graph,
                fault_records=fault_records, chunk_windows=chunk,
                adaptive_jump=adaptive, min_jump_ns=min_jump_ns,
                checkpoint_windows=ck_w)
        else:
            runner = _phold_runner(
                H * replicas, load, sim_s, shards=_SHARDS, graph=graph,
                replica_size=H if replicas > 1 else None,
                fault_records=fault_records,
                active_hosts=active, sparse_lanes=sparse,
                min_jump_ns=min_jump_ns)
        name = f"events_per_sec_per_chip@{H}hosts_phold_load{load}"
        if replicas > 1:
            name += f"_x{replicas}replicas"
            if os.environ.get("BENCH_LANE_ISOLATION", "0") != "0":
                name += "_lanes"
        if active is not None:
            name += f"_active{active}"
        if supervise:
            name += f"_supervised_chunk{chunk or 1}"
            if adaptive:
                name += "_adaptive"
        if mjms:
            name += f"_mj{mjms}ms"
    else:
        if fault_records:
            raise SystemExit(
                "--faults is only wired for BENCH_WORKLOAD=phold")
        if replicas > 1:
            raise SystemExit(
                "BENCH_REPLICAS is only wired for BENCH_WORKLOAD=phold; "
                "a pingpong run would silently measure one replica "
                "under an unlabeled metric name")
        if _bench_flow_sample() > 0:
            raise SystemExit("BENCH_FLOW_SAMPLE is only wired for the "
                             "phold/injection runners")
        if _bench_causality_sample() > 0:
            raise SystemExit("BENCH_CAUSALITY is only wired for the "
                             "phold/injection runners")
        runner = _pingpong_runner(H, sim_s)
        name = f"events_per_sec_per_chip@{H}hosts_udp_pingpong"
    if topo == "ref":
        name += "_reftopo"
    elif topo == "mix":
        name += "_mixtopo"
    if fault_records:
        name += "_faults"
    if _SHARDS > 1:
        name += f"_{_SHARDS}shards"
    flow_sample_n = _bench_flow_sample()
    if flow_sample_n > 0:
        # the flow ring shapes the program, so flow rows bank under
        # their own metric name — bench_regress compares like with like
        name += f"_flow{flow_sample_n}"
    if os.environ.get("BENCH_FLOW_OVERHEAD") == "1" \
            and flow_sample_n <= 0:
        raise SystemExit("BENCH_FLOW_OVERHEAD=1 needs "
                         "BENCH_FLOW_SAMPLE=N (what would it A/B?)")
    spec_on = _bench_specialize()
    if spec_on and (workload != "phold" or supervise or inject_on):
        raise SystemExit(
            "BENCH_SPECIALIZE=1 is only wired for the plain PHOLD "
            "runner (the supervised/injection loops build their own "
            "bundles)")
    if spec_on:
        # the trimmed variant is a DIFFERENT compiled program under
        # its own store key — bank it under its own metric name so
        # bench_regress compares like with like
        name += "_spec"
    caus_sample_n = _bench_causality_sample()
    if caus_sample_n > 0:
        # the causality planes shape the program too — own metric name
        name += f"_caus{caus_sample_n}"
    if os.environ.get("BENCH_CAUSALITY_OVERHEAD") == "1" \
            and caus_sample_n <= 0:
        raise SystemExit("BENCH_CAUSALITY_OVERHEAD=1 needs "
                         "BENCH_CAUSALITY=N (what would it A/B?)")
    sent_on = _bench_sentinel()
    if sent_on and workload != "phold" and not inject_on:
        raise SystemExit("BENCH_SENTINEL=1 is only wired for the "
                         "phold/injection runners")
    if sent_on:
        # the sentinel's digest fold shapes the program — own metric
        # name so bench_regress compares like with like
        name += "_sentinel"
    if os.environ.get("BENCH_SENTINEL_OVERHEAD") == "1" and not sent_on:
        raise SystemExit("BENCH_SENTINEL_OVERHEAD=1 needs "
                         "BENCH_SENTINEL=1 (what would it A/B?)")

    # compile + warm (may escalate capacity). Timed + cache-diffed:
    # compile_s is the wall cost of the first device call, and the
    # cache file-set diff says whether it truly compiled (fresh) or
    # was served from the persistent cache (VERDICT open item 6 —
    # compile accounting must ride the bench line, not folklore).
    cache_before = _cache_files()
    t0 = time.perf_counter()
    runner()
    compile_s = time.perf_counter() - t0
    cache_after = _cache_files()
    compile_fresh = (cache_before is None
                     or bool((cache_after or set()) - cache_before))
    # the warm-up call's program-store block (compile/serve.py): on a
    # fresh store this is the miss that paid lower_s+compile_s; the
    # TIMED call below re-resolves the same key and its block records
    # the cached cost (hit=true, load_s) — both ride the banked row
    warmup_cinfo = dict(getattr(runner, "last_compile", None) or {})
    while True:
        t0 = time.perf_counter()
        events = runner()         # timed (compile cached)
        wall = time.perf_counter() - t0
        if not getattr(runner, "escalated", False):
            break                 # a recompile polluted the timing; redo
    total_rate = events / wall
    # per-CHIP metric: a sharded run reports aggregate/shards so the
    # value stays comparable to the 1-chip/1-core baseline (reporting
    # the aggregate under the per-chip name would inflate vs_baseline
    # by the shard count)
    value = total_rate / _SHARDS if _SHARDS > 1 else total_rate

    # BENCH_FLOW_OVERHEAD=1: rebuild the SAME workload with the flow
    # ring off, time it the same way, and score the ring's cost as
    # (off - on) / off. Positive = the ring costs throughput;
    # acceptance is <=5% at the default 1-in-64 sampling.
    flow_overhead_pct = None
    value_flow_off = None
    if os.environ.get("BENCH_FLOW_OVERHEAD") == "1" \
            and flow_sample_n > 0:
        if inject_on:
            base = _inject_runner(
                H, sim_s, shards=_SHARDS, graph=graph,
                trace_path=inj_trace, rate=inj_rate,
                fault_records=fault_records, chunk_windows=chunk,
                adaptive_jump=adaptive, min_jump_ns=min_jump_ns,
                checkpoint_windows=ck_w, flow_sample=0)
        elif supervise:
            base = _phold_supervised_runner(
                H, load, sim_s, shards=_SHARDS, graph=graph,
                fault_records=fault_records, chunk_windows=chunk,
                adaptive_jump=adaptive, min_jump_ns=min_jump_ns,
                checkpoint_windows=ck_w, flow_sample=0)
        else:
            base = _phold_runner(
                H * replicas, load, sim_s, shards=_SHARDS, graph=graph,
                replica_size=H if replicas > 1 else None,
                fault_records=fault_records,
                active_hosts=active, sparse_lanes=sparse,
                min_jump_ns=min_jump_ns, flow_sample=0)
        base()                     # warm-up (compile, maybe escalate)
        while True:
            t0 = time.perf_counter()
            ev_off = base()
            wall_off = time.perf_counter() - t0
            if not getattr(base, "escalated", False):
                break
        rate_off = ev_off / wall_off
        value_flow_off = (rate_off / _SHARDS if _SHARDS > 1
                          else rate_off)
        flow_overhead_pct = round(
            (value_flow_off - value) / value_flow_off * 100.0, 2)

    # BENCH_CAUSALITY_OVERHEAD=1: same A/B for the lineage recorder —
    # rebuild with the causality planes off (every other knob
    # unchanged, so the delta IS the recorder), time it, score the
    # cost as (off - on) / off. Acceptance: <=5% at the default
    # 1-in-64 sampling; tools/bench_regress.py gates the bound.
    causality_overhead_pct = None
    value_caus_off = None
    if os.environ.get("BENCH_CAUSALITY_OVERHEAD") == "1" \
            and caus_sample_n > 0:
        if inject_on:
            base = _inject_runner(
                H, sim_s, shards=_SHARDS, graph=graph,
                trace_path=inj_trace, rate=inj_rate,
                fault_records=fault_records, chunk_windows=chunk,
                adaptive_jump=adaptive, min_jump_ns=min_jump_ns,
                checkpoint_windows=ck_w, causality_sample=0)
        elif supervise:
            base = _phold_supervised_runner(
                H, load, sim_s, shards=_SHARDS, graph=graph,
                fault_records=fault_records, chunk_windows=chunk,
                adaptive_jump=adaptive, min_jump_ns=min_jump_ns,
                checkpoint_windows=ck_w, causality_sample=0)
        else:
            base = _phold_runner(
                H * replicas, load, sim_s, shards=_SHARDS, graph=graph,
                replica_size=H if replicas > 1 else None,
                fault_records=fault_records,
                active_hosts=active, sparse_lanes=sparse,
                min_jump_ns=min_jump_ns, causality_sample=0)
        base()                     # warm-up (compile, maybe escalate)
        while True:
            t0 = time.perf_counter()
            ev_off = base()
            wall_off = time.perf_counter() - t0
            if not getattr(base, "escalated", False):
                break
        rate_off = ev_off / wall_off
        value_caus_off = (rate_off / _SHARDS if _SHARDS > 1
                          else rate_off)
        causality_overhead_pct = round(
            (value_caus_off - value) / value_caus_off * 100.0, 2)

    # BENCH_SENTINEL_OVERHEAD=1: same A/B for the integrity sentinel —
    # rebuild with the sentinel detached (every other knob unchanged,
    # so the delta IS the per-barrier digest + pmax/pmin compare),
    # time it, score the cost as (off - on) / off. Acceptance: <5%
    # (design goal <2%); tools/bench_regress.py gates the bound.
    sentinel_overhead_pct = None
    value_sent_off = None
    if os.environ.get("BENCH_SENTINEL_OVERHEAD") == "1" and sent_on:
        if inject_on:
            base = _inject_runner(
                H, sim_s, shards=_SHARDS, graph=graph,
                trace_path=inj_trace, rate=inj_rate,
                fault_records=fault_records, chunk_windows=chunk,
                adaptive_jump=adaptive, min_jump_ns=min_jump_ns,
                checkpoint_windows=ck_w, sentinel=False)
        elif supervise:
            base = _phold_supervised_runner(
                H, load, sim_s, shards=_SHARDS, graph=graph,
                fault_records=fault_records, chunk_windows=chunk,
                adaptive_jump=adaptive, min_jump_ns=min_jump_ns,
                checkpoint_windows=ck_w, sentinel=False)
        else:
            base = _phold_runner(
                H * replicas, load, sim_s, shards=_SHARDS, graph=graph,
                replica_size=H if replicas > 1 else None,
                fault_records=fault_records,
                active_hosts=active, sparse_lanes=sparse,
                min_jump_ns=min_jump_ns, sentinel=False)
        base()                     # warm-up (compile, maybe escalate)
        while True:
            t0 = time.perf_counter()
            ev_off = base()
            wall_off = time.perf_counter() - t0
            if not getattr(base, "escalated", False):
                break
        rate_off = ev_off / wall_off
        value_sent_off = (rate_off / _SHARDS if _SHARDS > 1
                          else rate_off)
        sentinel_overhead_pct = round(
            (value_sent_off - value) / value_sent_off * 100.0, 2)

    # BENCH_SPECIALIZE=1: time the unspecialized twin of the SAME
    # workload (every other knob unchanged, so the delta IS the
    # trimmed subgraphs) and score specialize_speedup =
    # rate_trimmed / rate_full. >1.0 means the trim pays; the
    # regression gate tracks the trajectory once banked.
    specialize_speedup = None
    value_spec_off = None
    if spec_on:
        base = _phold_runner(
            H * replicas, load, sim_s, shards=_SHARDS, graph=graph,
            replica_size=H if replicas > 1 else None,
            fault_records=fault_records,
            active_hosts=active, sparse_lanes=sparse,
            min_jump_ns=min_jump_ns, specialize=False)
        base()                     # warm-up (compile, maybe escalate)
        while True:
            t0 = time.perf_counter()
            ev_off = base()
            wall_off = time.perf_counter() - t0
            if not getattr(base, "escalated", False):
                break
        rate_off = ev_off / wall_off
        value_spec_off = (rate_off / _SHARDS if _SHARDS > 1
                          else rate_off)
        specialize_speedup = round(value / value_spec_off, 4)

    # compare against the measured baseline AT THE SAME SCALE (the
    # C pthread heap-skeleton upper bound, BASELINE.md): the published
    # block carries per-scale numbers because the heap baseline slows
    # as hosts grow (cache misses) while the device engine speeds up
    # (more lanes).
    baseline = 0.0
    try:
        with open(os.path.join(os.path.dirname(__file__),
                               "BASELINE.json")) as f:
            pub = json.load(f)["published"]
        if H >= 100_000:
            baseline = float(pub.get("events_per_sec_at_100k_hosts", 0.0))
        elif H >= 10_000:
            baseline = float(pub.get("events_per_sec_at_10k_hosts", 0.0))
        else:
            baseline = float(pub.get("events_per_sec", 0.0))
    except Exception:
        pass
    vs = value / baseline if baseline else 0.0

    out = {
        "metric": name,
        "value": round(value, 1),
        "unit": "events/s",
        "vs_baseline": round(vs, 3),
        "backend": jax.default_backend(),
        "compile_s": round(compile_s, 3),
        "compile_cache": "fresh" if compile_fresh else "cached",
    }
    if _SHARDS > 1:
        out["shards"] = _SHARDS
        out["total_events_per_sec"] = round(total_rate, 1)
    # chunked-dispatch accounting (supervised loop only): the JSON row
    # and the embedded manifest both carry the dispatch shape so the
    # sweep's banked lines are self-describing (tools/telemetry_lint)
    disp = None
    if (supervise or inject_on) \
            and getattr(runner, "last_result", None) is not None:
        r = runner.last_result
        wpd = chunk or 1
        disp = {"windows_per_dispatch": wpd,
                "dispatches": r.dispatches}
        if (wpd > 1 and r.dispatch_windows and r.attempts == 1
                and r.resume_of is None):
            disp["windows"] = list(r.dispatch_windows)
        if adaptive and getattr(runner, "harvester", None) is not None:
            m = runner.harvester.mean_window_ns()
            if m is not None:
                disp["adaptive_jump_mean_ns"] = round(m, 1)
        out["windows_per_dispatch"] = wpd
        out["dispatches"] = r.dispatches
        if "adaptive_jump_mean_ns" in disp:
            out["adaptive_jump_mean_ns"] = disp["adaptive_jump_mean_ns"]
    # program-store accounting (compile/): the TIMED call's block,
    # with the warm-up call's miss nested under "warmup" so one row
    # scores cached-vs-fresh (warm_speedup = fresh compile wall over
    # warm load wall — the ISSUE's ≥10x acceptance ratio)
    cinfo = dict(getattr(runner, "last_compile", None) or {})
    if warmup_cinfo and warmup_cinfo != cinfo:
        cinfo["warmup"] = warmup_cinfo
    plan = getattr(runner, "bucket_plan", None)
    if plan is not None:
        cinfo["buckets"] = plan.as_dict()
    if cinfo.get("hit") and cinfo.get("load_s"):
        fresh_s = ((cinfo.get("warmup") or {}).get("compile_s", 0.0)
                   + (cinfo.get("warmup") or {}).get("lower_s", 0.0))
        if fresh_s:
            cinfo["warm_speedup"] = round(
                fresh_s / max(cinfo["load_s"], 1e-9), 1)
    if cinfo:
        out["compile"] = cinfo
    if getattr(runner, "last_sim", None) is not None and (
            getattr(runner.last_sim, "telem", None) is not None):
        # per-window stats from the device telemetry ring of the TIMED
        # run, plus the run manifest (telemetry/export.py)
        from shadow_tpu import telemetry

        h = getattr(runner, "harvester", None)
        if h is None:
            h = telemetry.Harvester()
            h.drain(runner.last_sim)
        tel = h.summary()
        if "events_per_window" in tel:
            out["events_per_window"] = {
                k: round(v, 2)
                for k, v in tel["events_per_window"].items()}
        windows = int(runner.last_stats.windows)
        if windows:
            # wall clock is host-side and covers the whole program, so
            # only the mean is derivable (the ring's sim-time records
            # carry no wall timestamps — the device cannot read a
            # clock); percentiles here would be fabricated
            out["wallclock_per_window_ms"] = round(
                wall * 1000.0 / windows, 4)
        b = runner.state["bundle"]
        inj_blk = None
        if getattr(runner.last_sim, "inject", None) is not None:
            from shadow_tpu import inject as inject_mod

            inj_blk = inject_mod.manifest_block(
                runner.last_sim, getattr(runner, "last_feeder", None))
            out["injected"] = inj_blk["injected"]
        out["manifest"] = telemetry.run_manifest(
            cfg=b.cfg, seed=b.cfg.seed, shards=max(_SHARDS, 1),
            sim=runner.last_sim, stats=runner.last_stats,
            harvester=h, wall_seconds=wall,
            compile_s=compile_s, compile_fresh=compile_fresh,
            fault_plan=getattr(b, "fault_plan", None),
            dispatch=disp, injection=inj_blk,
            compile_info=cinfo or None,
            specialization=_spec_block(
                getattr(b, "caps", None), runner.last_sim))
    if flow_sample_n > 0 and getattr(runner, "last_sim", None) is not None \
            and getattr(runner.last_sim, "flows", None) is not None:
        # flow-latency accounting of the TIMED run: counters + per-lane
        # summary on the row, the full histogram block in the manifest
        from shadow_tpu import telemetry
        from shadow_tpu.telemetry.flows import flows_manifest_block

        fh = getattr(runner, "harvester", None)
        if fh is None:
            fh = telemetry.Harvester()
        fh.drain(runner.last_sim)
        fb = flows_manifest_block(
            fh, num_hosts=runner.state["bundle"].cfg.num_hosts,
            shards=max(_SHARDS, 1), sample_period=flow_sample_n)
        if fb is not None:
            out["flows"] = {k: fb[k] for k in
                            ("sample_period", "sampled", "recorded",
                             "harvested", "lost_ring",
                             "lost_window_clamp", "per_lane")}
            if "manifest" in out:
                out["manifest"]["flows"] = fb
    if caus_sample_n > 0 \
            and getattr(runner, "last_sim", None) is not None \
            and getattr(runner.last_sim, "causality", None) is not None:
        # causal-attribution accounting of the TIMED run: counters +
        # binding-cause histogram on the row, the full block (chains,
        # advances, utilization percentiles) in the manifest — the
        # input tools/critpath.py reads
        from shadow_tpu import telemetry
        from shadow_tpu.telemetry.causality import (
            causality_manifest_block)

        ch = getattr(runner, "harvester", None)
        if ch is None:
            ch = telemetry.Harvester()
        ch.drain(runner.last_sim)
        cb = causality_manifest_block(
            ch, num_hosts=runner.state["bundle"].cfg.num_hosts,
            shards=max(_SHARDS, 1), sample_period=caus_sample_n)
        if cb is not None:
            out["causality"] = {
                k: cb[k] for k in
                ("sample_period", "sampled", "harvested", "lost_ring",
                 "windows_attributed", "windows_lost", "causes")
                if k in cb}
            if "manifest" in out:
                out["manifest"]["causality"] = cb
    if flow_overhead_pct is not None:
        out["flow_overhead_pct"] = flow_overhead_pct
        out["events_per_sec_flow_off"] = round(value_flow_off, 1)
    if causality_overhead_pct is not None:
        out["causality_overhead_pct"] = causality_overhead_pct
        out["events_per_sec_causality_off"] = round(value_caus_off, 1)
    if sent_on and getattr(runner, "last_sim", None) is not None:
        # sentinel latch report of the TIMED run (row + manifest): the
        # lint validates it (trips <= checks, a trip names its shard)
        from shadow_tpu.parallel import elastic as elastic_mod

        srep = elastic_mod.sentinel_report(runner.last_sim)
        if srep is not None:
            out["sentinel"] = dict(srep)
            if "manifest" in out:
                out["manifest"]["sentinel"] = dict(srep)
    if sentinel_overhead_pct is not None:
        out["sentinel_overhead_pct"] = sentinel_overhead_pct
        out["events_per_sec_sentinel_off"] = round(value_sent_off, 1)
        if "manifest" in out and "sentinel" in out["manifest"]:
            out["manifest"]["sentinel"]["overhead_pct"] = \
                sentinel_overhead_pct
    if specialize_speedup is not None:
        out["specialize_speedup"] = specialize_speedup
        out["events_per_sec_full_program"] = round(value_spec_off, 1)
        caps = getattr(runner.state["bundle"], "caps", None) \
            if getattr(runner, "state", None) is not None else None
        if caps is not None:
            out["specialization"] = {"dropped": list(caps.dropped()),
                                     "key_extra": caps.key_extra()}
    # BENCH_PROFILE_DIR: capture ONE extra, unscored run, after every
    # export has read the timed run's state. Tracing costs wall time
    # (observed: an order of magnitude on small CPU shapes), so it
    # must never bracket the run whose events/s banks.
    prof_dir = os.environ.get("BENCH_PROFILE_DIR")
    if prof_dir:
        prof_on = False
        try:
            os.makedirs(prof_dir, exist_ok=True)
            jax.profiler.start_trace(prof_dir)
            prof_on = True
            runner()
        except Exception as e:
            import sys

            print(f"WARNING: BENCH_PROFILE_DIR: profiler unavailable "
                  f"({e}); continuing without capture", file=sys.stderr)
        finally:
            if prof_on:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
                out["profile"] = {"dir": os.path.abspath(prof_dir),
                                  "tool": "jax.profiler"}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
