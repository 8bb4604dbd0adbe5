"""Build a runnable simulation from topology + host specs.

This is the device-era analog of the reference's startup path
(ref: master.c:161-398 / slave.c:296-336): load + validate topology,
register every host with DNS, attach hosts to vertices via the hint
rules, derive the conservative window from the minimum path latency,
and initialize the struct-of-arrays device state. Process starts are
seeded as PROC_START events (ref: process.c:1326-1360).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from shadow_tpu.core import simtime
from shadow_tpu.core.engine import (
    EngineStats,
    _default_route,
    make_chunk_body,
    make_wend_fn,
    resolve_sparse_lanes,
)
from shadow_tpu.core.engine import run as engine_run
from shadow_tpu.core.events import EventKind, emit_words, push_rows
from shadow_tpu.parallel.elastic import make_sentinel_fn
from shadow_tpu.telemetry.flows import make_flow_fn
from shadow_tpu.telemetry.ring import make_telem_fn
from shadow_tpu.net.state import (
    NetConfig,
    NetState,
    Sim,
    make_net_state,
    make_sim,
)
from shadow_tpu.net.step import make_step_fn
from shadow_tpu.routing.dns import DNS
from shadow_tpu.routing.graphml import parse_graphml
from shadow_tpu.routing.topology import Topology


@dataclass
class HostSpec:
    """One virtual host (ref: <host> config element,
    configuration.h:62-101)."""

    name: str
    ip: str | None = None            # requested IP hint
    citycode: str | None = None
    countrycode: str | None = None
    geocode: str | None = None
    type: str | None = None
    bandwidthdown: int | None = None  # KiB/s override
    bandwidthup: int | None = None
    cpufrequency_khz: int | None = None  # virtual CPU speed (ref:
                                         # host cpufrequency attr)
    proc_start_time: int | None = None  # PROC_START event time (ns)
    proc_stop_time: int | None = None   # PROC_STOP event time (ns)
                                        # (ref: <process stoptime>,
                                        # process.c:1286-1324)

    def hints(self) -> dict:
        out: dict = {}
        for k in ("ip", "citycode", "countrycode", "geocode", "type"):
            v = getattr(self, k)
            if v is not None:
                out[k] = v
        for k in ("bandwidthdown", "bandwidthup"):
            v = getattr(self, k)
            if v is not None:
                out[k] = v
        return out


@dataclass
class SimBundle:
    cfg: NetConfig
    sim: Sim
    topology: Topology
    dns: DNS
    min_jump: int
    host_names: list[str]
    name_to_index: dict[str, int] = field(default_factory=dict)
    # Optional net.bulk.AppBulk installed by the configured app model
    # (config/loader.py): turns on the bulk window pass wherever the
    # bundle is run (CLI serial, sharded, bench).
    app_bulk: Any = None
    # Optional faults.plan.FaultPlan attached by faults.install():
    # runners derive the window-boundary fault_fn from it (and the
    # boot sim) via faults.fault_fn_for(bundle).
    fault_plan: Any = None
    # Optional rebuild(overrides: dict) -> SimBundle installed by
    # config/loader.py: re-run the whole load (topology, app setup,
    # fault install) with capacity overrides merged in. This is the
    # escalation path's lever (faults/escalate.py) — a grown capacity
    # needs a fresh Sim AND fresh step/fault closures, because every
    # compiled function shape-specializes on the boot arrays.
    rebuild: Any = None
    # Optional compile/specialize.Capabilities attached by
    # specialize.apply(): the runner factories below thread it into
    # the step/bulk builders (dead subgraphs are omitted from the
    # trace) and fold it into the program key when anything was
    # dropped. None = full (unspecialized) program. Escalation regrow
    # must re-derive it (a rebuilt bundle starts unspecialized).
    caps: Any = None

    def ip_of(self, name: str) -> int:
        return self.dns.resolve_name(name).ip

    def host_of(self, name: str) -> int:
        return self.name_to_index[name]


def build(cfg: NetConfig, graphml_text: str, hosts: Sequence[HostSpec],
          app: Any = None) -> SimBundle:
    if len(hosts) != cfg.num_hosts:
        raise ValueError(f"cfg.num_hosts={cfg.num_hosts} != {len(hosts)} specs")
    top = Topology(parse_graphml(graphml_text))
    dns = DNS()
    names = []
    for i, h in enumerate(hosts):
        dns.register(i, h.name, requested_ip=h.ip)
        names.append(h.name)

    # attach draws come from the deterministic seed hierarchy
    # (ref: master.c:417 -> slave.c:301): one uniform per host in
    # registration order.
    draws = np.random.default_rng(cfg.seed).random(len(hosts))
    placement = top.attach_hosts([h.hints() for h in hosts], draws)
    min_jump = top.min_jump_ns(placement)

    # Sequentially-allocated IPs (no config pinned an address out of
    # order) unlock the arithmetic IP fast path in the bulk passes
    # (state.ip_of_hosts) — detected here where the table is still
    # host-side numpy, and threaded through the bundle's cfg so every
    # step/bulk function built from it agrees.
    host_ips = dns.host_ips(cfg.num_hosts)
    if cfg.num_hosts and np.array_equal(
            host_ips, host_ips[0] + np.arange(cfg.num_hosts)):
        from dataclasses import replace as _dc_replace
        cfg = _dc_replace(cfg, ip_affine_base=int(host_ips[0]))

    net = make_net_state(
        cfg,
        host_ips=host_ips,
        bw_up_kibps=placement.bw_up_kibps,
        bw_down_kibps=placement.bw_down_kibps,
        vertex_of_host=placement.vertex,
        latency_ns=top.latency_ns,
        reliability=top.reliability,
        cpu_freq_khz=np.array(
            [h.cpufrequency_khz or 0 for h in hosts], np.int64),
    )
    sim = make_sim(cfg, net, app=app)

    # seed PROC_START / PROC_STOP events (ref: host_boot ->
    # process_schedule, process.c:1326-1360)
    H = cfg.num_hosts
    for attr, kind in ((lambda h: h.proc_start_time, EventKind.PROC_START),
                       (lambda h: h.proc_stop_time, EventKind.PROC_STOP)):
        times = np.full(cfg.num_hosts, -1, dtype=np.int64)
        for i, h in enumerate(hosts):
            t = attr(h)
            if t is not None:
                times[i] = t
        m = times >= 0
        if m.any():
            q = push_rows(
                sim.events,
                jnp.asarray(m),
                jnp.asarray(np.where(m, times, 0), simtime.DTYPE),
                jnp.full((H,), kind, jnp.int32),
                jnp.arange(H, dtype=jnp.int32),
                sim.events.next_seq,
                emit_words(0, num_hosts=H),
            )
            q = q.replace(next_seq=q.next_seq + jnp.asarray(m, jnp.int32))
            sim = sim.replace(events=q)

    return SimBundle(
        cfg=cfg, sim=sim, topology=top, dns=dns, min_jump=min_jump,
        host_names=names, name_to_index={n: i for i, n in enumerate(names)},
    )


def _resolve_bulk_fn(bundle: SimBundle, app_bulk, app_tcp_bulk,
                     tcp_bulk_lossless: bool = False, caps=None):
    """One bulk-pass selection rule for every runner flavor (the UDP
    bulk wins when both are given; make_bulk_fn's order_impl is a
    separate knob with its own vocabulary, not forwarded).
    tcp_bulk_lossless compiles the narrow loss-free TCP pass — see
    make_tcp_bulk_fn (bit-identical for any workload; faster when the
    workload is genuinely artifact-free). `caps` is the bundle's
    capability vector (compile/specialize.py) — the bulk builders trim
    their reliability-draw subgraphs under it."""
    if app_bulk is not None:
        from shadow_tpu.net.bulk import make_bulk_fn

        fn = make_bulk_fn(bundle.cfg, app_bulk, caps=caps)
        if fn is not None:
            return fn
    if app_tcp_bulk is not None:
        from shadow_tpu.net.tcp_bulk import make_tcp_bulk_fn

        return make_tcp_bulk_fn(bundle.cfg, app_tcp_bulk,
                                lossless=tcp_bulk_lossless, caps=caps)
    return None


def _resolve_caps(bundle: SimBundle, caller_fault_fn):
    """The capability vector a runner may trim under. An explicit
    caller fault_fn is OPAQUE — its closure could rewrite any table
    (e.g. re-introduce loss) invisibly to the static analysis — so it
    disables specialization exactly like it disables warm serving
    (_whole_run_key_fn). The installed-plan path (bundle.fault_plan)
    stays trimmable: derive() already folded the plan's record kinds
    into the vector."""
    caps = getattr(bundle, "caps", None)
    if caller_fault_fn is not None:
        if caps is not None and caps.dropped():
            # the specialized sim already carries the guard latch —
            # running it under a full (untrimmed) program would turn
            # any table rewrite by this opaque fault_fn into a false
            # fatal. Refuse loudly instead of mis-reporting.
            raise ValueError(
                "explicit fault_fn on a specialized bundle: an opaque "
                "fault rule defeats the static capability analysis — "
                "rebuild with specialize.apply(mode='off') or install "
                "the plan via faults.install()")
        return None
    return caps


def _caps_meta(caps):
    """Store-sidecar block for a trimmed program (compcache_ctl ls
    shows it next to the bucket plan); None when nothing was dropped
    so untrimmed sidecars are unchanged."""
    if caps is None or not caps.dropped():
        return None
    return {"specialization": caps.as_dict()}


def _resolve_fault_fn(bundle: SimBundle, fault_fn):
    """Every runner flavor applies a bundle's installed fault plan by
    default — a config-driven schedule must hold wherever the bundle
    runs (serial, chunked, sharded, bench). An explicit fault_fn
    overrides."""
    if fault_fn is not None:
        return fault_fn
    if getattr(bundle, "fault_plan", None) is not None:
        from shadow_tpu.faults.apply import fault_fn_for

        return fault_fn_for(bundle)
    return None


def adaptive_jump_spec(bundle: SimBundle):
    """Constants for the adaptive time jump (engine.make_wend_fn):
    ``(pair_mask, fault_times)``.

    pair_mask is the [V,V] bool set of vertex pairs that constrain the
    conservative window — ordered pairs of distinct host-bearing
    vertices, plus the self-path of any vertex carrying >= 2 hosts —
    exactly topology.min_jump_ns's pair rules, but evaluated on device
    against the LIVE latency/reliability tables each window instead of
    once at boot. fault_times is the installed plan's record times
    (None when no plan): wend clamps to the next record so every fault
    still materializes at a window boundary."""
    voh = np.asarray(bundle.sim.net.vertex_of_host)
    V = int(np.asarray(bundle.sim.net.latency_ns).shape[0])
    mask = np.zeros((V, V), dtype=bool)
    if voh.size:
        verts, counts = np.unique(voh, return_counts=True)
        mask[np.ix_(verts, verts)] = True
        mask[np.arange(V), np.arange(V)] = False
        for v, c in zip(verts, counts):
            if c >= 2:
                mask[v, v] = True
    return mask, plan_times(bundle)


def plan_times(bundle: SimBundle):
    """The installed fault plan's unique record times (None without a
    plan) — the wend clamp every window rule shares so records land at
    window boundaries exactly (engine.make_wend_fn / engine.run)."""
    plan = getattr(bundle, "fault_plan", None)
    if plan is not None and getattr(plan, "n", 0):
        return np.unique(np.asarray(plan.t_ns, np.int64))
    return None


def resolve_wend_fn(bundle: SimBundle, end_time: int, adaptive: bool,
                    fault_fn=None):
    """One window-end rule for every chunked runner: the reference's
    static ``wstart + min_jump`` (adaptive=False), or the live-table
    adaptive jump. `fault_fn` is the rule the runner resolved (post
    _resolve_fault_fn): adaptive mode needs the fault schedule's
    record times to stay conservative, so an opaque fault_fn with no
    installed plan is rejected — it could revive a short link in the
    middle of a window that was sized without it. Both modes clamp
    wend at the next record time so faults apply exactly on schedule
    and the executed event stream is invariant to the window
    partitioning (static vs adaptive, any windows_per_dispatch)."""
    if not adaptive:
        return make_wend_fn(min_jump=bundle.min_jump, end_time=end_time,
                            fault_times=plan_times(bundle))
    if fault_fn is not None and getattr(bundle, "fault_plan", None) is None:
        raise ValueError(
            "adaptive_jump requires the fault plan's record times "
            "(faults.install) — cannot bound an opaque fault_fn's "
            "table rewrites")
    mask, ft = adaptive_jump_spec(bundle)
    tf = None
    if getattr(bundle, "fault_plan", None) is not None:
        from shadow_tpu.faults.apply import make_table_fn

        # Size windows from the plan replay at wstart + 1, never the
        # live sim tables: step_window rewrites those only after the
        # span is chosen, so a window starting exactly at a restore
        # record would see the stale pre-restore latency (see
        # make_wend_fn's guard list).
        tf = make_table_fn(bundle.fault_plan, bundle.sim)
    return make_wend_fn(min_jump=bundle.min_jump, end_time=end_time,
                        pair_mask=mask, fault_times=ft, table_fn=tf)


def _whole_run_key_fn(bundle: SimBundle, app_handlers, *, end, path,
                      chunk_windows, adaptive, fault_fn, app_bulk,
                      app_tcp_bulk, tcp_bulk_lossless=False,
                      route_impl=None, shards=1,
                      exchange_capacity=None, caps=None):
    """Lazy program-key rule for the whole-run factories (compile/):
    the shape vector comes from the FIRST call's sim (telemetry /
    lane / injection attachments change the traced pytree, and the
    factory's callable accepts any of them), everything else is fixed
    at factory time. Returns None — warm serving disabled — when the
    caller passed an opaque fault_fn: its closure constants are baked
    into the trace but invisible to the key."""
    if fault_fn is not None:
        return None

    def _key(args, kwargs):
        from shadow_tpu.compile import buckets
        from shadow_tpu.telemetry.export import fault_plan_digest

        fp = getattr(bundle, "fault_plan", None)
        extra = {"path": path, "route_impl": route_impl,
                 "tcp_bulk_lossless": bool(tcp_bulk_lossless),
                 "tcp_bulk": (type(app_tcp_bulk).__name__
                              if app_tcp_bulk is not None else None)}
        if caps is not None and caps.key_extra() is not None:
            # trimmed variants are DIFFERENT executables — key them
            # apart so they coexist in the store next to their full
            # twins. Untrimmed specialized builds contribute nothing:
            # their program is byte-identical to the unspecialized one
            # and must share its key (and its warm artifacts).
            extra["caps"] = caps.key_extra()
        census = buckets.kind_census(
            app_handlers, app_bulk,
            fault_plan_digest=(fault_plan_digest(fp)
                               if fp is not None else None))
        shapes = buckets.shape_vector_for_sim(bundle.cfg, args[0])
        return buckets.program_key(
            shapes, shards=int(shards), chunk_windows=chunk_windows,
            adaptive=adaptive, census=census, end_time=int(end),
            min_jump=bundle.min_jump,
            exchange_capacity=exchange_capacity, extra=extra)

    return _key


def make_runner(bundle: SimBundle, app_handlers=(),
                end_time: int | None = None, app_bulk=None,
                app_tcp_bulk=None,
                route_impl: str | None = None,
                tcp_bulk_lossless: bool = False,
                fault_fn=None, warm_start: bool | None = None,
                compile_info: dict | None = None):
    """Build a jitted sim -> (sim, stats) callable for the whole run.
    Reuse it across calls: tracing the full netstack in Python costs
    seconds per call at this op count; a reused jitted callable pays
    it once and then hits the C++ dispatch fast path (this is what a
    benchmark's timed iteration must call).

    `app_bulk` (a net.bulk.AppBulk) turns on the bulk window pass:
    eligible hosts' whole windows are consumed in one vectorized pass
    per window instead of one micro-step per event, bit-identically
    (see net/bulk.py).

    `route_impl` ("sort"/"count") overrides the outbox-insert
    mechanism when the arrays live on a different backend than
    jax.default_backend() — e.g. CPU-pinned state on a TPU host
    (values are bit-identical either way; perf-only, mirrors
    make_bulk_fn's order_impl). "sort2" is also accepted but must NOT
    be used as an off-backend override on a TPU host: its Pallas
    mailbox kernel is gated on jax.default_backend() at trace time
    (array placement is unknowable under jit), so tracing it against
    CPU-pinned state would compile the TPU-only kernel. Use "sort"
    for CPU-pinned overrides.

    `warm_start` serves the program from the persistent AOT store
    (compile/) — a stored program for this shape loads without
    retracing the netstack; SHADOW_WARM_PROGRAMS overrides, and
    `compile_info` (a dict) receives the {key, hit, load_s|compile_s}
    block at the first call."""
    caller_fault_fn = fault_fn
    caps = _resolve_caps(bundle, caller_fault_fn)
    step = make_step_fn(bundle.cfg, app_handlers, caps=caps)
    end = end_time if end_time is not None else bundle.cfg.end_time
    bulk_fn = _resolve_bulk_fn(bundle, app_bulk, app_tcp_bulk,
                               tcp_bulk_lossless, caps=caps)
    fault_fn = _resolve_fault_fn(bundle, fault_fn)
    route_fn = _default_route
    if route_impl is not None:
        from shadow_tpu.core.events import route_outbox

        def route_fn(sim):
            q, out = route_outbox(sim.events, sim.outbox, impl=route_impl)
            return sim.replace(events=q, outbox=out)

    # trace-time no-ops unless telemetry.attach()ed /
    # telemetry.attach_flows()ed to the input sim
    telem_fn = make_telem_fn()
    flow_fn = make_flow_fn()

    def _go(sim):
        return engine_run(
            sim, step, end_time=end, min_jump=bundle.min_jump,
            emit_capacity=bundle.cfg.emit_capacity,
            lane_id=sim.net.lane_id,
            route_fn=route_fn,
            bulk_fn=bulk_fn,
            fault_fn=fault_fn,
            telem_fn=telem_fn,
            flow_fn=flow_fn,
            sparse_lanes=resolve_sparse_lanes(bundle.cfg),
            fault_times=plan_times(bundle),
            # serial identity sentinel: never trips, but advances the
            # verified-through ledger (trace-time no-op when off)
            sentinel_fn=make_sentinel_fn(None),
        )

    from shadow_tpu.compile import serve

    return serve.maybe_warm(
        jax.jit(_go),
        _whole_run_key_fn(bundle, app_handlers, end=end, path="whole",
                          chunk_windows=0, adaptive=False,
                          fault_fn=caller_fault_fn, app_bulk=app_bulk,
                          app_tcp_bulk=app_tcp_bulk,
                          tcp_bulk_lossless=tcp_bulk_lossless,
                          route_impl=route_impl, caps=caps),
        enabled=serve.warm_enabled(default=bool(warm_start)),
        meta=_caps_meta(caps),
        info=compile_info)


def make_chunked_runner(bundle: SimBundle, app_handlers=(),
                        end_time: int | None = None, app_bulk=None,
                        app_tcp_bulk=None, chunk_windows: int = 256,
                        tcp_bulk_lossless: bool = False,
                        fault_fn=None, adaptive_jump: bool = False,
                        warm_start: bool | None = None,
                        compile_info: dict | None = None):
    """make_runner variant that executes `chunk_windows` windows per
    device call with a host-side outer loop — window-for-window the
    SAME sequence engine.run's single while_loop produces (advance
    rule newStart = minNext, master.c:450-480), so results are
    bit-identical.

    Why it exists: one device call covering a whole long simulation
    (the real-topology regime: 200 windows per sim-second) can exceed
    a backend's per-execution limits (observed on a v5e before PR 1:
    relay runs on the reference topology died with UNAVAILABLE while
    the identical computation split into shorter calls completed).
    Chunking bounds single-call execution time at a few hundred
    windows and costs one dispatch per chunk.

    The host loop is pipelined: one speculative chunk is always in
    flight, and the loop only synchronizes on the PREVIOUS chunk's
    wstart while the next executes (a chunk dispatched past the end is
    a no-op — make_chunk_body guards every window on wstart <= end).
    The sim pytree is donated to each dispatch, so steady-state device
    allocation is one sim regardless of chunk count; the caller's
    input sim is copied once at entry and stays intact.

    `adaptive_jump` swaps the static min_jump window for the
    live-table rule (resolve_wend_fn / engine.make_wend_fn): window
    boundaries then differ from the static run wherever a fault plan
    raised latencies, but the final state is reachable-event
    identical — the conservative window invariant makes results
    independent of the partition into windows."""
    if chunk_windows < 1:
        raise ValueError(
            f"chunk_windows must be >= 1, got {chunk_windows} "
            "(0 iterations would spin the host loop forever)")

    caller_fault_fn = fault_fn
    caps = _resolve_caps(bundle, caller_fault_fn)
    step = make_step_fn(bundle.cfg, app_handlers, caps=caps)
    end = int(end_time if end_time is not None else bundle.cfg.end_time)
    bulk_fn = _resolve_bulk_fn(bundle, app_bulk, app_tcp_bulk,
                               tcp_bulk_lossless, caps=caps)
    fault_fn = _resolve_fault_fn(bundle, fault_fn)
    telem_fn = make_telem_fn()
    wend_fn = resolve_wend_fn(bundle, end, adaptive_jump, fault_fn)

    chunk = make_chunk_body(
        step, end_time=end, wend_fn=wend_fn,
        chunk_windows=int(chunk_windows),
        emit_capacity=bundle.cfg.emit_capacity,
        lane_fn=lambda s: s.net.lane_id,
        bulk_fn=bulk_fn, fault_fn=fault_fn, telem_fn=telem_fn,
        sparse_lanes=resolve_sparse_lanes(bundle.cfg),
        flow_fn=make_flow_fn(), sentinel_fn=make_sentinel_fn(None))
    from shadow_tpu.compile import serve

    k_windows = serve.maybe_warm(
        jax.jit(chunk, donate_argnums=(0,)),
        _whole_run_key_fn(bundle, app_handlers, end=end,
                          path="whole_chunk",
                          chunk_windows=int(chunk_windows),
                          adaptive=bool(adaptive_jump),
                          fault_fn=caller_fault_fn, app_bulk=app_bulk,
                          app_tcp_bulk=app_tcp_bulk,
                          tcp_bulk_lossless=tcp_bulk_lossless,
                          caps=caps),
        enabled=serve.warm_enabled(default=bool(warm_start)),
        meta=_caps_meta(caps),
        info=compile_info)

    def go(sim):
        # Donation consumes the sim argument buffers; copy once so the
        # caller's (usually bundle.sim) survives repeated go() calls.
        sim = jax.tree_util.tree_map(jnp.copy, sim)
        stats = EngineStats.create()
        wstart = jnp.min(sim.events.min_time())
        sim, stats, wstart = k_windows(sim, stats, wstart)
        while True:
            # Keep one chunk in flight: dispatch i+1 on chunk i's
            # as-yet-unresolved outputs, then block on chunk i's
            # wstart alone — the old loop's device_get(wstart) barrier
            # between every chunk left the device idle for a full host
            # round-trip per chunk.
            nsim, nstats, nwstart = k_windows(sim, stats, wstart)
            if int(wstart) > end:
                # Chunk i already ran past the end, so the speculative
                # chunk was a pure no-op: its outputs ARE chunk i's.
                return nsim, nstats
            sim, stats, wstart = nsim, nstats, nwstart

    return go


def run(bundle: SimBundle, app_handlers=(), end_time: int | None = None,
        app_bulk=None):
    """Run the whole simulation on device; returns (sim, stats)."""
    return make_runner(bundle, app_handlers, end_time,
                       app_bulk=app_bulk)(bundle.sim)
