"""The conservative windowed-PDES loop as a device program.

Reference semantics being reproduced (ref: SURVEY.md §3.2):
- All events inside the execution window [wstart, wend) run, one host's
  events serially in (time, src, seq) order, different hosts in
  parallel (ref: scheduler.c:359-414).
- Then a barrier; the next window starts at the global minimum pending
  event time and spans the minimum cross-host latency ("min time
  jump"), so no cross-host packet can violate causality
  (ref: master.c:450-480).

Mechanics here: the per-round worker pop loop becomes a lax.while_loop
of "micro-steps" — each micro-step pops at most one event per host
(a full [H] vector of events) and runs all handlers as masked batch
updates. The round barrier + min-reduction becomes jnp.min over queue
heads (jax.lax.pmin across shards in shadow_tpu.parallel).
"""

from __future__ import annotations

import inspect
from typing import Callable, Protocol

import jax
import jax.numpy as jnp
from flax import struct

from shadow_tpu.core import simtime
from shadow_tpu.core.compact import (
    active_indices,
    gather_lanes,
    scatter_lanes,
)
from shadow_tpu.core.events import (
    EmitBuffer,
    EventQueue,
    Outbox,
    Popped,
    apply_emissions,
    emit_kind_bits,
    kind_census,
    pop_earliest,
    route_outbox,
)

I32 = jnp.int32
I64 = jnp.int64

# Default active-lane budget S for the sparse-window fast path: when
# the global census of rows holding any event < wend fits, the window
# fixpoint runs over a compacted [S]-lane view of the Sim instead of
# all H rows (core/compact.py). 256 holds the config-#2-shaped sparse
# TCP workloads (~28 active of 10,240) with a wide margin while staying
# a single nice tile. NetConfig.sparse_lanes overrides; 0 disables.
DEFAULT_SPARSE_LANES = 256


def resolve_sparse_lanes(cfg) -> int:
    """Effective S for a config: cfg.sparse_lanes (None -> the
    default), forced to 0 (off) when it cannot narrow anything."""
    v = getattr(cfg, "sparse_lanes", None)
    if v is None:
        v = DEFAULT_SPARSE_LANES
    v = int(v)
    if v <= 0 or v >= int(cfg.num_hosts):
        return 0
    return v

# step_fn(sim, popped, emitbuf) -> (sim, emitbuf): apply every handler
# for one micro-step's popped events ([H] lanes, masked by popped.valid).
StepFn = Callable


class SimProtocol(Protocol):
    events: EventQueue
    outbox: Outbox


@struct.dataclass
class EngineStats:
    events_processed: jax.Array  # [] i64
    micro_steps: jax.Array       # [] i64
    windows: jax.Array           # [] i64
    # Sparse-window fast path: windows drained at compact [S] width vs
    # windows that ran the full-width body (census exceeded S, or the
    # window held no live lane at all). hit + miss == windows whenever
    # the fast path is enabled; both stay 0 when it is off.
    fastpath_hit: jax.Array      # [] i64
    fastpath_miss: jax.Array     # [] i64
    # Events the bulk window pass (bulk_fn) committed; the rest of
    # events_processed ran through the serial fixpoint. 0 without one.
    bulk_events: jax.Array       # [] i64

    @staticmethod
    def create() -> "EngineStats":
        z = jnp.zeros((), I64)
        return EngineStats(events_processed=z, micro_steps=z, windows=z,
                           fastpath_hit=z, fastpath_miss=z, bulk_events=z)

    # Host-side accumulation across attempts/rebuilds. The supervisor
    # carries totals over an escalation boundary, where the pre-trip
    # counters live in a *different* jitted program than the post-heal
    # ones — accumulate as plain ints, never mix traced arrays from
    # two builds.
    def add(self, other: "EngineStats") -> "EngineStats":
        return EngineStats(
            events_processed=self.events_processed + other.events_processed,
            micro_steps=self.micro_steps + other.micro_steps,
            windows=self.windows + other.windows,
            fastpath_hit=self.fastpath_hit + other.fastpath_hit,
            fastpath_miss=self.fastpath_miss + other.fastpath_miss,
            bulk_events=self.bulk_events + other.bulk_events,
        )

    def as_dict(self) -> dict:
        return {
            "events_processed": int(self.events_processed),
            "micro_steps": int(self.micro_steps),
            "windows": int(self.windows),
            "fastpath_hit": int(self.fastpath_hit),
            "fastpath_miss": int(self.fastpath_miss),
            "bulk_events": int(self.bulk_events),
        }

    @staticmethod
    def from_dict(d: dict) -> "EngineStats":
        def v(k):
            return jnp.asarray(int(d.get(k, 0)), I64)
        return EngineStats(events_processed=v("events_processed"),
                           micro_steps=v("micro_steps"),
                           windows=v("windows"),
                           fastpath_hit=v("fastpath_hit"),
                           fastpath_miss=v("fastpath_miss"),
                           bulk_events=v("bulk_events"))


# route_fn(sim) -> sim: deliver the outbox into destination queues.
# The default is the single-shard events.route_outbox; the multi-chip
# runner substitutes the all-to-all exchange (shadow_tpu.parallel).
def _default_route(sim):
    q, out = route_outbox(sim.events, sim.outbox)
    return sim.replace(events=q, outbox=out)


# min_fn(x) -> x: reduce a per-shard scalar to the global value. The
# multi-chip runner substitutes lax.pmin over the mesh axis — the
# device form of the executeEvents barrier + min-next-event-time
# reduction (ref: scheduler.c:359-414).
def _identity(x):
    return x


def _takes_census(step_fn) -> bool:
    """Does step_fn accept the per-window kind census? Hand-written
    3-arg step functions (tests, tools) keep working unchanged."""
    try:
        return "census" in inspect.signature(step_fn).parameters
    except (TypeError, ValueError):
        return False


def window_fixpoint(sim, stats: EngineStats, step_fn: StepFn, wend,
                    emit_capacity: int = 4, lane_id=None):
    """Drain every event earlier than wend (local events only — handlers
    may keep emitting same-host events inside the window, e.g. loopback
    +1ns deliveries, ref: network_interface.c:546-554; iterate to
    fixpoint like the reference's pop-until-NULL worker loop). Purely
    shard-local: no collectives, so shards iterate independently.

    When step_fn accepts a `census` kwarg (net.step.make_step_fn), the
    loop carries the window's kind bitmask (events.kind_census): seeded
    from the queue at entry, OR-extended with each micro-step's
    emissions, so handler families whose kinds never occur this window
    are skipped for the whole window instead of re-testing the popped
    vector each micro-step."""
    wend = jnp.asarray(wend, simtime.DTYPE)
    # Zero emission template hoisted out of the loop body: one constant
    # per trace instead of a fresh EmitBuffer.create materialized every
    # micro-step.
    buf0 = EmitBuffer.create(sim.events.num_hosts, emit_capacity,
                             nwords=sim.events.words.shape[-1])
    if getattr(sim.events, "overflow_h", None) is not None:
        # lane isolation (core/lanes.py): emission overflow must carry
        # per-host attribution too, or the queue plane would drift
        # from the scalar latch at apply_emissions
        buf0 = buf0.replace(
            overflow_h=jnp.zeros((sim.events.num_hosts,), I32))
    with_census = _takes_census(step_fn)

    def cond(carry):
        return jnp.any(carry[0].events.min_time() < wend)

    def body(carry):
        if with_census:
            sim, stats, census = carry
        else:
            sim, stats = carry
        q, popped = pop_earliest(sim.events, wend)
        sim = sim.replace(events=q)
        # events_processed counts EXECUTED events: pops the CPU
        # admission gate re-queues (step._cpu_gate) are excluded via
        # the blocked-counter delta, so a repeatedly deferred event
        # still counts exactly once
        blocked0 = (jnp.sum(sim.net.ctr_cpu_blocked)
                    if hasattr(sim, "net") else jnp.zeros((), I64))
        if with_census:
            sim, buf = step_fn(sim, popped, buf0, census=census)
        else:
            sim, buf = step_fn(sim, popped, buf0)
        blocked1 = (jnp.sum(sim.net.ctr_cpu_blocked)
                    if hasattr(sim, "net") else jnp.zeros((), I64))
        if getattr(sim, "causality", None) is not None:
            # event-lineage recorder (telemetry/causality.py): must see
            # the PRE-apply next_seq so each emission's identity hash
            # matches the seq apply_emissions is about to assign. Lazy
            # import like the injection merge below — core must not
            # depend on telemetry at module load. Trace-time no-op
            # (zero compiled ops) when Sim.causality is None.
            from shadow_tpu.telemetry.causality import lineage_update
            sim = lineage_update(sim, popped, buf, lane_id)
        q, out = apply_emissions(sim.events, sim.outbox, buf, lane_id)
        sim = sim.replace(events=q, outbox=out)
        stats = stats.replace(
            events_processed=stats.events_processed
            + jnp.sum(popped.valid, dtype=I64) - (blocked1 - blocked0),
            micro_steps=stats.micro_steps + 1,
        )
        if with_census:
            return sim, stats, census | emit_kind_bits(buf)
        return sim, stats

    if with_census:
        out = jax.lax.while_loop(
            cond, body, (sim, stats, kind_census(sim.events, wend)))
    else:
        out = jax.lax.while_loop(cond, body, (sim, stats))
    return out[0], out[1]


def step_window(sim, stats: EngineStats, step_fn: StepFn, wend,
                emit_capacity: int = 4, lane_id=None,
                route_fn=_default_route, min_fn=_identity,
                bulk_fn=None, fault_fn=None, telem_fn=None, wstart=None,
                sparse_lanes: int = 0, census_fn=None, flow_fn=None,
                adv_attr=None, sentinel_fn=None):
    """One full round: drain the window, then route cross-host events
    staged in the outbox into destination queues. Returns the new global
    minimum pending time (the master's minNextEventTime,
    ref: scheduler.c:634-650).

    When `bulk_fn` is set (net.bulk.make_bulk_fn), eligible hosts'
    whole windows are consumed in one vectorized pass first; the
    fixpoint below then only iterates for leftover hosts (zero
    iterations in the steady state of bulk-friendly workloads).

    `fault_fn` (faults.apply.make_fault_fn) runs first, at the window
    boundary: it rewrites the latency/reliability tables and applies
    crash resets as a pure function of wend, so every event inside the
    window sees the post-fault network. None (the default) leaves the
    body untouched.

    `telem_fn` (telemetry.ring.make_telem_fn) records one per-window
    telemetry record after the drain and BEFORE route_fn — the outbox
    must still hold the window's staged sends (route clears it), and
    queue occupancy is measured at its end-of-drain low-water point.
    `wstart` (the window's start time) is only consumed by telemetry;
    None records a zero-length window.

    `sparse_lanes` > 0 arms the sparse-window fast path: when the
    GLOBAL count of rows holding any event < wend (census_fn reduces
    the shard-local count; lax.psum under shard_map, so every shard
    takes the same branch) fits the budget S and is nonzero, the
    fixpoint runs over a compacted [S]-lane Sim (core/compact.py) and
    scatters back — bit-identical by construction. fault_fn, bulk_fn,
    telemetry and route all run at full width on both branches, so
    fault/checkpoint boundaries are unchanged.

    `adv_attr` — a (cause, edge_a, edge_b, raw_jump) tuple from a
    window-end rule's `.explain` companion (make_wend_fn) — latches
    this window's advance attribution into Sim.causality
    (telemetry/causality.py advance_latch) after the drain. None (the
    default, and always when causality is off) latches nothing."""
    if telem_fn is not None:
        ev0 = stats.events_processed
        ms0 = stats.micro_steps
    # Each layer runs under its own jax.named_scope (shadow_window,
    # shadow_bulk, shadow_serial, shadow_route, shadow_barrier): the
    # names ride every op's op_name into the compiled program and the
    # profiler's trace, so device time splits by layer. Scopes are
    # trace-time metadata only; the program's ops are unchanged.
    with jax.named_scope("shadow_window"):
        # Open-system injection (inject/staging.py) merges FIRST,
        # before the fault rewrite and the bulk/census passes: an
        # injected event with timestamp inside this window must be
        # census-visible and drain exactly like one an application
        # scheduled. Trace-time no-op when Sim.inject is None (the
        # default).
        inject_deltas = None
        if getattr(sim, "inject", None) is not None:
            from shadow_tpu.inject.staging import merge_staged
            sim, inj_w, drop_w, def_w = merge_staged(
                sim, 0 if wstart is None else wstart, wend, lane_id)
            inject_deltas = (inj_w, drop_w, def_w)
        if fault_fn is not None:
            sim = fault_fn(sim, wend)
        # Specialization guard (compile/specialize.py): on a
        # capability-trimmed program, evaluate one cheap predicate per
        # dropped capability right after the fault rewrite (the only
        # in-window writer of the watched tables) — a trip is latched
        # sticky and becomes a fatal health fault at gather time.
        # Trace-time no-op when Sim.guard is None (every full program).
        if getattr(sim, "guard", None) is not None:
            from shadow_tpu.compile.specialize import guard_update
            sim = guard_update(sim, wend)
    if bulk_fn is not None:
        with jax.named_scope("shadow_bulk"):
            sim, n_bulk = bulk_fn(sim, wend)
            stats = stats.replace(
                events_processed=stats.events_processed + n_bulk,
                bulk_events=stats.bulk_events + n_bulk)

    if adv_attr is not None and getattr(sim, "causality", None) is None:
        adv_attr = None
    S = int(sparse_lanes) if sparse_lanes else 0
    with jax.named_scope("shadow_serial"):
        n_active = None
        if S > 0 or telem_fn is not None or adv_attr is not None:
            active = sim.events.min_time() < jnp.asarray(wend,
                                                         simtime.DTYPE)
            n_active = jnp.sum(active, dtype=I32)  # shard-LOCAL lanes
        fastpath = jnp.zeros((), jnp.bool_)
        if S > 0:
            n_global = (census_fn or _identity)(n_active)
            # Require at least one live lane: an all-quiet window's
            # full-width fixpoint terminates immediately, so compaction
            # would pay gather+scatter for nothing (bulk-pass workloads
            # consume whole windows before the fixpoint every round).
            hit = (n_global > 0) & (n_global <= S)

            def _full_body(op):
                fsim, fstats = op
                return window_fixpoint(
                    fsim, fstats, step_fn, wend, emit_capacity, lane_id)

            if S < sim.events.num_hosts:
                def _compact_body(op):
                    fsim, fstats = op
                    idx = active_indices(active, S)
                    lane_c = (idx if lane_id is None
                              else jnp.asarray(lane_id, I32)[idx])
                    csim = gather_lanes(fsim, idx)
                    csim, fstats = window_fixpoint(
                        csim, fstats, step_fn, wend, emit_capacity, lane_c)
                    return scatter_lanes(fsim, csim, idx), fstats

                sim, stats = jax.lax.cond(hit, _compact_body, _full_body,
                                          (sim, stats))
            else:
                # This (shard-local) width is already <= S: there is
                # nothing to narrow, so run full width unconditionally
                # — but keep the GLOBAL hit/miss accounting below, so
                # the decision record is shard-count-invariant (a
                # 64-host serial run compacts to S=16 while its 8-shard
                # twin runs 8-wide shards as-is; both must count the
                # same hits).
                sim, stats = _full_body((sim, stats))
            stats = stats.replace(
                fastpath_hit=stats.fastpath_hit + hit.astype(I64),
                fastpath_miss=stats.fastpath_miss + (~hit).astype(I64))
            fastpath = hit
        else:
            sim, stats = window_fixpoint(sim, stats, step_fn, wend,
                                         emit_capacity, lane_id)
    with jax.named_scope("shadow_window"):
        if telem_fn is not None:
            # inject_deltas is passed only when injection is live, so
            # hand-written telem_fns without the kwarg keep working
            kw = ({"inject_deltas": inject_deltas}
                  if inject_deltas is not None else {})
            sim = telem_fn(sim, wend if wstart is None else wstart, wend,
                           stats.events_processed - ev0,
                           stats.micro_steps - ms0,
                           n_active, fastpath, **kw)
        if flow_fn is not None:
            # flow flight-recorder (telemetry/flows.py): samples the
            # staged outbox, so it must also run BEFORE route_fn
            # clears it
            sim = flow_fn(sim, wend if wstart is None else wstart, wend)
        if adv_attr is not None:
            # window-advance attribution (telemetry/causality.py): the
            # census reduction makes the latched active count GLOBAL,
            # so the replicated [W] plane stays shard-identical
            from shadow_tpu.telemetry.causality import advance_latch
            cause, edge_a, edge_b, raw_jump = adv_attr
            sim = advance_latch(
                sim, wend if wstart is None else wstart, wend,
                cause, edge_a, edge_b, raw_jump,
                (census_fn or _identity)(n_active))
    with jax.named_scope("shadow_route"):
        sim = route_fn(sim)
    with jax.named_scope("shadow_window"):
        if getattr(sim, "lanes", None) is not None:
            # lane barrier (core/lanes.py): reduce the per-host latch
            # planes per lane, trip + freeze sick lanes, and — when the
            # program is resident (Sim.admission, fleet/admission.py) —
            # enforce lease horizons and keep FREE lanes empty, all at
            # this barrier. After the route so this window's
            # deliveries are attributed (and a delivery past a lease
            # edge is flushed the window it arrives), before the min
            # so frozen/expired lanes stop holding the global advance
            # back.
            from shadow_tpu.core.lanes import window_update
            sim = window_update(sim, wend)
        if sentinel_fn is not None:
            # cross-shard integrity sentinel (parallel/elastic.py):
            # digest the replicated leaves AFTER the route barrier
            # restored the replication invariant (_replicate_scalars
            # runs inside route_fn) and the lane barrier settled — any
            # pmax-vs-pmin digest disagreement here is silent
            # divergence, latched sticky. Trace-time no-op when
            # Sim.sentinel is None.
            sim = sentinel_fn(sim, wend)
        stats = stats.replace(windows=stats.windows + 1)
        next_min = _next_min(sim, min_fn)
    return sim, stats, next_min


def _next_min(sim, min_fn):
    """The barrier: the earliest pending time over this shard's queue
    heads (and staged-but-unmerged injections), reduced by min_fn to
    the global value."""
    with jax.named_scope("shadow_barrier"):
        local_min = jnp.min(sim.events.min_time())
        if getattr(sim, "inject", None) is not None:
            # staged-but-unmerged events join the advance rule: a quiet
            # queue must still jump to the next injected timestamp
            # instead of declaring the run over
            from shadow_tpu.inject.staging import staged_pending_min
            local_min = jnp.minimum(local_min,
                                    staged_pending_min(sim.inject))
        return min_fn(local_min)


def make_wend_fn(*, min_jump: int, end_time: int,
                 pair_mask=None, fault_times=None, table_fn=None):
    """Build the window-end rule ``wend = wend_fn(sim, wstart)`` shared
    by every chunked runner.

    Static (``pair_mask`` is None): the reference's rule — ``wstart +
    min_jump`` clamped to ``end_time + 1`` (ref: master.c:450-480),
    with the same positive floor as `run`.

    Adaptive (``pair_mask`` is a [V,V] bool array of host-bearing
    vertex pairs, see net.build.adaptive_jump_spec): advance by the
    CURRENT minimum cross-host path latency read from
    ``sim.net.latency_ns`` — the reference's lazily-recomputed min time
    jump (topology.c:1374-1385) done live, so fault plans that raise
    latencies let windows grow. Three guards keep it conservative:

    - floor at the static ``min_jump``: plan validation rejects
      negative latency deltas (faults/plan.py), so the live tables are
      always >= boot and the floor only matters for links a fault
      disabled entirely;
    - links with ``reliability == 0`` (downed by LINK_DOWN/PARTITION)
      do not constrain the jump — no packet crosses them — which is
      only sound together with:
    - ``fault_times`` (the plan's record times): wend never crosses the
      next record > wstart, so a LINK_UP/HEAL cannot revive a short
      link in the middle of a window sized without it, and every
      record materializes at a window boundary exactly (seed_wakeups
      pins a pending event at each record time, so wstart reaches it);
    - ``table_fn`` (faults.apply.make_table_fn, required whenever a
      plan is installed): the window is sized from the plan-replayed
      tables at ``wstart + 1`` — records at exactly wstart applied —
      NOT from the live ``sim.net`` tables. step_window only rewrites
      the live tables AFTER the span was chosen, so a window starting
      exactly at a latency-restore record would otherwise be sized by
      the stale (still-spiked) table: packets flying at the restored
      short latency then land inside the over-long window, out of
      conservative order.

    The returned rule carries an ``explain`` companion —
    ``wend_fn.explain(sim, wstart) -> (wend, cause, edge_a, edge_b,
    raw_jump)`` — computing the SAME wend plus its advance attribution
    (telemetry/causality.py CAUSE_* codes): which constraint bound the
    window, the binding latency-table vertex pair under adaptive jump
    (-1 otherwise), and the available lookahead before the record/end
    clamps. Clamps are attributed in a fixed priority order (floor ->
    record -> end) and only a clamp that STRICTLY lowers wend takes
    the cause, so ties are deterministic on every path.
    """
    from shadow_tpu.telemetry.causality import (
        CAUSE_ADAPTIVE_EDGE,
        CAUSE_END_TIME,
        CAUSE_FAULT_RECORD,
        CAUSE_MIN_JUMP,
    )
    if isinstance(min_jump, int) and min_jump <= 0:
        raise ValueError(f"min_jump must be positive, got {min_jump}")
    end = jnp.asarray(int(end_time), simtime.DTYPE)
    jump0 = jnp.maximum(jnp.asarray(min_jump, simtime.DTYPE), 1)
    ft_c = None
    if fault_times is not None and len(fault_times):
        ft_c = jnp.asarray(fault_times, simtime.DTYPE)
    neg1 = jnp.asarray(-1, I32)
    if pair_mask is None:
        def wend_fn(sim, wstart):
            wend = jnp.minimum(wstart + jump0, end + 1)
            # Static windows take the same clamp as adaptive ones:
            # without it a window crossing a record would apply the
            # fault EARLY (step_window rewrites for records < wend),
            # smearing fault timing by up to min_jump and making the
            # final state depend on where window boundaries happen to
            # fall. With it every record lands at a boundary exactly,
            # in every driver, under every partitioning.
            if ft_c is not None:
                nxt = jnp.min(jnp.where(ft_c > wstart, ft_c,
                                        simtime.INVALID))
                wend = jnp.minimum(wend, nxt)
            return wend

        def explain(sim, wstart):
            wend = wstart + jump0
            cause = jnp.asarray(CAUSE_MIN_JUMP, I32)
            if ft_c is not None:
                nxt = jnp.min(jnp.where(ft_c > wstart, ft_c,
                                        simtime.INVALID))
                cause = jnp.where(nxt < wend, CAUSE_FAULT_RECORD, cause)
                wend = jnp.minimum(wend, nxt)
            cause = jnp.where(end + 1 < wend, CAUSE_END_TIME, cause)
            wend = jnp.minimum(wend, end + 1)
            return wend, cause, neg1, neg1, jump0

        wend_fn.explain = explain
        return wend_fn
    mask_c = jnp.asarray(pair_mask, bool)
    V = int(mask_c.shape[0])

    def _adaptive_jump(sim, wstart):
        if table_fn is not None:
            lat, rel = table_fn(wstart + 1)
        else:
            lat, rel = sim.net.latency_ns, sim.net.reliability
        lat = jnp.asarray(lat, simtime.DTYPE)
        live = mask_c & (rel > 0)
        return jnp.where(live, lat, simtime.INVALID)

    def wend_fn(sim, wstart):
        jump = jnp.min(_adaptive_jump(sim, wstart))
        # Tables are replicated across shards (REPLICATED_FIELDS), so
        # this min is shard-invariant without a collective. The upper
        # clip keeps wstart + jump from overflowing i64 when no pair
        # constrains the window at all (mask empty or every masked
        # link down): any span is conservative then, and end + 1 ends
        # the run in one window.
        jump = jnp.clip(jump, jump0, end + 1)
        wend = wstart + jump
        if ft_c is not None:
            nxt = jnp.min(jnp.where(ft_c > wstart, ft_c, simtime.INVALID))
            wend = jnp.minimum(wend, nxt)
        return jnp.minimum(wend, end + 1)

    def explain(sim, wstart):
        masked = _adaptive_jump(sim, wstart)
        flat = masked.reshape(-1)
        k = jnp.argmin(flat)            # first min: deterministic edge
        jump_u = flat[k]
        jump = jnp.clip(jump_u, jump0, end + 1)
        # at (or below) the floor the EDGE is not the constraint
        adaptive = jump_u > jump0
        cause = jnp.where(adaptive, CAUSE_ADAPTIVE_EDGE,
                          CAUSE_MIN_JUMP).astype(I32)
        edge_a = jnp.where(adaptive, (k // V).astype(I32), neg1)
        edge_b = jnp.where(adaptive, (k % V).astype(I32), neg1)
        wend = wstart + jump
        if ft_c is not None:
            nxt = jnp.min(jnp.where(ft_c > wstart, ft_c, simtime.INVALID))
            cause = jnp.where(nxt < wend, CAUSE_FAULT_RECORD, cause)
            wend = jnp.minimum(wend, nxt)
        cause = jnp.where(end + 1 < wend, CAUSE_END_TIME, cause)
        wend = jnp.minimum(wend, end + 1)
        return wend, cause, edge_a, edge_b, jump

    wend_fn.explain = explain
    return wend_fn


def make_chunk_body(step_fn: StepFn, *, end_time: int, wend_fn,
                    chunk_windows: int, emit_capacity: int = 4,
                    lane_fn=None, route_fn=_default_route,
                    min_fn=_identity, bulk_fn=None, fault_fn=None,
                    telem_fn=None, sparse_lanes: int = 0,
                    census_fn=None, flow_fn=None, sentinel_fn=None):
    """Build ``chunk(sim, stats, wstart) -> (sim, stats, wstart')``:
    up to `chunk_windows` full window rounds as ONE device program (a
    lax.fori_loop over step_window), so host-driven loops pay one
    dispatch per K windows instead of per window.

    The window sequence is identical to `run`'s while_loop: each round
    computes ``wend = wend_fn(sim, wstart)`` (make_wend_fn) and
    advances to the min_fn-reduced next pending time. The loop is a
    lax.while_loop over ``(i < chunk_windows) & (wstart <= end)`` —
    the same shape as `run`, just bounded — so a round whose wstart
    already passed end_time (or an empty queue: next_min ==
    simtime.INVALID > end) exits immediately and a whole chunk
    dispatched past the end returns its carry unchanged. Callers may
    therefore keep one speculative chunk in flight and only
    synchronize on the *previous* chunk's wstart. (A fori_loop with a
    per-window lax.cond no-op guard is the obvious alternative; it
    shuttles the entire sim tuple through a conditional every window,
    which on some backends costs more than the window itself.)

    ``lane_fn(sim)`` supplies step_window's lane_id (None -> identity
    lanes); it is evaluated once per chunk on the carried sim — lane
    identity is static for a run. fault_fn/telem_fn/bulk_fn and the
    sparse fast path all run INSIDE the loop, per window, exactly as
    in the per-window host loop. The trip condition reads only
    replicated values (wstart is min_fn-reduced), so shards stay in
    lockstep exactly as in `run`."""
    if int(chunk_windows) < 1:
        raise ValueError(
            f"chunk_windows must be >= 1, got {chunk_windows}")
    end = jnp.asarray(int(end_time), simtime.DTYPE)
    K = int(chunk_windows)

    def chunk(sim, stats, wstart):
        wstart = jnp.asarray(wstart, simtime.DTYPE)
        lane = None if lane_fn is None else lane_fn(sim)
        # Streamed injection: no window may start at (or cross) the
        # staging horizon — the first trace event the host has NOT
        # yet staged — or that event would merge late once staged.
        # The chunk hands control back to the host there; the feeder
        # refills, horizon advances, and the loop is redispatched.
        # INVALID horizon (no feeder / whole trace staged) never
        # binds, so closed-loop runs are untouched.
        streamed = getattr(sim, "inject", None) is not None

        def cond(carry):
            i, _sim, _stats, ws = carry
            ok = (i < K) & (ws <= end)
            if streamed:
                ok = ok & (ws < _sim.inject.horizon)
            return ok

        explain = getattr(wend_fn, "explain", None)
        tracing = (getattr(sim, "causality", None) is not None
                   and explain is not None)

        def body(carry):
            i, sim, stats, ws = carry
            adv = None
            with jax.named_scope("shadow_window"):
                if tracing:
                    from shadow_tpu.telemetry.causality import (
                        CAUSE_INJECT_HORIZON,
                    )
                    wend, cause, edge_a, edge_b, raw = explain(sim, ws)
                    if streamed:
                        cause = jnp.where(sim.inject.horizon < wend,
                                          CAUSE_INJECT_HORIZON, cause)
                        wend = jnp.minimum(wend, sim.inject.horizon)
                    adv = (cause, edge_a, edge_b, raw)
                else:
                    wend = wend_fn(sim, ws)
                    if streamed:
                        wend = jnp.minimum(wend, sim.inject.horizon)
            sim, stats, next_min = step_window(
                sim, stats, step_fn, wend,
                emit_capacity=emit_capacity, lane_id=lane,
                route_fn=route_fn, min_fn=min_fn, bulk_fn=bulk_fn,
                fault_fn=fault_fn, telem_fn=telem_fn, wstart=ws,
                sparse_lanes=sparse_lanes, census_fn=census_fn,
                flow_fn=flow_fn, adv_attr=adv, sentinel_fn=sentinel_fn)
            return i + 1, sim, stats, next_min

        _, sim, stats, wstart = jax.lax.while_loop(
            cond, body, (jnp.asarray(0, jnp.int32), sim, stats, wstart))
        return sim, stats, wstart

    return chunk


def run(
    sim,
    step_fn: StepFn,
    *,
    end_time: int,
    min_jump: int,
    start_time: int = 0,
    emit_capacity: int = 4,
    lane_id=None,
    route_fn=_default_route,
    min_fn=_identity,
    bulk_fn=None,
    fault_fn=None,
    telem_fn=None,
    sparse_lanes: int = 0,
    census_fn=None,
    fault_times=None,
    flow_fn=None,
    sentinel_fn=None,
):
    """Run the whole simulation as one device program (fast path for
    on-device application models). Window advance rule is the
    reference's: newStart = minNextEventTime, newEnd = newStart +
    minJump, clamped to end (ref: master.c:450-480). min_jump is the
    precomputed minimum cross-host path latency with the same 10ms
    floor the reference applies when unknown (ref: master.c:133-159).
    `fault_times` (the installed plan's record times) additionally
    clamps each window at the next record > wstart — the same rule as
    make_wend_fn — so faults take effect exactly at their timestamps
    instead of up to min_jump early when a window would cross one.

    Under shard_map, route_fn carries the only collectives (all-to-all
    + the pmin in min_fn), both outside the inner fixpoint loop, so the
    outer window loop runs in lockstep across shards while each shard
    drains its own window at its own pace.
    """
    if isinstance(min_jump, int) and min_jump <= 0:
        raise ValueError(f"min_jump must be positive, got {min_jump}")
    end_time = jnp.asarray(end_time, simtime.DTYPE)
    # A non-positive window length would spin the outer loop forever;
    # clamp like the reference's runahead floor (master.c:133-159).
    min_jump = jnp.maximum(jnp.asarray(min_jump, simtime.DTYPE), 1)
    ft_c = None
    if fault_times is not None and len(fault_times):
        ft_c = jnp.asarray(fault_times, simtime.DTYPE)
    stats = EngineStats.create()

    def cond(carry):
        sim, stats, wstart = carry
        return wstart <= end_time

    tracing = getattr(sim, "causality", None) is not None

    def body(carry):
        sim, stats, wstart = carry
        adv = None
        with jax.named_scope("shadow_window"):
            if tracing:
                # same attribution rule (and clamp-priority order) as
                # the static make_wend_fn explain — the whole-run
                # program's advance plane must be bit-identical to the
                # chunked drivers' (telemetry/causality.py)
                from shadow_tpu.telemetry.causality import (
                    CAUSE_END_TIME,
                    CAUSE_FAULT_RECORD,
                    CAUSE_MIN_JUMP,
                )
                wend = wstart + min_jump
                cause = jnp.asarray(CAUSE_MIN_JUMP, I32)
                if ft_c is not None:
                    nxt = jnp.min(jnp.where(ft_c > wstart, ft_c,
                                            simtime.INVALID))
                    cause = jnp.where(nxt < wend, CAUSE_FAULT_RECORD,
                                      cause)
                    wend = jnp.minimum(wend, nxt)
                cause = jnp.where(end_time + 1 < wend, CAUSE_END_TIME,
                                  cause)
                wend = jnp.minimum(wend, end_time + 1)
                neg1 = jnp.asarray(-1, I32)
                adv = (cause, neg1, neg1, min_jump)
            else:
                wend = jnp.minimum(wstart + min_jump, end_time + 1)
                if ft_c is not None:
                    nxt = jnp.min(jnp.where(ft_c > wstart, ft_c,
                                            simtime.INVALID))
                    wend = jnp.minimum(wend, nxt)
        sim, stats, next_min = step_window(
            sim, stats, step_fn, wend, emit_capacity, lane_id,
            route_fn, min_fn, bulk_fn, fault_fn, telem_fn, wstart,
            sparse_lanes, census_fn, flow_fn, adv, sentinel_fn,
        )
        return sim, stats, next_min

    # Whole-run programs never return to the host, so an injection
    # feeder must have staged the ENTIRE trace (Feeder.fill_all;
    # horizon stays INVALID). The staged minimum joins the first-window
    # rule (_next_min) so a trace-only run (empty queue) still starts.
    with jax.named_scope("shadow_window"):
        first = jnp.maximum(_next_min(sim, min_fn),
                            jnp.asarray(start_time, simtime.DTYPE))
    sim, stats, _ = jax.lax.while_loop(cond, body, (sim, stats, first))
    return sim, stats
