"""Multi-chip execution: hosts sharded over a jax.sharding.Mesh axis.

This is the TPU-native replacement for the reference's host→thread
assignment and barrier machinery (ref: scheduler.c:437-531 host
shuffling; scheduler.c:359-414 + master.c:450-480 round barriers):

- Host rows (event queues, socket tables, NIC state) shard over the
  mesh's host axis; global lookup tables (IP maps, the dense
  latency/reliability matrices) replicate.
- The window fixpoint is purely shard-local — each chip drains its own
  hosts' events at its own pace, no communication (the analog of
  worker threads running between barriers).
- The only collectives, once per window: an all-to-all exchanging
  cross-shard events staged in the outbox (the analog of
  scheduler_push to another thread's queue, scheduler.c:339-357), and
  a pmin over per-shard next-event times (the analog of the
  executeEvents barrier + min reduction, scheduler.c:393-398). Both
  ride ICI on a real TPU mesh.

Determinism: event identity is (time, dst, src, per-source seq) and
pop order is a lexicographic argmin over those keys (events.py), so
results are bit-identical for any shard count — the same property the
reference gets from its 4-key event sort (ref: event.c:110-153).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import tree_map_with_path

from shadow_tpu.core import collectives
from shadow_tpu.core.engine import (
    EngineStats,
    resolve_sparse_lanes,
    run as engine_run,
)
from shadow_tpu.core.events import (
    EventQueue,
    Outbox,
    _pack_time,
    _unpack_time,
    clear_outbox,
    insert_flat,
    segment_ranks,
)
from shadow_tpu.net.state import NetState, REPLICATED_FIELDS
from shadow_tpu.parallel.elastic import make_sentinel_fn
from shadow_tpu.telemetry.flows import make_flow_fn
from shadow_tpu.telemetry.ring import make_telem_fn

I32 = jnp.int32


def sim_specs(sim, axis: str):
    """PartitionSpec pytree for a Sim (or any engine-compatible state):
    NetState's replicated lookup tables and scalar leaves get P();
    everything else shards its leading (host) dimension over `axis`.
    App states must follow the same convention: leading-H arrays or
    scalars."""

    def spec(path, leaf):
        names = [k.name for k in path if hasattr(k, "name")]
        # The telemetry ring is replicated state: its [W] planes are
        # ring slots, not host rows, and every value stored is already
        # globally reduced at the window barrier (telemetry/ring.py).
        # This check must come first — the 1-D planes would otherwise
        # fall through to P(axis). The injection staging buffer is
        # replicated the same way: every shard sees every staged
        # event and merges only the rows it owns (inject/staging.py).
        # The lane-health latches (core/lanes.py) are [R] lane planes,
        # also not host rows — but their window_update reduces
        # shard-LOCAL host planes, so lane isolation is a
        # single-shard feature today (enforced by the attach sites).
        # The flow ring (telemetry/flows.py) is replicated like telem:
        # its [F] planes are ring slots holding globally-merged
        # records, identical on every shard after the barrier psum.
        if names and names[0] in ("telem", "inject", "lanes", "flows"):
            return P()
        # Causality state (telemetry/causality.py) is mixed: the
        # lineage sub-rings are per-HOST rows ([H, F] planes and [H]
        # counters — appends are row-local, so they shard like event
        # queues), while the advance-attribution plane (adv_* leaves)
        # is latched from replicated window values on every shard and
        # replicates like the telemetry ring.
        if names and names[0] == "causality":
            if names[-1].startswith("adv_") or jnp.ndim(leaf) == 0:
                return P()
            return P(axis)
        # Replicated lookup tables are identified by NetState field
        # name, scoped to the NetState subtree ("net" in a Sim, or a
        # bare NetState) so an app field that happens to share a name
        # still shards.
        if names and names[-1] in REPLICATED_FIELDS and (
            names[-2] == "net" if len(names) > 1
            else isinstance(sim, NetState)
        ):
            return P()
        if jnp.ndim(leaf) == 0:
            return P()
        return P(axis)

    return tree_map_with_path(spec, sim)


def route_outbox_sharded(
    q: EventQueue, out: Outbox, axis: str, num_shards: int,
    lane_id: jax.Array, exchange_capacity: int | None = None,
    narrow: int | None = None,
) -> tuple[EventQueue, Outbox]:
    """Exchange staged cross-host events across shards and insert them
    into destination rows — the window-boundary all-to-all of
    (dst, time, kind, src, seq, words) records (SURVEY.md §5.8).

    Each shard owns the contiguous global host range
    [lane_id[0], lane_id[0] + Hl); an event's target shard is
    dst // Hl. Entries are grouped per target shard by a stable sort,
    exchanged with lax.all_to_all, then inserted with the same
    insert_flat as the single-shard path, in the same global
    (source row, emission slot) order — so the resulting queue state is
    bit-identical to the single-shard route.

    exchange_capacity bounds the per-peer exchange buffer (default:
    the whole outbox, Hl*M, which can never overflow). Smaller values
    cut ICI transfer ~linearly; entries beyond the cap are counted in
    q.overflow, never silently dropped.

    The narrow tier (r4, the sharded analog of events.ROUTE_NARROW):
    the worst-case buffer is sized for one shard sending its WHOLE
    outbox to one peer, but a steady-state window spreads far fewer
    events across peers — so both the collective payload and the
    receive-side insert (which scale with num_shards * C) run at a
    narrow capacity whenever the LARGEST per-target group fits it,
    decided by a scalar pmax so every shard takes the same branch.
    Entries never drop: oversize windows take the full-width branch."""
    Hl, M = out.dst.shape
    GH = Hl * num_shards
    base = lane_id[0]
    n = Hl * M
    C_full = n if exchange_capacity is None else min(exchange_capacity, n)

    dst = out.dst.reshape(n)
    occupied = dst >= 0
    bad = occupied & (dst >= GH)
    valid = occupied & ~bad
    tgt = jnp.where(valid, dst // Hl, num_shards)

    # group by target shard (stable keeps global source order)
    order = jnp.argsort(tgt, stable=True)
    tgt_s = tgt[order]
    ok = tgt_s < num_shards
    rank = segment_ranks(tgt_s, num_shards)

    # Pack EVERY plane — the i64 time split into two i32 words — into
    # one buffer so the per-window exchange is exactly ONE collective
    # instead of six; each all_to_all pays its ICI launch latency once
    # per window (VERDICT r3 #4). Unwritten slots must read dst == -1
    # (empty), so the dst plane's fill is -1.
    W = out.words.shape[-1]
    t_lo, t_hi = _pack_time(out.time)
    packed = jnp.concatenate(
        [out.dst[..., None], t_lo[..., None], t_hi[..., None],
         out.kind[..., None], out.src[..., None], out.seq[..., None],
         out.words], axis=2,
    )  # [Hl, M, 6+W]
    flat = packed.reshape(n, 6 + W)[order]

    def exchange(qq, C):
        fits = ok & (rank < C)
        xofl = jnp.sum(ok & ~fits, dtype=I32)
        row = jnp.where(fits, tgt_s, num_shards)
        slot = jnp.where(fits, rank, C)
        sb_i32 = jnp.zeros((num_shards, C, 6 + W), I32).at[..., 0].set(-1)
        sb_i32 = sb_i32.at[row, slot].set(flat, mode="drop")

        with jax.named_scope("shadow_exchange"):
            rb_i32 = lax.all_to_all(sb_i32, axis, split_axis=0,
                                    concat_axis=0)

        nn = num_shards * C
        ri32 = rb_i32.reshape(nn, 6 + W)
        rdst = ri32[:, 0]
        rtime = _unpack_time(ri32[:, 1], ri32[:, 2])
        occupied_r = rdst >= 0
        local_row = rdst - base
        # An arriving dst outside this shard's [base, base+Hl) block
        # means the lane assignment violated the contiguous-block
        # contract — count it loudly (a negative row would otherwise
        # wrap-around write; an oversized one would be silently
        # dropped).
        misrouted = occupied_r & ((local_row < 0) | (local_row >= Hl))
        rvalid = occupied_r & ~misrouted
        qq = insert_flat(
            qq, rvalid, jnp.where(rvalid, local_row, Hl),
            rtime, ri32[:, 3], ri32[:, 4],
            ri32[:, 5], ri32[:, 6:],
        )
        return qq.replace(
            overflow=qq.overflow + jnp.sum(bad, dtype=I32) + xofl
            + jnp.sum(misrouted, dtype=I32))

    C_n = (max(M, n // (4 * num_shards)) if narrow is None
           else narrow)
    # +1 so rank == C_n-1 fits; a globally empty exchange gives
    # gmax == 0 — the common case in sparse windows, where the whole
    # all-to-all + insert pipeline is elided (layer 3). The pmax'd
    # predicate is identical on every shard, so skipping the
    # collective is coherent (the narrow-tier precedent).
    with jax.named_scope("shadow_exchange"):
        gmax = collectives.pmax(jnp.max(jnp.where(ok, rank, -1)) + 1,
                                axis)
    empty = gmax == 0

    def elide(qq):
        # bad-dst entries are excluded from `ok` (they never enter the
        # exchange) but still owe their loud overflow accounting
        return qq.replace(overflow=qq.overflow + jnp.sum(bad, dtype=I32))

    if C_n and C_n < C_full:
        hit = gmax <= C_n
        out = out.replace(
            narrow_hit=out.narrow_hit + hit.astype(I32),
            narrow_miss=out.narrow_miss + (~hit).astype(I32),
            max_occupied=jnp.maximum(out.max_occupied,
                                     gmax.astype(I32)),
            route_elided=out.route_elided + empty.astype(I32))
        q = lax.cond(
            empty,
            elide,
            lambda qq: lax.cond(
                hit,
                lambda q2: exchange(q2, C_n),
                lambda q2: exchange(q2, C_full),
                qq),
            q)
    else:
        out = out.replace(
            route_elided=out.route_elided + empty.astype(I32))
        q = lax.cond(empty, elide, lambda qq: exchange(qq, C_full), q)
    return q, clear_outbox(out)


def _replicate_scalars(sim, initial_sim, stats: EngineStats, axis: str):
    """psum EVERY scalar leaf's *delta* over the run so out_specs can
    declare them replicated — scalar leaves are per-shard partial
    counters by convention (overflow/drop totals); a new counter added
    anywhere in the state tree is aggregated automatically instead of
    silently returning one shard's value. The delta (not the value) is
    summed because the initial value is replicated on every shard —
    psumming it directly would multiply a nonzero starting count by the
    shard count. stats.windows is identical on every shard (lockstep
    outer loop), so pmax is the identity there."""
    # the narrow-tier telemetry is pmax'd, not delta-psummed: the
    # exchange gate's own pmax makes the branch (and so hit/miss)
    # identical on every shard, and a sum of per-shard maxima would be
    # meaningless for max_occupied — pin all three, overwrite after.
    ob = sim.outbox
    # route_elided rides along: the elision branch is decided by a
    # pmax'd census, so the count is already identical on every shard.
    narrow_pinned = (collectives.pmax(ob.narrow_hit, axis),
                     collectives.pmax(ob.narrow_miss, axis),
                     collectives.pmax(ob.max_occupied, axis),
                     collectives.pmax(ob.route_elided, axis))
    # The telemetry ring is pinned the same way: its scalars (count,
    # prev_*) and planes already hold globally-reduced values — the
    # delta-psum below would multiply them by the shard count.
    telem = getattr(sim, "telem", None)
    # The flow ring's planes and scalars are likewise already
    # globally merged at the barrier (telemetry/flows.py) — pin.
    flows = getattr(sim, "flows", None)
    # Injection staging: seq_floor and horizon are REPLICATED values
    # (the floor advance is the same pure function of the replicated
    # planes on every shard) — the delta-psum would multiply the
    # advance by the shard count. Pin both; the cumulative counters
    # (injected/dropped/late) are per-shard partials and take the
    # generic delta-psum below like every other counter.
    inject = getattr(sim, "inject", None)
    # Causality's only scalar, adv_count, is REPLICATED (every shard
    # latches the same windows into the same slots) — the delta-psum
    # would multiply it by the shard count. The [H]/[H,F] lineage
    # leaves and [W] adv planes are non-scalar and untouched below.
    caus = getattr(sim, "causality", None)
    # The integrity sentinel's leaves are all replicated scalars —
    # every update is a pure function of collectives
    # (parallel/elastic.py make_sentinel_fn) — so the subtree pins
    # like the telemetry ring.
    sentinel = getattr(sim, "sentinel", None)
    # The per-path matrix is declared replicated (REPLICATED_FIELDS)
    # but each shard scatter-adds only its own hosts' sends into its
    # replica — psum the [V,V] delta so the reassembled matrix equals
    # the serial one. Skipped when track_paths is off (the [1,1] zero
    # matrix needs no collective).
    net = getattr(sim, "net", None)
    path_pinned = None
    if net is not None and net.ctr_path_packets.shape != (1, 1):
        init_paths = initial_sim.net.ctr_path_packets
        path_pinned = init_paths + lax.psum(
            net.ctr_path_packets - init_paths, axis)
    sim = jax.tree.map(
        lambda leaf, init: init + lax.psum(leaf - init, axis)
        if jnp.ndim(leaf) == 0 else leaf,
        sim, initial_sim,
    )
    sim = sim.replace(outbox=sim.outbox.replace(
        narrow_hit=narrow_pinned[0], narrow_miss=narrow_pinned[1],
        max_occupied=narrow_pinned[2], route_elided=narrow_pinned[3]))
    if telem is not None:
        sim = sim.replace(telem=telem)
    if flows is not None:
        sim = sim.replace(flows=flows)
    if inject is not None:
        sim = sim.replace(inject=sim.inject.replace(
            seq_floor=inject.seq_floor, horizon=inject.horizon))
    if caus is not None:
        sim = sim.replace(causality=sim.causality.replace(
            adv_count=caus.adv_count))
    if sentinel is not None:
        sim = sim.replace(sentinel=sentinel)
    if path_pinned is not None:
        sim = sim.replace(net=sim.net.replace(
            ctr_path_packets=path_pinned))
    stats = EngineStats(
        events_processed=lax.psum(stats.events_processed, axis),
        micro_steps=lax.psum(stats.micro_steps, axis),
        windows=collectives.pmax(stats.windows, axis),
        # the fastpath branch is globally decided (census_fn psum), so
        # every shard counted the same hits/misses — pin, don't sum
        fastpath_hit=collectives.pmax(stats.fastpath_hit, axis),
        fastpath_miss=collectives.pmax(stats.fastpath_miss, axis),
        bulk_events=lax.psum(stats.bulk_events, axis),
    )
    return sim, stats


def _harness_specs(mesh: Mesh, axis: str, sim):
    """Shared shard_map harness pieces: divisibility check + Sim and
    stats PartitionSpecs (used by both the whole-run and per-window
    wrappers — keep them identical)."""
    num_shards = mesh.shape[axis]
    H = sim.events.num_hosts
    if H % num_shards != 0:
        raise ValueError(f"num_hosts={H} not divisible by {num_shards} shards")
    specs = sim_specs(sim, axis)
    stats_specs = EngineStats(
        events_processed=P(), micro_steps=P(), windows=P(),
        fastpath_hit=P(), fastpath_miss=P(), bulk_events=P(),
    )
    return num_shards, specs, stats_specs


def _sharded_route_fn(axis: str, num_shards: int, lane,
                      exchange_capacity: int | None,
                      narrow: int | None = None):
    """The window-boundary all-to-all as an engine route_fn."""
    def route(s):
        q, out = route_outbox_sharded(s.events, s.outbox, axis, num_shards,
                                      lane, exchange_capacity, narrow)
        return s.replace(events=q, outbox=out)
    return route


def _make_whole_run(mesh: Mesh, axis: str, sim, step_fn, *,
                    end_time: int, min_jump: int, emit_capacity: int,
                    lane_id_fn=None, exchange_capacity: int | None = None,
                    narrow: int | None = None,
                    bulk_fn=None, fault_fn=None, sparse_lanes: int = 0,
                    fault_times=None, warm_key=None,
                    warm_start: bool | None = None,
                    compile_info: dict | None = None):
    """Shared factory: a jitted sim -> (sim, stats) running the full
    engine loop under shard_map (used by sharded_engine_run and
    make_sharded_runner — keep their semantics identical).

    `warm_key` (a program key or a lazy (args, kwargs) -> key rule,
    compile/buckets.py) routes the jitted program through the
    persistent AOT store when `warm_start`/SHADOW_WARM_PROGRAMS says
    so — callers that know the bundle derive the key
    (net.build._whole_run_key_fn); without one, serving stays off
    (this factory only sees opaque closures it cannot key)."""
    num_shards, specs, stats_specs = _harness_specs(mesh, axis, sim)

    def _body(local_sim):
        lane = (lane_id_fn(local_sim) if lane_id_fn is not None
                else local_sim.net.lane_id)
        out_sim, stats = engine_run(
            local_sim,
            step_fn,
            end_time=end_time,
            min_jump=min_jump,
            emit_capacity=emit_capacity,
            lane_id=lane,
            route_fn=_sharded_route_fn(axis, num_shards, lane,
                                       exchange_capacity, narrow),
            min_fn=lambda x: collectives.pmin(x, axis),
            bulk_fn=bulk_fn,
            # fault_fn closes over replicated plan constants and
            # derives everything from wend, which the pmin barrier
            # keeps identical on every shard — so each chip rewrites
            # the replicated tables to the same values with no extra
            # collective (faults/apply.py).
            fault_fn=fault_fn,
            # trace-time no-op when sim.telem is None (telemetry off)
            telem_fn=make_telem_fn(axis),
            # likewise a no-op when sim.flows is None (flow tracing off)
            flow_fn=make_flow_fn(axis),
            sparse_lanes=sparse_lanes,
            # the active-lane census is a GLOBAL count so every shard
            # takes the same compact/full branch
            census_fn=lambda x: lax.psum(x, axis),
            # the record-time wend clamp is computed from replicated
            # constants + the lockstep wstart, so it is shard-invariant
            fault_times=fault_times,
            # trace-time no-op when sim.sentinel is None (sentinel off)
            sentinel_fn=make_sentinel_fn(axis),
        )
        return _replicate_scalars(out_sim, local_sim, stats, axis)

    # check_vma=False: the engine's while_loop carries mix varying and
    # replicated leaves, which static VMA checking rejects without
    # pvary annotations throughout; replication of the declared-P()
    # outputs is guaranteed by _replicate_scalars psumming every
    # scalar leaf (and verified by the bit-identity tests).
    shmapped = shard_map(
        _body, mesh=mesh, in_specs=(specs,), out_specs=(specs, stats_specs),
        check_vma=False,
    )
    from shadow_tpu.compile import serve

    jitted = serve.maybe_warm(
        jax.jit(shmapped), warm_key,
        enabled=serve.warm_enabled(default=bool(warm_start)),
        info=compile_info)
    in_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                is_leaf=lambda x: isinstance(x, P))

    def go(s):
        return jitted(jax.device_put(s, in_shardings))

    return go


def sharded_engine_run(
    mesh: Mesh,
    axis: str,
    sim,
    step_fn,
    *,
    end_time: int,
    min_jump: int,
    emit_capacity: int = 4,
    lane_id_fn=None,
    exchange_capacity: int | None = None,
    narrow: int | None = None,
    bulk_fn=None,
    fault_fn=None,
    sparse_lanes: int = 0,
    fault_times=None,
):
    """shard_map the full engine.run over `mesh[axis]`. `sim` is the
    *global* state (as built for single-shard); sharding/replication
    follows sim_specs. lane_id_fn(local_sim) must return the [Hl]
    global host ids of the shard's rows (defaults to sim.net.lane_id).

    Returns (sim, stats) with global arrays reassembled."""
    return _make_whole_run(
        mesh, axis, sim, step_fn, end_time=end_time, min_jump=min_jump,
        emit_capacity=emit_capacity, lane_id_fn=lane_id_fn,
        exchange_capacity=exchange_capacity, narrow=narrow,
        bulk_fn=bulk_fn, fault_fn=fault_fn,
        sparse_lanes=sparse_lanes, fault_times=fault_times)(sim)


def make_sharded_window(mesh: Mesh, axis: str, sim_template, cfg, step_fn,
                        exchange_capacity: int | None = None,
                        narrow: int | None = None, bulk_fn=None,
                        fault_fn=None, donate: bool = False):
    """A jitted (sim, wstart, wend) -> (sim, stats, next_min) running
    ONE window round under shard_map — the building block for
    host-driven window loops (ProcessRuntime, checkpoint.run_windows)
    on a mesh. next_min is replicated by the pmin barrier; `sim` may be
    passed unsharded on first call (jit reshards per sim_specs). The
    telemetry hook is threaded with the mesh axis so ring aggregates
    are globally reduced — a trace-time no-op when sim.telem is None,
    exactly like the whole-run harness.

    `donate=True` donates the sim argument's buffers to the call
    (steady-state device allocation stays one sim across a long window
    loop). Opt-in: callers that re-read the input sim after dispatch —
    or pass the same sim twice (retry paths) — must leave it off."""
    from shadow_tpu.core.engine import step_window

    num_shards, specs, stats_specs = _harness_specs(mesh, axis,
                                                    sim_template)

    def _body(local_sim, wstart, wend):
        lane = local_sim.net.lane_id
        stats = EngineStats.create()
        out_sim, stats, next_min = step_window(
            local_sim, stats, step_fn, wend,
            emit_capacity=cfg.emit_capacity, lane_id=lane,
            route_fn=_sharded_route_fn(axis, num_shards, lane,
                                       exchange_capacity, narrow),
            min_fn=lambda x: collectives.pmin(x, axis),
            bulk_fn=bulk_fn, fault_fn=fault_fn,
            telem_fn=make_telem_fn(axis), wstart=wstart,
            sparse_lanes=resolve_sparse_lanes(cfg),
            census_fn=lambda x: lax.psum(x, axis),
            flow_fn=make_flow_fn(axis),
            sentinel_fn=make_sentinel_fn(axis),
        )
        out_sim, stats = _replicate_scalars(out_sim, local_sim, stats, axis)
        return out_sim, stats, next_min

    shmapped = shard_map(
        _body, mesh=mesh, in_specs=(specs, P(), P()),
        out_specs=(specs, stats_specs, P()), check_vma=False,
    )
    return jax.jit(shmapped, donate_argnums=(0,) if donate else ())


def make_sharded_chunk(mesh: Mesh, axis: str, sim_template, cfg, step_fn,
                       *, end_time: int, wend_fn, chunk_windows: int,
                       exchange_capacity: int | None = None,
                       narrow: int | None = None, bulk_fn=None,
                       fault_fn=None, donate: bool = False):
    """make_sharded_window's chunked sibling: a jitted
    (sim, stats, wstart) -> (sim, stats, next_min) running up to
    `chunk_windows` full window rounds per dispatch under ONE
    shard_map (engine.make_chunk_body) — the per-window all-to-all,
    pmin barrier, fault rewrites, telemetry stores and sparse-census
    psum all stay on device between host barriers, so the host pays
    one dispatch per K windows.

    Stats accumulate in the carry: pass EngineStats.create() to get
    per-chunk deltas (what the supervisor's on_chunk consumes). Scalar
    replication (_replicate_scalars) runs once per chunk against the
    chunk's ENTRY state — correct because it psums deltas, and deltas
    over K windows compose. The window-end rule `wend_fn` comes from
    net.build.resolve_wend_fn (static min_jump or the adaptive live
    -table jump); rounds whose wstart passed end_time are no-ops, so a
    caller may keep one speculative chunk in flight past the end."""
    from shadow_tpu.core.engine import make_chunk_body

    num_shards, specs, stats_specs = _harness_specs(mesh, axis,
                                                    sim_template)

    def _body(local_sim, stats, wstart):
        lane = local_sim.net.lane_id
        chunk = make_chunk_body(
            step_fn, end_time=end_time, wend_fn=wend_fn,
            chunk_windows=chunk_windows,
            emit_capacity=cfg.emit_capacity,
            lane_fn=lambda s: s.net.lane_id,
            route_fn=_sharded_route_fn(axis, num_shards, lane,
                                       exchange_capacity, narrow),
            min_fn=lambda x: collectives.pmin(x, axis),
            bulk_fn=bulk_fn, fault_fn=fault_fn,
            telem_fn=make_telem_fn(axis),
            sparse_lanes=resolve_sparse_lanes(cfg),
            census_fn=lambda x: lax.psum(x, axis),
            flow_fn=make_flow_fn(axis),
            sentinel_fn=make_sentinel_fn(axis),
        )
        out_sim, stats, next_min = chunk(local_sim, stats, wstart)
        out_sim, stats = _replicate_scalars(out_sim, local_sim, stats, axis)
        return out_sim, stats, next_min

    shmapped = shard_map(
        _body, mesh=mesh, in_specs=(specs, stats_specs, P()),
        out_specs=(specs, stats_specs, P()), check_vma=False,
    )
    return jax.jit(shmapped, donate_argnums=(0,) if donate else ())


def make_sharded_runner(bundle, mesh: Mesh, axis: str = "hosts",
                        app_handlers=(), end_time: int | None = None,
                        exchange_capacity: int | None = None,
                        app_bulk=None, app_tcp_bulk=None,
                        tcp_bulk_lossless: bool = False,
                        fault_fn=None, warm_start: bool | None = None,
                        compile_info: dict | None = None):
    """Multi-chip variant of shadow_tpu.net.build.make_runner: a
    REUSABLE jitted sim -> (sim, stats) callable running the whole
    window loop under shard_map (benchmarks must reuse one callable —
    re-tracing the netstack costs seconds per call; see make_runner).
    The input sim may be unsharded; device_put inside applies the
    NamedShardings once per call."""
    from shadow_tpu.net.step import make_step_fn
    from shadow_tpu.net.build import (_resolve_caps, _resolve_fault_fn,
                                      _whole_run_key_fn, plan_times)

    caller_fault_fn = fault_fn
    # Capability trims are shard-invariant: the loss trim's counter
    # arithmetic and the omitted timer family are per-row, and the
    # guard's scalar trip counters take the generic delta-psum
    # (_replicate_scalars) like every other sticky latch.
    caps = _resolve_caps(bundle, caller_fault_fn)
    step = make_step_fn(bundle.cfg, app_handlers, caps=caps)
    bulk_fn = None
    if app_bulk is not None:
        from shadow_tpu.net.bulk import make_bulk_fn

        bulk_fn = make_bulk_fn(bundle.cfg, app_bulk, caps=caps)
    if bulk_fn is None and app_tcp_bulk is not None:
        # lane-local like the UDP pass (all its reads/writes are
        # per-row or replicated-table gathers), so it drops straight
        # into the shard-local window step
        from shadow_tpu.net.tcp_bulk import make_tcp_bulk_fn

        bulk_fn = make_tcp_bulk_fn(bundle.cfg, app_tcp_bulk,
                                   lossless=tcp_bulk_lossless, caps=caps)
    fault_fn = _resolve_fault_fn(bundle, fault_fn)
    end = end_time if end_time is not None else bundle.cfg.end_time
    return _make_whole_run(
        mesh, axis, bundle.sim, step,
        end_time=end,
        min_jump=bundle.min_jump,
        emit_capacity=bundle.cfg.emit_capacity,
        exchange_capacity=exchange_capacity,
        bulk_fn=bulk_fn, fault_fn=fault_fn,
        sparse_lanes=resolve_sparse_lanes(bundle.cfg),
        fault_times=plan_times(bundle),
        warm_key=_whole_run_key_fn(
            bundle, app_handlers, end=end, path="sharded_whole",
            chunk_windows=0, adaptive=False, fault_fn=caller_fault_fn,
            app_bulk=app_bulk, app_tcp_bulk=app_tcp_bulk,
            tcp_bulk_lossless=tcp_bulk_lossless,
            shards=mesh.shape[axis],
            exchange_capacity=exchange_capacity, caps=caps),
        warm_start=warm_start, compile_info=compile_info)


def run_sharded(bundle, mesh: Mesh, axis: str = "hosts", app_handlers=(),
                end_time: int | None = None,
                exchange_capacity: int | None = None,
                app_bulk=None, app_tcp_bulk=None,
                warm_start: bool | None = None,
                compile_info: dict | None = None):
    """One-shot multi-chip variant of shadow_tpu.net.build.run."""
    return make_sharded_runner(
        bundle, mesh, axis, app_handlers, end_time,
        exchange_capacity, app_bulk, app_tcp_bulk,
        warm_start=warm_start, compile_info=compile_info)(bundle.sim)
