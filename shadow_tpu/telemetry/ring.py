"""Device-resident per-window telemetry ring.

The reference's observability is host-side counters sampled whenever
the tracker feels like it (tracker.c); on TPU every host<->device sync
stalls the window loop, so per-window visibility must be *written by
the device program itself*. This module keeps a fixed-capacity ring of
per-window records — one record per window barrier, written as pure
masked one-hot stores (the same no-scatter idiom as events._put and
the pcap capture ring) — that the host drains between device calls
(telemetry/harvest.py).

This ring answers "how did each WINDOW go"; its per-packet sibling is
telemetry/flows.py (the flow flight-recorder), which reuses the same
count-monotonic ring/overrun contract but samples individual
cross-host sends into latency records. Both drain through one
Harvester and surface through the same manifest/metrics/trace fan-out
(telemetry/export.py).

Record fields (one [W] plane each):

- wstart / wend      window bounds in sim-ns
- events             events executed inside the window (global)
- micro_steps        fixpoint iterations (max over shards — the
                     single-shard value; a psum would double-count)
- routed_local       outbox entries whose destination is on the same
                     shard (== all entries on 1 shard)
- routed_cross       outbox entries bound for another shard
- drops              packets dropped this window (all drop classes,
                     net.state.drop_total delta)
- retx               TCP segments retransmitted this window
- qocc_min/max/sum   event-queue occupancy across hosts at the end of
                     the window drain (pre-route)
- active_lanes       host rows holding any event < wend when the
                     window fixpoint started (global psum; the
                     sparse-window census input, core/engine.py)
- fastpath           1 when the window drained on the compact [S]-lane
                     fast path, 0 when it ran full width (replicated:
                     the census branch is globally decided)

Shard invariance: every field is reduced at the window barrier with
the collective that makes it *identical on every shard and equal to
the single-shard value* — psum for totals, pmax for micro_steps /
qocc_max, pmin for qocc_min. The ring is therefore replicated state
(parallel.shard.sim_specs gives the telem subtree P()), and per-window
records are bit-identical for any shard count, except that the
local/cross routing *split* is mesh-dependent (their sum is not).

Overflow: the ring never blocks the device program. `count` is
monotonic and slot = count % capacity (the pcap-ring pattern,
net/state.py cap_count); the host-side harvester detects count
advancing more than `capacity` since its last drain and latches the
lost-record total as a *warning* in faults/health.py — results stay
exact, only observability degraded.

Chunked dispatch: host-driven loops with windows_per_dispatch > 1
(utils/checkpoint.run_windows, net/build.make_chunked_runner) drain
the ring only once per K-window chunk, so size the capacity >=
windows_per_dispatch or the middle of each chunk is overwritten before
the host ever sees it. The overrun latch above is the safety net — the
loss is reported, never silent — but a ring that fits a whole chunk is
the intended configuration.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax

from shadow_tpu.core import collectives, simtime

I32 = jnp.int32
I64 = jnp.int64

# plane name -> dtype, in record order (harvest.py iterates this)
PLANES = (
    ("wstart", I64),
    ("wend", I64),
    ("events", I64),
    ("micro_steps", I64),
    ("routed_local", I64),
    ("routed_cross", I64),
    ("drops", I64),
    ("retx", I64),
    ("qocc_min", I32),
    ("qocc_max", I32),
    ("qocc_sum", I64),
    ("active_lanes", I64),
    ("fastpath", I32),
    # open-system injection (inject/staging.py), all zero when off:
    ("injected", I64),      # staged events merged this window (global)
    ("inj_dropped", I64),   # merges lost to full rows this window
    ("inj_deferred", I64),  # staged, pending beyond wend (replicated)
)

DEFAULT_CAPACITY = 4096


@struct.dataclass
class TelemetryRing:
    """Fixed-capacity ring of per-window records ([W] planes) plus the
    running scalars the per-window deltas are computed against."""

    wstart: jax.Array        # [W] i64
    wend: jax.Array          # [W] i64
    events: jax.Array        # [W] i64
    micro_steps: jax.Array   # [W] i64
    routed_local: jax.Array  # [W] i64
    routed_cross: jax.Array  # [W] i64
    drops: jax.Array         # [W] i64
    retx: jax.Array          # [W] i64
    qocc_min: jax.Array      # [W] i32
    qocc_max: jax.Array      # [W] i32
    qocc_sum: jax.Array      # [W] i64
    active_lanes: jax.Array  # [W] i64
    fastpath: jax.Array      # [W] i32
    injected: jax.Array      # [W] i64
    inj_dropped: jax.Array   # [W] i64
    inj_deferred: jax.Array  # [W] i64
    # monotonic windows-recorded counter; slot = count % W. The host
    # detects overruns from count jumps (never a device-side latch:
    # the whole-run device program cannot see host drains).
    count: jax.Array         # [] i64
    # cumulative counters at the previous record (for per-window deltas
    # of counters that only exist as running totals in NetState/TcpState)
    prev_drops: jax.Array    # [] i64
    prev_retx: jax.Array     # [] i64
    # --- lane-isolated runs (core/lanes.py), both None when off -----
    # Per-lane fan-out of the events plane: lane_events[w, r] is the
    # events lane r executed in window w (delta of the cumulative
    # ctr_events_exec lane share). Single-shard only, like lane
    # isolation itself. None-default: programs without lanes are
    # byte-identical.
    lane_events: Any = None      # [W, R] i64
    prev_lane_exec: Any = None   # [R] i64 cumulative at last record

    @property
    def capacity(self) -> int:
        return self.wstart.shape[0]

    @staticmethod
    def create(capacity: int = DEFAULT_CAPACITY) -> "TelemetryRing":
        if capacity < 1:
            raise ValueError(f"telemetry capacity must be >= 1, got "
                             f"{capacity}")
        planes = {name: jnp.zeros((capacity,), dt) for name, dt in PLANES}
        z = jnp.zeros((), I64)
        return TelemetryRing(count=z, prev_drops=z, prev_retx=z, **planes)


def attach(sim, capacity: int = DEFAULT_CAPACITY):
    """Return `sim` with a telemetry ring attached (no-op if one
    already is). Sim.telem defaults to None — a None field contributes
    no pytree leaves, so checkpoints and jitted programs built without
    telemetry are untouched; attaching is an explicit opt-in that
    changes the pytree structure (and therefore retraces).

    Lane-isolated sims (core/lanes.py — attach lanes FIRST) get the
    per-lane event fan-out planes sized off sim.lanes.replicas."""
    if getattr(sim, "telem", None) is not None:
        return sim
    ring = TelemetryRing.create(capacity)
    lanes = getattr(sim, "lanes", None)
    if lanes is not None:
        ring = ring.replace(
            lane_events=jnp.zeros((capacity, lanes.replicas), I64),
            prev_lane_exec=jnp.zeros((lanes.replicas,), I64))
    return sim.replace(telem=ring)


def _record(ring: TelemetryRing, vals: dict) -> TelemetryRing:
    """Masked one-hot store of one record at slot count % W."""
    W = ring.capacity
    slot = (ring.count % W).astype(I32)
    sel = jnp.arange(W, dtype=I32) == slot
    new = {
        k: jnp.where(sel, jnp.asarray(v).astype(getattr(ring, k).dtype),
                     getattr(ring, k))
        for k, v in vals.items()
    }
    return ring.replace(count=ring.count + 1, **new)


def make_telem_fn(axis: str | None = None):
    """Build the engine's telem_fn(sim, wstart, wend, ev_delta,
    ms_delta) -> sim hook. It runs inside step_window after the window
    fixpoint and BEFORE route_fn, so the outbox still holds the
    window's staged cross-host sends (route clears it).

    `axis` names the shard_map mesh axis; None compiles the
    single-shard identity reductions. All cross-shard sums ride ONE
    psum of a stacked i64 vector (plus one pmax vector and one pmin
    scalar) so telemetry adds three small collectives per window, at
    the barrier where the route all-to-all already synchronizes.

    When sim.telem is None the hook is a trace-time no-op: zero ops in
    the compiled program, so telemetry-off runs are bit-for-bit and
    cost-for-cost identical to builds without this hook."""

    if axis is None:
        def psum(x):
            return x

        pmax = pmin = psum
    else:
        def psum(x):
            return lax.psum(x, axis)

        def pmax(x):
            return collectives.pmax(x, axis)

        def pmin(x):
            return collectives.pmin(x, axis)

    def telem_fn(sim, wstart, wend, ev_delta, ms_delta,
                 active_lanes=None, fastpath=None, inject_deltas=None):
        """active_lanes is the SHARD-LOCAL live-lane count (psummed
        into the record below so it rides the existing collective);
        fastpath is the replicated census-branch indicator. Both
        default to zero for callers predating the sparse fast path.
        inject_deltas is the window's (injected, dropped, deferred)
        from inject.merge_staged — the first two are SHARD-LOCAL
        partials that ride the psum stack, deferred is replicated;
        the engine passes it only when injection is live."""
        ring = getattr(sim, "telem", None)
        if ring is None:
            return sim

        from shadow_tpu.net.state import drop_total

        out = sim.outbox
        occupied = out.occupied()
        lane = sim.net.lane_id
        Hl = lane.shape[0]
        base = lane[0]
        # local = destined to a host this shard owns (contiguous block
        # [base, base+Hl), parallel.shard.route_outbox_sharded); on one
        # shard every valid destination is local.
        local = occupied & (out.dst >= base) & (out.dst < base + Hl)
        n_local = jnp.sum(local, dtype=I64)
        n_cross = jnp.sum(occupied, dtype=I64) - n_local

        drops_cum = jnp.sum(drop_total(sim.net), dtype=I64)
        retx_cum = (jnp.sum(sim.tcp.retx_segs, dtype=I64)
                    if getattr(sim, "tcp", None) is not None
                    else jnp.zeros((), I64))
        # shard-local end-of-drain occupancy; reduced below
        qmin_l, qmax_l, qsum_l = sim.events.occupancy()

        active_l = (jnp.zeros((), I64) if active_lanes is None
                    else jnp.asarray(active_lanes).astype(I64))
        z64 = jnp.zeros((), I64)
        inj_l, injdrop_l, injdef = ((z64, z64, z64)
                                    if inject_deltas is None
                                    else inject_deltas)
        sums = psum(jnp.stack([
            ev_delta.astype(I64), n_local, n_cross, drops_cum, retx_cum,
            qsum_l, active_l, inj_l.astype(I64), injdrop_l.astype(I64),
        ]))
        maxes = pmax(jnp.stack([
            ms_delta.astype(I64), qmax_l.astype(I64),
        ]))
        qmin = pmin(qmin_l)

        ring = _record(ring, dict(
            wstart=jnp.asarray(wstart, simtime.DTYPE),
            wend=jnp.asarray(wend, simtime.DTYPE),
            events=sums[0],
            micro_steps=maxes[0],
            routed_local=sums[1],
            routed_cross=sums[2],
            drops=sums[3] - ring.prev_drops,
            retx=sums[4] - ring.prev_retx,
            qocc_sum=sums[5],
            qocc_min=qmin,
            qocc_max=maxes[1],
            active_lanes=sums[6],
            fastpath=(jnp.zeros((), I32) if fastpath is None
                      else jnp.asarray(fastpath).astype(I32)),
            injected=sums[7],
            inj_dropped=sums[8],
            inj_deferred=injdef.astype(I64),
        ))
        ring = ring.replace(prev_drops=sums[3], prev_retx=sums[4])

        # per-lane event fan-out (single-shard: lane isolation's
        # contract — no collective needed). Stored into the slot
        # _record just wrote (count - 1).
        lanes_st = getattr(sim, "lanes", None)
        if getattr(ring, "lane_events", None) is not None \
                and lanes_st is not None:
            from shadow_tpu.core.lanes import lane_sum

            cum = lane_sum(sim.net.ctr_events_exec,
                           lanes_st.replicas).astype(I64)
            delta = cum - ring.prev_lane_exec
            W = ring.capacity
            sel = (jnp.arange(W, dtype=I32)
                   == ((ring.count - 1) % W).astype(I32))
            ring = ring.replace(
                lane_events=jnp.where(sel[:, None], delta[None, :],
                                      ring.lane_events),
                prev_lane_exec=cum)
        return sim.replace(telem=ring)

    return telem_fn
