"""Device-resident event queues.

The reference keeps one locked binary min-heap of events per host
(ref: priority_queue.c:17-40, scheduler_policy_host_single.c:20-33) with
the deterministic total order key (time, dstHostID, srcHostID,
perSourceSequence) (ref: event.c:110-153). Here each host owns one row
of fixed-capacity struct-of-arrays tensors; "pop" is a masked
lexicographic argmin over the row, so ordering is bit-identical to the
reference's heap order for any thread/shard count.

Cross-host events never target the current window (every inter-host
path latency >= the window length, which is the min path latency — ref:
master.c:450-480, scheduler_policy_host_single.c:171-184), so sends are
staged per *source* host in an Outbox (collision-free writes) and routed
to destination rows once per window by a sort-based shuffle. On a
sharded mesh that shuffle is the all-to-all exchange point.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from flax import struct

from shadow_tpu.core import simtime

I32 = jnp.int32
# Number of generic int32 payload words carried by every event. Wide
# enough for a simulated TCP header (ref: packet.h:66-86): src/dst
# ports, seq, ack, flags, window, timestamp, ts-echo, a 3-range
# selective-ack list, payload ref+len, plus the delivery-status audit
# word (packetfmt.W_STATUS; ref: packet.h:18-40).
NWORDS = 17
# Narrow width for configs without TCP state: just the
# protocol-independent words (packetfmt indices 0..5). Every pass of
# the window loop moves the whole words tensor, so UDP-only workloads
# carrying 6 instead of 17 words nearly halve per-event bytes.
# Producers may build NWORDS-wide rows; sinks fit_words() them to the
# allocated width (trailing TCP words are zero in non-TCP configs).
NWORDS_BASE = 6


def fit_words(words: jax.Array, width: int) -> jax.Array:
    """Pad (zeros) or slice the trailing word dim to `width`. Slicing
    is only sound when the dropped columns are zero — guaranteed
    because narrow queues exist only in non-TCP configs, where nothing
    writes the TCP header words."""
    w = words.shape[-1]
    if w == width:
        return words
    if w > width:
        return words[..., :width]
    pad = [(0, 0)] * (words.ndim - 1) + [(0, width - w)]
    return jnp.pad(words, pad)


class EventKind:
    """Builtin event kinds. The reference's Task is an arbitrary C
    closure (ref: task.c:13-21); on device we enumerate handler ids.
    Kinds >= USER are claimed by application models."""

    NONE = 0
    PACKET = 1          # packet arrival at dst host's upstream router
    PACKET_LOCAL = 2    # loopback delivery (ref: network_interface.c:546-554)
    TIMER = 3           # timerfd expiration (ref: timer.c)
    PROC_START = 4      # process start (ref: process.c:1326-1360)
    PROC_STOP = 5
    NIC_RECV = 6        # rx token-bucket drain retry (ref: network_interface.c:421-455)
    NIC_SEND = 7        # tx token-bucket drain retry
    TCP_RTX_TIMER = 8   # TCP retransmission timeout
    TCP_CLOSE_TIMER = 9  # TIMEWAIT 60s close timer (ref: tcp.c:604-699)
    TCP_DACK_TIMER = 10  # delayed-ACK timer
    HEARTBEAT = 11      # tracker heartbeat (ref: tracker.c:607)
    TCP_FLUSH = 12      # same-time flush continuation: one coalesced
                        # ACK can admit far more segments than one
                        # micro-step packetizes; the chain unwinds in
                        # the window fixpoint (ref: _tcp_flush's while
                        # loop, tcp.c:1121-...)
    FAULT_WAKEUP = 13   # pending no-op seeded at each fault-plan time
                        # so a window boundary lands at (or before) the
                        # fault even in sparse workloads (faults/apply)
    USER = 16


@struct.dataclass
class EventQueue:
    """Per-host event store: row h = host h's pending events.

    time == simtime.INVALID marks an empty slot. `seq` is the
    per-*source*-host sequence number that makes the total order
    deterministic (ref: event.c:29-35,110-153)."""

    time: jax.Array   # [H, K] i64
    kind: jax.Array   # [H, K] i32
    src: jax.Array    # [H, K] i32
    seq: jax.Array    # [H, K] i32
    words: jax.Array  # [H, K, NWORDS] i32
    # Per-source-host monotonic event id (ref: host_getNewEventID).
    next_seq: jax.Array   # [H] i32
    # Sticky count of events dropped because a row was full. The host
    # side checks this between windows and re-runs with a larger K
    # (the reference never drops events; neither do we silently).
    overflow: jax.Array   # [] i32
    # Optional per-host attribution plane for the same latch ([H] i32),
    # attached by core/lanes.attach for lane-isolated ensemble runs.
    # None (the default) contributes no pytree leaves, so programs and
    # checkpoints built without lane isolation stay byte-identical
    # (same contract as Sim.telem / Sim.inject). Invariant when
    # attached: overflow == sum(overflow_h) — every bump site below
    # updates both, attributing drops to the DESTINATION row.
    overflow_h: Any = None

    @property
    def num_hosts(self) -> int:
        return self.time.shape[0]

    @property
    def capacity(self) -> int:
        return self.time.shape[1]

    @staticmethod
    def create(num_hosts: int, capacity: int,
               nwords: int = NWORDS) -> "EventQueue":
        return EventQueue(
            time=jnp.full((num_hosts, capacity), simtime.INVALID, simtime.DTYPE),
            kind=jnp.zeros((num_hosts, capacity), I32),
            src=jnp.zeros((num_hosts, capacity), I32),
            seq=jnp.zeros((num_hosts, capacity), I32),
            words=jnp.zeros((num_hosts, capacity, nwords), I32),
            next_seq=jnp.zeros((num_hosts,), I32),
            overflow=jnp.zeros((), I32),
        )

    def valid(self) -> jax.Array:
        return self.time != simtime.INVALID

    def fill_count(self) -> jax.Array:
        """[H] number of occupied slots per host row."""
        return jnp.sum(self.valid(), axis=1, dtype=I32)

    def occupancy(self) -> tuple:
        """(min, max, sum) of per-host occupied slots — the telemetry
        ring's queue-occupancy probe (shard-local values; the telem
        hook reduces them across shards at the window barrier)."""
        fill = self.fill_count()
        return (jnp.min(fill), jnp.max(fill),
                jnp.sum(fill, dtype=simtime.DTYPE))

    def min_time(self) -> jax.Array:
        """[H] earliest pending event time per host (INVALID if none).
        The per-shard reduction of this is the conservative barrier's
        min-next-event-time (ref: scheduler.c:393-398)."""
        return jnp.min(self.time, axis=1)


class Popped(NamedTuple):
    """One popped event per host lane ([H]-shaped; valid=False lanes
    hold garbage and must be masked by handlers)."""

    valid: jax.Array  # [H] bool
    time: jax.Array   # [H] i64
    kind: jax.Array   # [H] i32
    src: jax.Array    # [H] i32
    seq: jax.Array    # [H] i32
    words: jax.Array  # [H, NWORDS] i32

    def word(self, i: int) -> jax.Array:
        return self.words[:, i]


def _onehot(mask: jax.Array, slot: jax.Array, width: int) -> jax.Array:
    """[H] masked slot -> [H, width] one-hot row selector. Writes via
    jnp.where(onehot, ...) instead of scatter: XLA fuses selects, while
    each scatter is a separate slow-to-compile op (this path runs every
    micro-step)."""
    return mask[:, None] & (jnp.arange(width)[None, :] == slot[:, None])


def _put(arr: jax.Array, sel: jax.Array, value) -> jax.Array:
    """Masked row write arr[H,W] (or [H,W,NWORDS] when value is
    [H,NWORDS]) under a one-hot selector."""
    value = jnp.asarray(value, arr.dtype)
    if arr.ndim == 3:
        return jnp.where(sel[:, :, None], value[:, None, :], arr)
    v = value[:, None] if value.ndim == 1 else value
    return jnp.where(sel, v, arr)


def _tie_key(src: jax.Array, seq: jax.Array) -> jax.Array:
    """Pack (srcHost, perSourceSeq) into one sortable i64 — the 3rd and
    4th keys of the reference's event order (ref: event.c:137-152).
    (dstHost, the 2nd key, is the row index here.)"""
    return (src.astype(jnp.int64) << 32) | seq.astype(jnp.uint32).astype(jnp.int64)


def pop_earliest(q: EventQueue, horizon) -> tuple[EventQueue, Popped]:
    """Pop each host's earliest event with time < horizon.

    This is the device analog of one scheduler_pop round across all
    hosts at once (ref: scheduler.c:359-377): one host's events stay
    serial (one pop per micro-step), different hosts pop in parallel.
    (Whole-window batching lives in net/bulk.py instead.)
    """
    t = q.time  # [H, K]
    # Lexicographic argmin over (time, src, seq) within each row.
    tmin = jnp.min(t, axis=1, keepdims=True)              # [H,1]
    is_tmin = t == tmin
    tie = jnp.where(is_tmin, _tie_key(q.src, q.seq), jnp.iinfo(jnp.int64).max)
    idx = jnp.argmin(tie, axis=1)                          # [H]
    rows = jnp.arange(q.num_hosts)
    ptime = t[rows, idx]
    valid = ptime < jnp.asarray(horizon, simtime.DTYPE)
    popped = Popped(
        valid=valid,
        time=ptime,
        kind=q.kind[rows, idx],
        src=q.src[rows, idx],
        seq=q.seq[rows, idx],
        words=q.words[rows, idx],
    )
    # Clear popped slots (only where valid).
    sel = _onehot(valid, idx, q.capacity)
    new_time = jnp.where(sel, simtime.INVALID, q.time)
    return q.replace(time=new_time), popped


def push_rows(
    q: EventQueue,
    mask: jax.Array,   # [H] bool — which rows receive an event
    time: jax.Array,   # [H] i64
    kind: jax.Array,   # [H] i32
    src: jax.Array,    # [H] i32
    seq: jax.Array,    # [H] i32
    words: jax.Array,  # [H, NWORDS] i32
) -> EventQueue:
    """Insert one event into each masked host row (first free slot)."""
    words = fit_words(words, q.words.shape[-1])
    free = ~q.valid()                                     # [H, K]
    has_free = jnp.any(free, axis=1)
    slot = jnp.argmax(free, axis=1)                       # first free slot
    ok = mask & has_free
    sel = _onehot(ok, slot, q.capacity)
    q = q.replace(
        time=_put(q.time, sel, time),
        kind=_put(q.kind, sel, kind),
        src=_put(q.src, sel, src),
        seq=_put(q.seq, sel, seq),
        words=_put(q.words, sel, words),
        overflow=q.overflow + jnp.sum(mask & ~has_free, dtype=I32),
    )
    if q.overflow_h is not None:
        q = q.replace(
            overflow_h=q.overflow_h + (mask & ~has_free).astype(I32))
    return q


@struct.dataclass
class Outbox:
    """Cross-host events staged per *source* host, so writes are
    collision-free inside a micro-step. Routed to destination rows once
    per window by route_outbox() (the shard-exchange point;
    ref: worker_sendPacket, worker.c:243-304 is the only place events
    cross hosts)."""

    dst: jax.Array    # [H, M] i32  (-1 = empty)
    time: jax.Array   # [H, M] i64
    kind: jax.Array   # [H, M] i32
    src: jax.Array    # [H, M] i32
    seq: jax.Array    # [H, M] i32
    words: jax.Array  # [H, M, NWORDS] i32
    count: jax.Array  # [H] i32
    overflow: jax.Array  # [] i32
    # narrow-tier telemetry (VERDICT r4 #10): how often the route /
    # exchange took the narrow vs full-width branch, and the largest
    # occupancy the gate ever measured — a new workload that silently
    # overflows the tier shows up as narrow_miss > 0 instead of an
    # invisible slow branch. Running totals survive clear_outbox.
    narrow_hit: jax.Array   # [] i32 windows on the narrow branch
    narrow_miss: jax.Array  # [] i32 windows forced to full width
    max_occupied: jax.Array  # [] i32 max occupancy the gate measured
    # sparse-window layer 3: windows whose outbox staged nothing, so
    # route_outbox skipped the insert pipeline entirely (and, sharded,
    # the all-to-all's cheap branch). Running total, like the narrow
    # counters.
    route_elided: jax.Array  # [] i32 windows with an empty exchange
    # Optional per-SOURCE-host overflow attribution ([H] i32) — same
    # opt-in / invariant contract as EventQueue.overflow_h.
    overflow_h: Any = None

    @property
    def num_hosts(self) -> int:
        return self.dst.shape[0]

    @property
    def capacity(self) -> int:
        return self.dst.shape[1]

    def occupied(self) -> jax.Array:
        """[H, M] bool: slots holding a staged entry (dst >= 0).
        `count` alone cannot answer this — the TCP bulk pass stages at
        sparse columns, so consumers (route, telemetry) must test the
        dst plane."""
        return self.dst >= 0

    @staticmethod
    def create(num_hosts: int, capacity: int,
               nwords: int = NWORDS) -> "Outbox":
        return Outbox(
            dst=jnp.full((num_hosts, capacity), -1, I32),
            time=jnp.full((num_hosts, capacity), simtime.INVALID, simtime.DTYPE),
            kind=jnp.zeros((num_hosts, capacity), I32),
            src=jnp.zeros((num_hosts, capacity), I32),
            seq=jnp.zeros((num_hosts, capacity), I32),
            words=jnp.zeros((num_hosts, capacity, nwords), I32),
            count=jnp.zeros((num_hosts,), I32),
            overflow=jnp.zeros((), I32),
            narrow_hit=jnp.zeros((), I32),
            narrow_miss=jnp.zeros((), I32),
            max_occupied=jnp.zeros((), I32),
            route_elided=jnp.zeros((), I32),
        )


def outbox_append(
    out: Outbox,
    mask: jax.Array,   # [H] bool
    dst: jax.Array,    # [H] i32
    time: jax.Array,   # [H] i64
    kind: jax.Array,   # [H] i32
    src: jax.Array,    # [H] i32
    seq: jax.Array,    # [H] i32
    words: jax.Array,  # [H, NWORDS] i32
) -> Outbox:
    words = fit_words(words, out.words.shape[-1])
    ok = mask & (out.count < out.capacity)
    sel = _onehot(ok, out.count, out.capacity)
    if out.overflow_h is not None:
        out = out.replace(
            overflow_h=out.overflow_h
            + (mask & ~(out.count < out.capacity)).astype(I32))
    return out.replace(
        dst=_put(out.dst, sel, dst),
        time=_put(out.time, sel, time),
        kind=_put(out.kind, sel, kind),
        src=_put(out.src, sel, src),
        seq=_put(out.seq, sel, seq),
        words=_put(out.words, sel, words),
        count=out.count + ok.astype(I32),
        overflow=out.overflow + jnp.sum(mask & ~(out.count < out.capacity), dtype=I32),
    )


def compact_rows(q: EventQueue) -> EventQueue:
    """Stable-partition each row so occupied slots are contiguous at the
    front. Pop order is argmin-based, so intra-row layout is free; this
    just makes free slots addressable as fill_count + rank."""
    empty = ~q.valid()
    order = jnp.argsort(empty, axis=1, stable=True)       # [H, K]
    take = lambda a: jnp.take_along_axis(a, order, axis=1)
    return q.replace(
        time=take(q.time), kind=take(q.kind), src=take(q.src), seq=take(q.seq),
        words=jnp.take_along_axis(q.words, order[..., None], axis=1),
    )


def segment_ranks(sorted_keys: jax.Array, num_keys: int) -> jax.Array:
    """[n] i32 rank of each element within its run of equal keys (keys
    must already be sorted, each in [0, num_keys]). Counted per key
    and offset by the exclusive prefix sum over the num_keys + 1
    keys: a running max over all n elements (the obvious form) took
    the TPU compiler 124 s at n=491,520 (v5e, jax 0.9), this 1.6 s."""
    n = sorted_keys.shape[0]
    cnt = jnp.zeros((num_keys + 1,), I32).at[sorted_keys].add(
        1, indices_are_sorted=True)
    start = jnp.cumsum(cnt, dtype=I32) - cnt
    return jnp.arange(n, dtype=I32) - start[sorted_keys]


# Below this many entries key_counts is an MXU product: f32 sums of
# 0/1 products are exact up to 2^24.
MXU_COUNT_LIMIT = 1 << 24


def key_counts(keys: jax.Array, num_keys: int) -> jax.Array:
    """[num_keys] i32 count of each key in [0, num_keys); key num_keys
    (the dropped bin) is not counted. Below MXU_COUNT_LIMIT entries the
    histogram is one bf16 one-hot product on the MXU,
    one_hot(k // 128)^T . one_hot(k % 128) accumulated in f32, whose
    flat index k // 128 * 128 + k % 128 is k again: the dropped key
    falls past num_keys or outside the one-hot. XLA fuses both
    one-hots into the dot, so neither is materialised (v5e, n =
    245,760, H = 10,240: 0.26 ms, where a scatter-add, serialized per
    entry, took 2.2 ms; products batched over n took as long, so the
    MXU is not what bounds it). Above the limit
    it is that scatter-add, which needs the keys sorted."""
    n = keys.shape[0]
    if n >= MXU_COUNT_LIMIT:
        return jnp.zeros((num_keys + 1,), I32).at[keys].add(
            1, indices_are_sorted=True)[:num_keys]
    lanes = 128
    hi = jax.nn.one_hot(keys // lanes, -(-num_keys // lanes),
                        dtype=jnp.bfloat16)
    lo = jax.nn.one_hot(keys % lanes, lanes, dtype=jnp.bfloat16)
    cnt = jnp.einsum("nh,nl->hl", hi, lo,
                     preferred_element_type=jnp.float32)
    return cnt.astype(I32).reshape(-1)[:num_keys]


# Group width for insert_flat's sort-free "count-route": cross-group
# ranks come from a scatter-add [n/G, H] count matrix + exclusive
# cumsum, within-group ranks from an [n/G, G, G] compare cube. Larger
# G shrinks the count matrix and grows the cube. (Kept for
# measurement; "sort2" superseded it as the accelerator default in r4
# — 65.7 -> 30.4 ms/window at 10k hosts on v5e.)
INSERT_GROUP = 64
# Above these element counts the count matrix / free-slot cube are
# worse than the sort path (and at 100k unsharded hosts the count
# matrix alone would be ~30 GB) — fall back to sorting.
COUNT_MATRIX_BUDGET = 400_000_000
SLOT_CUBE_BUDGET = 1_000_000_000


def _insert_impl(n: int, H: int) -> str:
    if jax.default_backend() == "cpu":
        # CPU gathers/sorts are cheap; the select sweep and padded
        # scatter are pure waste there
        return "sort"
    # key sort + select sweep (Pallas mailbox) or sorted scatter: no
    # count matrix, no cube — and no scale ceiling (the count matrix
    # at 100k hosts would be ~30 GB)
    return "sort2"


def _pack_time(t: jax.Array) -> tuple[jax.Array, jax.Array]:
    """i64 -> (lo, hi) i32 words, exact for every bit pattern."""
    lo = t.astype(jnp.uint32).astype(I32)
    hi = (t >> 32).astype(I32)
    return lo, hi


def _unpack_time(lo: jax.Array, hi: jax.Array) -> jax.Array:
    return (hi.astype(jnp.int64) << 32) | lo.astype(jnp.uint32).astype(
        jnp.int64)


def _free_slot_of_rank(q: EventQueue, impl: str) -> jax.Array:
    """[H, K] map: rank r (among a row's free slots, ascending slot
    order) -> slot index, K where the row has fewer than r+1 free
    slots. Insertion fills holes in place — the queue is never
    compacted (pop order is argmin-based, so intra-row layout carries
    no semantics; both impls produce identical values so plane layout
    is impl-independent)."""
    H, K = q.time.shape
    free = ~q.valid()                                      # [H,K]
    if impl == "count" and H * K * K <= SLOT_CUBE_BUDGET:
        free_rank = jnp.cumsum(free, axis=1, dtype=I32) - free
        hit = free[:, :, None] & (
            free_rank[:, :, None] == jnp.arange(K)[None, None, :])
        slot = jnp.sum(
            jnp.where(hit, jnp.arange(K)[:, None], 0), axis=1, dtype=I32)
        return jnp.where(jnp.any(hit, axis=1), slot, K)
    # row-sort mechanism, same values: free slots first, index order
    order = jnp.argsort(~free, axis=1, stable=True).astype(I32)
    n_free = jnp.sum(free, axis=1, dtype=I32)              # [H]
    return jnp.where(jnp.arange(K)[None, :] < n_free[:, None], order, K)


# Per-destination-row arrival budget of the "sort2" select sweep: when
# every destination row receives at most this many entries (measured
# 10k PHOLD: max 23), the insert needs NO per-entry scatter at all —
# a windowed gather (H index rows) plus INSERT_SWEEP dense selects.
# Rows over budget fall back to the sorted-scatter form via lax.cond.
INSERT_SWEEP = 32


def _queue_packed(q: EventQueue):
    """The queue's planes as one [H, K, 5+W] i32 tensor."""
    return jnp.concatenate(
        [jnp.stack(_pack_time(q.time), axis=2), q.kind[:, :, None],
         q.src[:, :, None], q.seq[:, :, None], q.words], axis=2)


def _queue_unpacked(q: EventQueue, packed_q, overflow_add,
                    overflow_add_h=None):
    q = q.replace(
        time=_unpack_time(packed_q[:, :, 0], packed_q[:, :, 1]),
        kind=packed_q[:, :, 2],
        src=packed_q[:, :, 3],
        seq=packed_q[:, :, 4],
        words=packed_q[:, :, 5:],
        overflow=q.overflow + overflow_add,
    )
    if q.overflow_h is not None and overflow_add_h is not None:
        q = q.replace(overflow_h=q.overflow_h + overflow_add_h)
    return q


def _insert_sorted_scatter(q: EventQueue, rowc, packed, n, H, K):
    """The "sort2" insert mechanism: sort the entries by destination
    row (a two-operand stable lax.sort of the row key and the entry
    index), permute the packed planes with ONE [n, P] row gather, then
    apply the sorted stream with one of two writers:

    - select sweep (common case, every destination row receives at
      most INSERT_SWEEP entries): per-row arrival counts come from one
      one-hot histogram product on the MXU (key_counts); each row's
      arrivals are pulled as a contiguous [INSERT_SWEEP, P] window of
      the sorted stream with ONE gather of H index rows (per-entry
      gathers/scatters on TPU cost ~20-45 ns/row serialized — H rows
      instead of n is the whole win); arrival j then lands in the
      row's j-th free slot via INSERT_SWEEP dense masked selects,
      fully vectorized.
    - sorted scatter (fallback): one lexicographically sorted
      [n, P] scatter into a padded operand; rejected entries redirect
      to a pad row/column that is sliced off, so duplicate pad writes
      are discarded harmlessly.

    Values are bit-identical to the "count"/"sort" mechanisms either
    way: the stable sort preserves caller order within each row, so
    ranks and chosen free slots agree entry-for-entry."""
    P = packed.shape[1]
    # Not a co-sort of all P planes: the TPU compiler's time for a
    # stable multi-operand sort grows steeply with operands x length
    # (v5e, jax 0.9: 23 operands took 7.6 s at n=4,096 and 152 s at
    # n=16,384, where 2 operands took 5.4 s; the 10,240-host PHOLD
    # route has n=491,520). Same stable order, so the same stream.
    with jax.named_scope("sort"):
        row_o, perm = jax.lax.sort((rowc, jnp.arange(n, dtype=I32)),
                                   num_keys=1, is_stable=True)
    with jax.named_scope("permute"):
        packed_o = packed[perm]                            # [n, P]
        valid_o = row_o < H

    # per-destination-row arrival counts (invalid entries fall in the
    # dropped bin H) and each row's start offset in the sorted stream
    with jax.named_scope("count"):
        cnt = key_counts(row_o, H)
        start = jnp.cumsum(cnt, dtype=I32) - cnt           # [H] excl

    free = ~q.valid()                                      # [H, K]
    nfree = jnp.sum(free, axis=1, dtype=I32)
    packed_q = _queue_packed(q)

    Wn = INSERT_SWEEP
    # per-row overflow attribution (lane isolation): both writers drop
    # exactly the arrivals beyond a row's free slots, so the plane add
    # is max(cnt - nfree, 0) either way — computed once, outside the
    # cond, only when the plane is attached (trace-time no-op else)
    ofl_h = (jnp.maximum(cnt - nfree, 0).astype(I32)
             if q.overflow_h is not None else None)

    def _select_sweep(_):
        use_pallas = False
        if jax.default_backend() == "tpu":
            from shadow_tpu.core import insert_pallas

            use_pallas = insert_pallas.mailbox_available(H)
        with jax.named_scope("mailbox"):
            # each row's arrivals as a contiguous window of the stream
            pad_o = jnp.pad(packed_o, ((0, Wn), (0, 0)))
            if use_pallas:
                # pipelined per-row HBM->VMEM DMAs instead of XLA's
                # strictly serial H-iteration gather loop. Mosaic needs
                # the DMA'd minor dim 128-aligned, so the stream is
                # padded P -> 128 (the extra bytes ride otherwise-idle
                # DMA bandwidth; the serial loop they replace was
                # latency bound, not bandwidth bound).
                wide = jnp.pad(pad_o, ((0, 0), (0, 128 - P)))
                win = insert_pallas.mailbox_gather(wide, start,
                                                   Wn)[..., :P]
            else:
                dnums = jax.lax.GatherDimensionNumbers(
                    offset_dims=(1, 2), collapsed_slice_dims=(),
                    start_index_map=(0,))
                win = jax.lax.gather(
                    pad_o, start[:, None], dnums, slice_sizes=(Wn, P),
                    indices_are_sorted=True,
                    mode=jax.lax.GatherScatterMode.CLIP)   # [H, Wn, P]
        with jax.named_scope("sweep"):
            f_rank = jnp.cumsum(free, axis=1, dtype=I32) - free
            acc = packed_q
            for j in range(Wn):
                take = free & (f_rank == j) & (j < cnt)[:, None]
                acc = jnp.where(take[:, :, None], win[:, j, None, :], acc)
            ofl = jnp.sum(jnp.maximum(cnt - nfree, 0), dtype=I32)
        return acc, ofl

    @jax.named_scope("scatter")
    def _sorted_scatter(_):
        rank_o = segment_ranks(row_o, H)
        slot_map = _free_slot_of_rank(q, "sort")           # [H, K]
        # Keep the clipped index sequence genuinely sorted for the
        # hint: invalid entries (row H, clipped to H-1) restart
        # segment_ranks at 0, so pin their rank index to K-1 —
        # (H-1, K-1) repeated is >= every preceding (H-1, k<=K-1)
        # pair. Their cand value is unused (fits requires valid_o).
        rank_c = jnp.where(valid_o, jnp.clip(rank_o, 0, K - 1), K - 1)
        cand = slot_map.at[
            jnp.clip(row_o, 0, H - 1), rank_c].get(
            indices_are_sorted=True)
        fits = valid_o & (rank_o < K) & (cand < K)
        # (row, slot) is lexicographically non-decreasing: rows
        # ascend, and within a row fit slots ascend (rank-th free
        # slot) with the rejected suffix pinned at the pad column K.
        r = jnp.where(valid_o, row_o, H)
        s = jnp.where(fits, cand, K)
        padded = jnp.pad(packed_q, ((0, 1), (0, 1), (0, 0)))
        idx = jnp.stack([r, s], axis=1)                    # [n, 2]
        dnums = jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1,), inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1))
        padded = jax.lax.scatter(
            padded, idx, packed_o, dnums, indices_are_sorted=True,
            unique_indices=False, mode=jax.lax.GatherScatterMode.CLIP)
        ofl = jnp.sum(valid_o & ~fits, dtype=I32)
        return padded[:H, :K], ofl

    packed_q, ofl = jax.lax.cond(
        jnp.max(cnt) <= Wn, _select_sweep, _sorted_scatter, 0)
    return _queue_unpacked(q, packed_q, ofl, ofl_h)


def insert_flat(
    q: EventQueue,
    valid: jax.Array,  # [n] bool
    row: jax.Array,    # [n] i32 *local* destination row
    time: jax.Array,   # [n] i64
    kind: jax.Array,   # [n] i32
    src: jax.Array,    # [n] i32 (global source host id)
    seq: jax.Array,    # [n] i32
    words: jax.Array,  # [n, NWORDS] i32
    impl: str | None = None,
) -> EventQueue:
    """Insert a flat batch of events into their destination rows, in
    caller order within each row (the determinism contract: caller
    order = global source order). Overflow is counted, never silent.

    Each entry's within-row rank = #earlier entries with the same row;
    its slot = the rank-th free slot of that row (holes fill in
    place). Three bit-identical mechanisms, chosen per backend by
    _insert_impl:

    - "sort2" (accelerators, the default): one stable sort of the
      destination rows, the packed planes permuted by one row gather,
      then the select-sweep or sorted-scatter writer
      (_insert_sorted_scatter).
    - "sort" (CPU): stable argsort by row + segment ranks, the
      classic shuffle — cheap where gathers are cheap.
    - "count" (kept for measurement, no longer auto-selected):
      scatter-add a [n/G, H] per-group count matrix, exclusive-cumsum
      for cross-group ranks, an [n/G, G, G] within-group compare cube
      (the r2 design that beat the argsort+gather form on TPU before
      sort2 beat both; INSERT_GROUP/COUNT_MATRIX_BUDGET only matter
      when it is requested explicitly).

    All planes move through ONE packed [.., 5+W] i32 gather/scatter
    (time split into two i32 words) instead of per-plane ops."""
    n = row.shape[0]
    H = q.num_hosts
    K = q.capacity
    W = q.words.shape[-1]
    if impl is None:
        impl = _insert_impl(n, H)
    rowc = jnp.where(valid, row, H)

    tlo, thi = _pack_time(time)
    packed = jnp.concatenate(
        [tlo[:, None], thi[:, None], kind[:, None], src[:, None],
         seq[:, None], words], axis=1)                     # [n, 5+W]

    if impl == "sort2":
        return _insert_sorted_scatter(q, rowc, packed, n, H, K)

    if impl == "count":
        with jax.named_scope("count"):
            G = INSERT_GROUP
            pad = (-n) % G
            rowp = jnp.pad(rowc, (0, pad), constant_values=H)
            ng = rowp.shape[0] // G
            gidx = jnp.arange(ng * G) // G
            cnt = jnp.zeros((ng, H), I32).at[gidx, rowp].add(
                1, mode="drop")
            base_excl = jnp.cumsum(cnt, axis=0, dtype=I32) - cnt
            base = base_excl[
                jnp.clip(gidx, 0, ng - 1), jnp.clip(rowp, 0, H - 1)]
            rg = rowp.reshape(ng, G)
            earlier = jnp.arange(G)[:, None] < jnp.arange(G)[None, :]
            intra = jnp.sum(
                (rg[:, :, None] == rg[:, None, :]) & earlier[None],
                axis=1, dtype=I32).reshape(-1)
            rank = (base + intra)[:n]
        row_o, rank_o, packed_o, valid_o = rowc, rank, packed, valid
    else:
        with jax.named_scope("sort"):
            order = jnp.argsort(rowc, stable=True)
        with jax.named_scope("permute"):
            row_o = rowc[order]
            packed_o = packed[order]
            valid_o = row_o < H
        with jax.named_scope("count"):
            rank_o = segment_ranks(row_o, H)

    with jax.named_scope("count"):
        slot_map = _free_slot_of_rank(q, impl)             # [H,K]
        cand = slot_map[
            jnp.clip(row_o, 0, H - 1), jnp.clip(rank_o, 0, K - 1)]
    with jax.named_scope("scatter"):
        fits = valid_o & (rank_o < K) & (cand < K)
        r = jnp.where(fits, row_o, H)                      # OOB -> drop
        s = jnp.where(fits, cand, K)
        packed_q = _queue_packed(q).at[r, s].set(packed_o, mode="drop")
    ofl_h = None
    if q.overflow_h is not None:
        # destination-row attribution: non-fitting valid entries
        # scatter-added onto their (clipped; masked-off when invalid)
        # destination rows
        ofl_h = jnp.zeros((H,), I32).at[jnp.clip(row_o, 0, H - 1)].add(
            (valid_o & ~fits).astype(I32))
    return _queue_unpacked(q, packed_q,
                           jnp.sum(valid_o & ~fits, dtype=I32), ofl_h)


def clear_outbox(out: Outbox) -> Outbox:
    H, M = out.dst.shape
    return out.replace(
        dst=jnp.full((H, M), -1, I32),
        time=jnp.full((H, M), simtime.INVALID, simtime.DTYPE),
        count=jnp.zeros((H,), I32),
    )


# Narrow-route tier: outbox rows are cursor-appended (left-packed), so
# when every row's count fits this width the route runs over a sliced
# [H, ROUTE_NARROW] view — the whole insert pipeline (sort/scatter,
# rank maps) scales with candidate count, and the capacity is sized
# for worst-case bursts the steady state never reaches (measured r4:
# 10k-host PHOLD load 8 stages max 23/48 per row). None disables.
ROUTE_NARROW = 24


def _route_width(q: EventQueue, out: Outbox, width: int,
                 impl: str | None) -> EventQueue:
    """Insert the first `width` outbox columns of every row."""
    H = out.dst.shape[0]
    n = H * width
    dst = out.dst[:, :width].reshape(n)
    occupied = dst >= 0
    # A dst outside [0, H) is a routing bug — count it, never silently
    # drop.
    bad_dst = occupied & (dst >= H)
    valid = occupied & ~bad_dst
    q = insert_flat(
        q, valid, dst,
        out.time[:, :width].reshape(n), out.kind[:, :width].reshape(n),
        out.src[:, :width].reshape(n), out.seq[:, :width].reshape(n),
        out.words[:, :width].reshape(n, out.words.shape[-1]),
        impl=impl,
    )
    if q.overflow_h is not None:
        # bad_dst is flattened row-major from the SOURCE rows — the
        # destination is out of range, so attribute to the sender
        q = q.replace(overflow_h=q.overflow_h + jnp.sum(
            bad_dst.reshape(H, width), axis=1, dtype=I32))
    return q.replace(overflow=q.overflow + jnp.sum(bad_dst, dtype=I32))


def route_outbox(q: EventQueue, out: Outbox, impl: str | None = None,
                 narrow: int | None = None) -> tuple[EventQueue, Outbox]:
    """Deliver all staged cross-host events into destination rows.

    Single-shard version: destination host ids are row indices
    directly. The multi-chip path runs insert_flat after an all-to-all
    keyed by dst // hosts_per_shard (see shadow_tpu.parallel.shard).
    `impl` overrides the insert mechanism ("count"/"sort"/"sort2") for
    callers whose arrays live on a different backend than
    jax.default_backend() (values are bit-identical either way; this
    is perf-only). `narrow` overrides ROUTE_NARROW.

    Bit-identity of the narrow tier: the gate is the true maximum
    OCCUPIED column (not the per-row count — the UDP bulk pass stages
    replies at sparse time-order columns, net/bulk.py ord_col, so a
    row can hold entries past its count), so slicing drops only empty
    slots, and candidate enumeration order (row-major over the slice)
    preserves the relative order of every occupied entry — ranks,
    slots and overflow accounting are unchanged.
    """
    H, M = out.dst.shape
    width = ROUTE_NARROW if narrow is None else narrow
    if width and width < M:
        occupied_width = jnp.max(
            jnp.where(out.dst >= 0, jnp.arange(M, dtype=I32)[None, :] + 1,
                      0))
        hit = occupied_width <= width
        empty = occupied_width == 0
        out = out.replace(
            narrow_hit=out.narrow_hit + hit.astype(I32),
            narrow_miss=out.narrow_miss + (~hit).astype(I32),
            max_occupied=jnp.maximum(out.max_occupied, occupied_width),
            route_elided=out.route_elided + empty.astype(I32))
        # Empty-exchange elision (sparse-window layer 3): an occupied
        # width of zero means no row staged anything, so the insert
        # pipeline is a structural no-op — skip it. occupied_width
        # counts bad-dst entries too, so empty also implies no
        # overflow accounting is owed.
        q = jax.lax.cond(
            empty,
            lambda qq: qq,
            lambda qq: jax.lax.cond(
                hit,
                lambda q2: _route_width(q2, out, width, impl),
                lambda q2: _route_width(q2, out, M, impl),
                qq),
            q)
    else:
        empty = ~jnp.any(out.dst >= 0)
        out = out.replace(
            route_elided=out.route_elided + empty.astype(I32))
        q = jax.lax.cond(
            empty,
            lambda qq: qq,
            lambda qq: _route_width(qq, out, M, impl),
            q)
    return q, clear_outbox(out)


@struct.dataclass
class EmitBuffer:
    """Per-micro-step emission staging. Handlers run sequentially (one
    masked batch per kind), each lane (= the host whose event was
    popped) appending at its private cursor — deterministic and
    collision-free. apply_emissions() then assigns per-source sequence
    numbers in slot order and moves local events into the queue and
    remote events into the Outbox."""

    dst: jax.Array    # [H, E] i32
    time: jax.Array   # [H, E] i64
    kind: jax.Array   # [H, E] i32
    words: jax.Array  # [H, E, NWORDS] i32
    count: jax.Array  # [H] i32
    overflow: jax.Array  # [] i32
    # Optional per-host overflow attribution ([H] i32) — attached by
    # window_fixpoint when the queue carries its own plane, folded into
    # EventQueue.overflow_h by apply_emissions.
    overflow_h: Any = None

    @property
    def num_hosts(self) -> int:
        return self.dst.shape[0]

    @property
    def capacity(self) -> int:
        return self.dst.shape[1]

    @staticmethod
    def create(num_hosts: int, capacity: int = 4,
               nwords: int = NWORDS) -> "EmitBuffer":
        return EmitBuffer(
            dst=jnp.full((num_hosts, capacity), -1, I32),
            time=jnp.full((num_hosts, capacity), simtime.INVALID, simtime.DTYPE),
            kind=jnp.zeros((num_hosts, capacity), I32),
            words=jnp.zeros((num_hosts, capacity, nwords), I32),
            count=jnp.zeros((num_hosts,), I32),
            overflow=jnp.zeros((), I32),
        )


def emit(
    buf: EmitBuffer,
    mask: jax.Array,          # [H] bool
    dst: jax.Array,           # [H] i32 (dst == lane index -> local)
    time: jax.Array,          # [H] i64
    kind,                     # [H] i32 or int
    words: jax.Array,         # [H, NWORDS] i32
) -> EmitBuffer:
    H = buf.num_hosts
    words = fit_words(words, buf.words.shape[-1])
    kind = jnp.broadcast_to(jnp.asarray(kind, I32), (H,))
    ok = mask & (buf.count < buf.capacity)
    sel = _onehot(ok, buf.count, buf.capacity)
    if buf.overflow_h is not None:
        buf = buf.replace(
            overflow_h=buf.overflow_h
            + (mask & ~(buf.count < buf.capacity)).astype(I32))
    return buf.replace(
        dst=_put(buf.dst, sel, dst),
        time=_put(buf.time, sel, time),
        kind=_put(buf.kind, sel, kind),
        words=_put(buf.words, sel, words),
        count=buf.count + ok.astype(I32),
        overflow=buf.overflow + jnp.sum(mask & ~(buf.count < buf.capacity), dtype=I32),
    )


def emit_words(*vals, num_hosts: int | None = None) -> jax.Array:
    """Assemble an [H, NWORDS] word array from [H] (or scalar) columns."""
    assert len(vals) <= NWORDS, f"{len(vals)} payload words > NWORDS={NWORDS}"
    cols = []
    H = num_hosts
    for v in vals:
        v = jnp.asarray(v)
        if v.ndim == 1:
            H = v.shape[0]
    assert H is not None
    for v in vals:
        v = jnp.asarray(v, I32)
        cols.append(jnp.broadcast_to(v, (H,)))
    while len(cols) < NWORDS:
        cols.append(jnp.zeros((H,), I32))
    return jnp.stack(cols[:NWORDS], axis=1)


def apply_emissions(
    q: EventQueue, out: Outbox, buf: EmitBuffer, lane_id: jax.Array | None = None
) -> tuple[EventQueue, Outbox]:
    """Move staged emissions into the local queue / cross-host outbox,
    assigning per-source sequence numbers in slot order (matching the
    reference's per-push host_getNewEventID ordering).

    `lane_id` is each local row's *global* host id ([H] i32) — the
    identity of the sharded lane. Emission dst fields are global host
    ids; dst == lane_id means a same-host event that stays in the local
    queue. Defaults to arange(H) (single-shard)."""
    H, E = buf.dst.shape
    lane = jnp.arange(H, dtype=I32) if lane_id is None else lane_id.astype(I32)
    nvalid = jnp.zeros((H,), I32)
    for e in range(E):
        v = buf.dst[:, e] >= 0
        seq = q.next_seq + nvalid
        is_local = v & (buf.dst[:, e] == lane)
        is_remote = v & (buf.dst[:, e] != lane)
        q = push_rows(
            q, is_local, buf.time[:, e], buf.kind[:, e], lane, seq, buf.words[:, e]
        )
        out = outbox_append(
            out, is_remote, buf.dst[:, e], buf.time[:, e], buf.kind[:, e],
            lane, seq, buf.words[:, e],
        )
        nvalid = nvalid + v.astype(I32)
    q = q.replace(next_seq=q.next_seq + nvalid,
                  overflow=q.overflow + buf.overflow)
    if q.overflow_h is not None and buf.overflow_h is not None:
        q = q.replace(overflow_h=q.overflow_h + buf.overflow_h)
    return q, out


# --- Window kind census (sparse-window layer 2) -------------------------
#
# One u32 bitmask per window: bit k set when any event of kind k could
# be popped before wend. Kinds >= 31 share bit 31, so the mask can only
# OVER-approximate — sound, because every handler is a masked batch
# update and an all-false mask is the identity (net/step.py documents
# the invariant). The census seeds from the queue at window entry and
# is OR-extended with each micro-step's emissions, so kinds that only
# appear mid-window (e.g. TCP_FLUSH staged by the receive path) are
# re-admitted before their events can be popped.

def _kind_bit(kind: jax.Array) -> jax.Array:
    """One-hot u32 bit per kind; kinds >= 31 collapse onto bit 31."""
    return jnp.uint32(1) << jnp.clip(kind, 0, 31).astype(jnp.uint32)


def _or_reduce(bits: jax.Array) -> jax.Array:
    return jax.lax.reduce(bits, jnp.uint32(0),
                          lambda a, b: jax.lax.bitwise_or(a, b),
                          tuple(range(bits.ndim)))


def kind_census(q: EventQueue, wend) -> jax.Array:
    """[] u32 bitmask of event kinds present in `q` before `wend`."""
    m = q.time < jnp.asarray(wend, simtime.DTYPE)
    return _or_reduce(jnp.where(m, _kind_bit(q.kind), jnp.uint32(0)))


def emit_kind_bits(buf: EmitBuffer) -> jax.Array:
    """[] u32 bitmask of event kinds staged in an EmitBuffer."""
    m = buf.dst >= 0
    return _or_reduce(jnp.where(m, _kind_bit(buf.kind), jnp.uint32(0)))


def census_mask(kinds) -> int:
    """Static u32 mask for a handler family's kind tuple (host side)."""
    m = 0
    for k in kinds:
        m |= 1 << min(int(k), 31)
    return m
