"""Event-queue ordering contract tests.

The contract under test is the reference's deterministic total order
(time, dstHost, srcHost, perSourceSeq) — ref: event.c:110-153 — and
exact delivery of cross-host events via the outbox shuffle."""

import numpy as np
import jax.numpy as jnp
import pytest

from shadow_tpu.core import simtime
from shadow_tpu.core.events import (
    EmitBuffer,
    EventQueue,
    Outbox,
    apply_emissions,
    compact_rows,
    emit,
    emit_words,
    outbox_append,
    pop_earliest,
    push_rows,
    route_outbox,
)


def _push_one(q, host, time, kind=1, src=0, seq=0, w0=0):
    H = q.num_hosts
    mask = jnp.arange(H) == host
    return push_rows(
        q,
        mask,
        jnp.full((H,), time, simtime.DTYPE),
        jnp.full((H,), kind, jnp.int32),
        jnp.full((H,), src, jnp.int32),
        jnp.full((H,), seq, jnp.int32),
        emit_words(w0, num_hosts=H),
    )


def _drain_host(q, host, horizon=simtime.MAX):
    """Pop row `host` to empty; return list of (time, src, seq)."""
    out = []
    while True:
        q, p = pop_earliest(q, horizon)
        if not bool(p.valid[host]):
            break
        out.append((int(p.time[host]), int(p.src[host]), int(p.seq[host])))
    return q, out


def test_pop_orders_by_time_src_seq():
    rng = np.random.default_rng(7)
    q = EventQueue.create(num_hosts=2, capacity=32)
    evs = []
    for i in range(20):
        t = int(rng.integers(0, 5)) * 100  # force ties
        src = int(rng.integers(0, 3))
        seq = i
        evs.append((t, src, seq))
        q = _push_one(q, 0, t, src=src, seq=seq)
    q, popped = _drain_host(q, 0)
    assert popped == sorted(evs)


def test_pop_respects_horizon():
    q = EventQueue.create(num_hosts=1, capacity=8)
    q = _push_one(q, 0, 50)
    q = _push_one(q, 0, 150)
    q2, p = pop_earliest(q, horizon=100)
    assert bool(p.valid[0]) and int(p.time[0]) == 50
    q3, p = pop_earliest(q2, horizon=100)
    assert not bool(p.valid[0])
    # the 150 event is still there
    assert int(q3.min_time()[0]) == 150


def test_push_overflow_is_counted_not_silent():
    q = EventQueue.create(num_hosts=1, capacity=2)
    for t in (1, 2, 3):
        q = _push_one(q, 0, t)
    assert int(q.overflow) == 1
    assert int(q.fill_count()[0]) == 2


def test_route_outbox_delivers_to_dst_rows():
    H = 4
    q = EventQueue.create(H, capacity=8)
    q = _push_one(q, 2, 10)  # pre-existing event on host 2
    out = Outbox.create(H, capacity=8)
    rows = jnp.arange(H)
    # every host sends one event to host 2 at time 100+h
    out = outbox_append(
        out,
        jnp.ones((H,), bool),
        jnp.full((H,), 2, jnp.int32),
        (100 + rows).astype(simtime.DTYPE),
        jnp.full((H,), 1, jnp.int32),
        rows.astype(jnp.int32),
        jnp.zeros((H,), jnp.int32),
        emit_words(0, num_hosts=H),
    )
    q, out = route_outbox(q, out)
    assert int(out.count.sum()) == 0
    assert int(q.fill_count()[2]) == 5
    assert int(q.fill_count()[0]) == 0
    q, popped = _drain_host(q, 2)
    assert [t for t, _, _ in popped] == [10, 100, 101, 102, 103]


def test_route_outbox_overflow_counted():
    H = 2
    q = EventQueue.create(H, capacity=2)
    out = Outbox.create(H, capacity=4)
    ones = jnp.ones((H,), bool)
    for i in range(3):
        out = outbox_append(
            out, ones,
            jnp.full((H,), 1, jnp.int32),
            jnp.full((H,), 100 + i, simtime.DTYPE),
            jnp.full((H,), 1, jnp.int32),
            jnp.arange(H, dtype=jnp.int32),
            jnp.zeros((H,), jnp.int32),
            emit_words(0, num_hosts=H),
        )
    q, out = route_outbox(q, out)  # 6 events -> host 1 row of capacity 2
    assert int(q.fill_count()[1]) == 2
    assert int(q.overflow) == 4


def test_route_outbox_bad_dst_counted_as_overflow():
    H = 2
    q = EventQueue.create(H, capacity=4)
    out = Outbox.create(H, capacity=4)
    mask = jnp.array([True, False])
    out = outbox_append(
        out, mask,
        jnp.full((H,), H, jnp.int32),  # dst out of range
        jnp.full((H,), 100, simtime.DTYPE),
        jnp.full((H,), 1, jnp.int32),
        jnp.zeros((H,), jnp.int32),
        jnp.zeros((H,), jnp.int32),
        emit_words(0, num_hosts=H),
    )
    q, out = route_outbox(q, out)
    assert int(q.fill_count().sum()) == 0
    assert int(q.overflow) == 1


def test_apply_emissions_assigns_seq_in_slot_order():
    H = 2
    q = EventQueue.create(H, capacity=8)
    out = Outbox.create(H, capacity=8)
    buf = EmitBuffer.create(H, capacity=4)
    ones = jnp.ones((H,), bool)
    lane = jnp.arange(H, dtype=jnp.int32)
    w = emit_words(0, num_hosts=H)
    t = jnp.full((H,), 5, simtime.DTYPE)
    # host h emits: local@5, remote->other@5, local@5
    buf = emit(buf, ones, lane, t, 1, w)
    buf = emit(buf, ones, 1 - lane, t, 1, w)
    buf = emit(buf, ones, lane, t, 1, w)
    q, out = apply_emissions(q, out, buf)
    assert list(np.asarray(q.next_seq)) == [3, 3]
    # local events got seq 0 and 2; remote got seq 1
    q2, popped = _drain_host(q, 0)
    assert [(s, n) for _, s, n in popped] == [(0, 0), (0, 2)]
    assert int(out.seq[0, 0]) == 1
    assert int(out.dst[0, 0]) == 1


def test_compact_rows_preserves_multiset():
    q = EventQueue.create(2, capacity=6)
    for t in (30, 10, 20):
        q = _push_one(q, 1, t)
    q2, p = pop_earliest(q, simtime.MAX)  # pops 10, leaves hole at slot 1
    q3 = compact_rows(q2)
    v = np.asarray(q3.valid()[1])
    assert v[:2].all() and not v[2:].any()
    _, popped = _drain_host(q3, 1)
    assert [t for t, _, _ in popped] == [20, 30]


def test_insert_flat_impls_bit_identical():
    """insert_flat has two rank computations (count-route for
    accelerators, stable sort for CPU); both must place every entry
    in the same slot, including hole-filling, ordering within a row,
    and overflow counting."""
    import numpy as np

    from shadow_tpu.core.events import insert_flat

    rng = np.random.default_rng(42)
    H, K, W = 13, 7, 6
    n = 150
    q0 = EventQueue.create(H, K, nwords=W)
    # pre-occupy random slots (holes pattern) with live events
    occ = rng.random((H, K)) < 0.4
    t0 = jnp.where(jnp.asarray(occ),
                   jnp.asarray(rng.integers(1, 1000, (H, K))),
                   simtime.INVALID)
    q0 = q0.replace(time=t0.astype(q0.time.dtype))

    valid = jnp.asarray(rng.random(n) < 0.8)
    row = jnp.asarray(rng.integers(0, H, n), jnp.int32)
    time = jnp.asarray(rng.integers(1000, 9999, n))
    kind = jnp.asarray(rng.integers(1, 5, n), jnp.int32)
    src = jnp.asarray(rng.integers(0, H, n), jnp.int32)
    seq = jnp.asarray(np.arange(n), jnp.int32)
    words = jnp.asarray(rng.integers(-2**31, 2**31 - 1, (n, W)), jnp.int32)

    qa = insert_flat(q0, valid, row, time, kind, src, seq, words,
                     impl="count")
    qb = insert_flat(q0, valid, row, time, kind, src, seq, words,
                     impl="sort")
    for f in ("time", "kind", "src", "seq", "words", "overflow"):
        np.testing.assert_array_equal(
            np.asarray(getattr(qa, f)), np.asarray(getattr(qb, f)),
            err_msg=f"{f} diverged between impls")
    # overflow must have engaged (n >> free capacity) and be counted
    assert int(qa.overflow) > 0


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_segment_ranks_matches_loop(seed):
    """segment_ranks counts per key; the loop is the reference."""
    from shadow_tpu.core.events import segment_ranks

    rng = np.random.default_rng(seed)
    keys = np.sort(rng.integers(0, 9, size=200))   # keys in [0, 8]
    want, run = [], 0
    for i, k in enumerate(keys):
        run = run + 1 if i and k == keys[i - 1] else 0
        want.append(run)
    got = np.asarray(segment_ranks(jnp.asarray(keys, jnp.int32), 8))
    np.testing.assert_array_equal(got, want)
