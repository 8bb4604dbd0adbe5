"""Bit-identity matrix over the outbox-insert mechanisms and the
narrow-route tier (core/events.py insert_flat / route_outbox).

The accelerator default ("sort2": co-sort + select-sweep with a
sorted-scatter fallback under lax.cond) never runs in the CPU suite
via _insert_impl, so these tests request every impl explicitly and
compare raw queue planes pairwise. Shapes are chosen to exercise:

- the narrow tier (outbox capacity > width) and its full-width
  fallback,
- the select sweep (all destination rows under INSERT_SWEEP) and the
  sorted-scatter branch (a hot row overloaded past it),
- queue-row overflow accounting (more arrivals than free slots),
- sort2's per-row arrival counts (key_counts) as the MXU histogram
  and as the scatter-add it keeps for the largest inputs,
- SPARSE outbox rows: the UDP bulk pass stages replies at time-order
  columns (net/bulk.py ord_col), so occupied entries can sit past the
  per-row count with holes below them — the narrow gate must widen on
  the true occupied width, not the count (r4 review finding: gating
  on count silently dropped such entries).
"""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.core import simtime
from shadow_tpu.core import events as ev

INVALID = int(simtime.INVALID)
IMPLS = ("sort", "count", "sort2")


def _mkqueue(rng, H, K, W, fill):
    q = ev.EventQueue.create(H, K, nwords=W)
    valid = rng.random((H, K)) < fill
    t = np.where(valid, rng.integers(100, 10_000, (H, K)), INVALID)
    return q.replace(
        time=jnp.asarray(t, simtime.DTYPE),
        kind=jnp.asarray(np.where(valid, 1, 0), jnp.int32),
        src=jnp.asarray(rng.integers(0, H, (H, K)), jnp.int32),
        seq=jnp.asarray(rng.integers(0, 99, (H, K)), jnp.int32),
        words=jnp.asarray(rng.integers(0, 1 << 20, (H, K, W)), jnp.int32))


def _mkoutbox(rng, H, M, W, cols_of_row, dst_of):
    """Build an outbox with entries at explicit (row, col) positions.
    count is the number of occupied columns per row — NOT the width —
    exactly what outbox_append/bulk staging would produce."""
    out = ev.Outbox.create(H, M, nwords=W)
    dst = np.full((H, M), -1, np.int64)
    tm = np.full((H, M), INVALID, np.int64)
    kd = np.zeros((H, M), np.int64)
    sq = np.zeros((H, M), np.int64)
    wd = np.zeros((H, M, W), np.int64)
    cnt = np.zeros((H,), np.int64)
    for h in range(H):
        for c in cols_of_row(h):
            dst[h, c] = dst_of(h, c)
            tm[h, c] = rng.integers(100, 10_000)
            kd[h, c] = rng.integers(1, 5)
            sq[h, c] = rng.integers(0, 99)
            wd[h, c] = rng.integers(0, 1 << 20, W)
            cnt[h] += 1
    return out.replace(
        dst=jnp.asarray(dst, jnp.int32), time=jnp.asarray(tm, simtime.DTYPE),
        kind=jnp.asarray(kd, jnp.int32),
        src=jnp.asarray(np.broadcast_to(np.arange(H)[:, None], (H, M)),
                        jnp.int32),
        seq=jnp.asarray(sq, jnp.int32), words=jnp.asarray(wd, jnp.int32),
        count=jnp.asarray(cnt, jnp.int32))


def _snap(q):
    return jax.tree_util.tree_map(
        np.asarray, (q.time, q.kind, q.src, q.seq, q.words, q.overflow))


def _assert_all_equal(q, out, narrows):
    """Every impl at every narrow width, and sort2 once more with its
    per-row counts forced onto the scatter-add that key_counts keeps
    above MXU_COUNT_LIMIT (these shapes take the MXU product)."""
    ref = None
    runs = [(impl, ev.MXU_COUNT_LIMIT) for impl in IMPLS] + [("sort2", 0)]
    for impl, limit in runs:
        for narrow in narrows:
            with mock.patch.object(ev, "MXU_COUNT_LIMIT", limit):
                q2, out2 = ev.route_outbox(q, out, impl=impl, narrow=narrow)
            s = _snap(q2)
            if ref is None:
                ref = s
            else:
                for i, (a, b) in enumerate(zip(ref, s)):
                    assert np.array_equal(a, b), (impl, limit, narrow, i)
            assert int(jnp.sum(out2.count)) == 0  # cleared
    return ref


def _counts_of(keys, H):
    return jax.jit(ev.key_counts, static_argnums=1)(jnp.asarray(keys), H)


@pytest.mark.parametrize("rows", ["uniform", "invalid", "one_row"])
@pytest.mark.parametrize("H", [1, 7, 127, 128, 129, 1_000])
def test_key_counts_match_bincount(H, rows):
    """The MXU histogram equals np.bincount with the dropped key H left
    out, whether or not H is a multiple of the 128-lane split; n = 1,001
    fills no whole block of 128."""
    rng = np.random.default_rng(H)
    n = 1_001
    keys = {"uniform": rng.integers(0, H + 1, n),
            "invalid": np.full(n, H),
            "one_row": np.full(n, H - 1)}[rows].astype(np.int32)
    assert "dot_general" in str(jax.make_jaxpr(
        lambda k: ev.key_counts(k, H))(keys))
    got = np.asarray(_counts_of(keys, H))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, np.bincount(keys, minlength=H + 1)[:H])


def test_key_counts_scatter_add_from_the_limit():
    """At MXU_COUNT_LIMIT entries f32 sums stop being exact, and the
    counts come from the sorted scatter-add instead."""
    H, n = 1_000, ev.MXU_COUNT_LIMIT
    keys = (jnp.arange(n, dtype=jnp.int64) * (H + 1) // n).astype(jnp.int32)
    jaxpr = str(jax.make_jaxpr(lambda k: ev.key_counts(k, H))(keys))
    assert "scatter-add" in jaxpr and "dot_general" not in jaxpr
    np.testing.assert_array_equal(
        np.asarray(_counts_of(keys, H)),
        np.bincount(np.asarray(keys), minlength=H + 1)[:H])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_packed_rows_all_impls_identical(seed):
    rng = np.random.default_rng(seed)
    H, K, M, W = 53, 12, 10, 6
    q = _mkqueue(rng, H, K, W, fill=0.4)
    cnt = rng.integers(0, M + 1, H)
    out = _mkoutbox(rng, H, M, W,
                    cols_of_row=lambda h: range(cnt[h]),
                    dst_of=lambda h, c: int(rng.integers(0, H)))
    _assert_all_equal(q, out, narrows=(0, 4, 8))


@pytest.mark.parametrize("H", [127, 128, 129])
def test_lane_boundary_rows_all_impls_identical(H):
    """Host counts around the MXU count's 128-lane split, with most
    arrivals on the last rows, next to the dropped bin H (few enough
    per row for the select sweep)."""
    rng = np.random.default_rng(H)
    K, M, W = 8, 6, 6
    q = _mkqueue(rng, H, K, W, fill=0.3)
    cnt = rng.integers(0, 3, H)
    out = _mkoutbox(rng, H, M, W,
                    cols_of_row=lambda h: range(cnt[h]),
                    dst_of=lambda h, c: H - 1 - int(rng.integers(0, 32)))
    _assert_all_equal(q, out, narrows=(0, 3))


def test_hot_row_overload_takes_scatter_branch_and_overflows():
    rng = np.random.default_rng(7)
    H, K, M, W = 40, 8, 12, 6
    q = _mkqueue(rng, H, K, W, fill=0.6)
    # every source row fires all M entries at host 3: 480 arrivals at
    # one destination -> far past INSERT_SWEEP and past row capacity
    out = _mkoutbox(rng, H, M, W,
                    cols_of_row=lambda h: range(M),
                    dst_of=lambda h, c: 3)
    ref = _assert_all_equal(q, out, narrows=(0, 6))
    assert ref[5] > 0  # overflow counted, not silent


def test_sparse_rows_narrow_gate_widens():
    """Occupied columns PAST the narrow width with count <= width:
    gating on count would silently drop them (r4 review finding)."""
    rng = np.random.default_rng(11)
    H, K, M, W = 31, 10, 9, 6
    q = _mkqueue(rng, H, K, W, fill=0.2)
    # rows hold 2 entries each, one at column 0 and one at the LAST
    # column — count=2 <= narrow, occupied width = M
    out = _mkoutbox(rng, H, M, W,
                    cols_of_row=lambda h: (0, M - 1),
                    dst_of=lambda h, c: (h * 7 + c) % H)
    ref = _assert_all_equal(q, out, narrows=(0, 4))
    # every staged entry must have landed (no row overloads here):
    # 2 events per source row, all unique (row, slot) targets
    landed = int(np.sum(ref[1] != 0)) - int(np.sum(np.asarray(q.kind) != 0))
    assert landed == 2 * H, landed
    assert ref[5] == 0  # zero overflow


def test_narrow_tier_telemetry():
    """route_outbox records the gate decision and max occupancy
    (VERDICT r4 #10): a fitting window counts narrow_hit, an
    overflowing one counts narrow_miss, and max_occupied tracks the
    true occupied width either way."""
    import shadow_tpu.core.events as ev

    rng = np.random.default_rng(3)
    H, K, M, W = 16, 8, 10, 6
    q = _mkqueue(rng, H, K, W, fill=0.2)
    # 3 occupied columns per row -> fits narrow=4
    out = _mkoutbox(rng, H, M, W,
                    cols_of_row=lambda h: range(3),
                    dst_of=lambda h, c: (h + c) % H)
    q2, out2 = ev.route_outbox(q, out, narrow=4)
    assert int(out2.narrow_hit) == 1 and int(out2.narrow_miss) == 0
    assert int(out2.max_occupied) == 3
    # occupancy past the width -> miss counted, max tracked, totals
    # carried forward on the SAME outbox across windows
    out3 = _mkoutbox(rng, H, M, W,
                     cols_of_row=lambda h: (0, M - 1),
                     dst_of=lambda h, c: (h + c) % H)
    out3 = out3.replace(narrow_hit=out2.narrow_hit,
                        narrow_miss=out2.narrow_miss,
                        max_occupied=out2.max_occupied)
    q3, out4 = ev.route_outbox(q2, out3, narrow=4)
    assert int(out4.narrow_hit) == 1 and int(out4.narrow_miss) == 1
    assert int(out4.max_occupied) == M


def test_sweep_matches_scatter_across_random_shapes():
    rng = np.random.default_rng(23)
    for _ in range(4):
        H = int(rng.integers(8, 70))
        K = int(rng.integers(4, 16))
        M = int(rng.integers(3, 14))
        q = _mkqueue(rng, H, K, 6, fill=float(rng.random()) * 0.8)
        cnt = rng.integers(0, M + 1, H)
        hot = int(rng.integers(0, H))
        out = _mkoutbox(
            rng, H, M, 6,
            cols_of_row=lambda h: sorted(
                rng.choice(M, size=cnt[h], replace=False)),
            dst_of=lambda h, c: hot if rng.random() < 0.5
            else int(rng.integers(0, H)))
        _assert_all_equal(q, out, narrows=(0, max(2, M // 2)))


def test_no_pallas_env_gate_and_gather_fallback_identity(monkeypatch):
    """SHADOW_NO_PALLAS=1 must force mailbox_available False (the
    device-fault-bisection escape hatch) and leave the sort2 insert
    bit-identical: the select sweep then takes the XLA windowed-gather
    fallback, which this CPU suite compares plane-for-plane against
    the sort/count reference impls and the ungated run."""
    from shadow_tpu.core import insert_pallas

    monkeypatch.setenv("SHADOW_NO_PALLAS", "1")
    assert insert_pallas.mailbox_available(8) is False
    assert insert_pallas.mailbox_available(
        insert_pallas._MAX_SMEM_START_ROWS) is False

    rng = np.random.default_rng(7)
    H, K, M, W = 31, 8, 6, 6
    q = _mkqueue(rng, H, K, W, fill=0.3)
    cnt = rng.integers(0, M + 1, H)
    cols = {h: sorted(rng.choice(M, size=cnt[h], replace=False))
            for h in range(H)}
    dsts = {(h, c): int(rng.integers(0, H))
            for h in range(H) for c in cols[h]}
    out = _mkoutbox(rng, H, M, W,
                    cols_of_row=lambda h: cols[h],
                    dst_of=lambda h, c: dsts[(h, c)])
    ref = None
    for env in ("1", None):
        if env is None:
            monkeypatch.delenv("SHADOW_NO_PALLAS", raising=False)
        else:
            monkeypatch.setenv("SHADOW_NO_PALLAS", env)
        for impl in IMPLS:
            q2, _ = ev.route_outbox(q, out, impl=impl, narrow=0)
            s = _snap(q2)
            if ref is None:
                ref = s
            else:
                for i, (a, b) in enumerate(zip(ref, s)):
                    assert np.array_equal(a, b), (env, impl, i)
