"""Multi-chip sharding: results must be bit-identical to the
single-shard run for any shard count (the reference's thread-count
independence, ref: event.c:110-153 + determinism tests, here across
the virtual 8-device CPU mesh from conftest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from shadow_tpu.apps import pingpong
from shadow_tpu.core import simtime
from shadow_tpu.net.build import HostSpec, build, run
from shadow_tpu.net.state import NetConfig
from shadow_tpu.parallel import run_sharded

# the reference's standard single-vertex fixture: one self-looped
# vertex, latency 50 ms (SURVEY.md §4)
ONE_VERTEX = """<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
  <key attr.name="latency" attr.type="double" for="edge" id="lat" />
  <key attr.name="bandwidthup" attr.type="int" for="node" id="up" />
  <key attr.name="bandwidthdown" attr.type="int" for="node" id="dn" />
  <graph edgedefault="undirected">
    <node id="v0"><data key="up">10240</data><data key="dn">10240</data></node>
    <edge source="v0" target="v0"><data key="lat">50.0</data></edge>
  </graph>
</graphml>"""

H = 8
PORT = 7000


def _build(seed=1):
    cfg = NetConfig(num_hosts=H, end_time=5 * simtime.ONE_SECOND, seed=seed)
    hosts = []
    for i in range(H // 2):
        hosts.append(HostSpec(name=f"client{i}",
                              proc_start_time=simtime.ONE_SECOND))
    for i in range(H // 2):
        hosts.append(HostSpec(name=f"server{i}"))
    b = build(cfg, ONE_VERTEX, hosts)
    client = jnp.asarray(np.arange(H) < H // 2)
    server = jnp.asarray(np.arange(H) >= H // 2)
    # client i pings server i
    server_ip = np.zeros(H, np.int64)
    for i in range(H // 2):
        server_ip[i] = b.ip_of(f"server{i}")
    sim = pingpong.setup(
        b.sim, client_mask=client, server_mask=server,
        server_ip=jnp.asarray(server_ip), server_port=PORT,
        count=5, size=128,
    )
    b.sim = sim
    return b


@pytest.fixture(scope="module")
def single():
    sim, stats = run(_build(), app_handlers=(pingpong.handler,))
    return jax.device_get((sim, stats))


@pytest.mark.parametrize("nshards", [2, 8])
def test_sharded_matches_single(single, nshards):
    sim1, stats1 = single
    devices = np.array(jax.devices()[:nshards])
    mesh = Mesh(devices, ("hosts",))
    b = _build()
    sim2, stats2 = run_sharded(b, mesh, "hosts",
                               app_handlers=(pingpong.handler,))
    sim2, stats2 = jax.device_get((sim2, stats2))

    assert int(stats1.events_processed) == int(stats2.events_processed)
    assert int(stats1.windows) == int(stats2.windows)
    assert int(sim2.events.overflow) == 0
    assert int(sim2.outbox.overflow) == 0

    # every ping completed
    assert np.asarray(sim2.app.rcvd[: H // 2]).tolist() == [5] * (H // 2)
    # full app + netstack state is bit-identical across shard counts
    np.testing.assert_array_equal(np.asarray(sim1.app.rtt_sum),
                                  np.asarray(sim2.app.rtt_sum))
    np.testing.assert_array_equal(np.asarray(sim1.net.ctr_rx_bytes),
                                  np.asarray(sim2.net.ctr_rx_bytes))
    np.testing.assert_array_equal(np.asarray(sim1.net.ctr_tx_packets),
                                  np.asarray(sim2.net.ctr_tx_packets))
    np.testing.assert_array_equal(np.asarray(sim1.net.rng_ctr),
                                  np.asarray(sim2.net.rng_ctr))
    # event queue contents identical (same times in each row set)
    np.testing.assert_array_equal(np.sort(np.asarray(sim1.events.time)),
                                  np.sort(np.asarray(sim2.events.time)))
    # narrow-exchange telemetry (VERDICT r4 #10): every window's gate
    # decision is recorded, traffic was measured, and this workload's
    # bursts fit the narrow tier (a regression that overflows the tier
    # flips hit -> miss loudly instead of taking a silent slow branch).
    # At Hl == 1 host/shard the tier is structurally inactive
    # (C_n == C_full), so no decisions exist to record.
    hit = int(sim2.outbox.narrow_hit)
    miss = int(sim2.outbox.narrow_miss)
    if H // nshards > 1:
        assert hit + miss == int(stats2.windows), (hit, miss)
        assert miss == 0, f"narrow tier overflowed {miss} windows"
        assert int(sim2.outbox.max_occupied) > 0
    else:
        assert hit == 0 and miss == 0


def test_exchange_capacity_counts_overflow(single):
    """A too-small per-peer exchange buffer must count dropped entries
    in events.overflow, never lose them silently."""
    devices = np.array(jax.devices()[:2])
    mesh = Mesh(devices, ("hosts",))
    b = _build()
    sim, stats = run_sharded(b, mesh, "hosts",
                             app_handlers=(pingpong.handler,),
                             exchange_capacity=1)
    sim = jax.device_get(sim)
    # 4 clients per shard ping 4 servers on the other shard in the same
    # window; cap 1 forces drops, which must show up in overflow.
    assert int(sim.events.overflow) > 0


def test_sharded_preserves_initial_scalar_counters():
    """Scalar counters entering the sharded run nonzero must come back
    as initial + delta, not initial * num_shards (replicated input)."""
    devices = np.array(jax.devices()[:4])
    mesh = Mesh(devices, ("hosts",))
    b = _build()
    b.sim = b.sim.replace(
        events=b.sim.events.replace(
            overflow=jnp.asarray(3, jnp.int32)))
    sim, stats = run_sharded(b, mesh, "hosts",
                             app_handlers=(pingpong.handler,))
    assert int(jax.device_get(sim.events.overflow)) == 3


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("dtype", [jnp.int64, jnp.uint64, jnp.int32])
def test_collectives_min_max_exact_for_64_bit(dtype, ties):
    """core.collectives.pmin/pmax: 64-bit operands reduce as two
    32-bit words (the TPU compiler lowers only Sum for 64-bit
    all-reduce); values that tie on the high word, straddle zero or
    sit at the type's ends must come out exact."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from shadow_tpu.core import collectives

    info = np.iinfo(dtype)
    vals = [info.min, info.max, 0, 1, (1 << 31) + 5, (1 << 31) + 3,
            (7 << 32) + 9, (7 << 32) + 2] if info.bits == 64 else [
        info.min, info.max, 0, 1, -5, 17, 3, 3]
    if ties:    # min and max each decided by the low word alone
        vals = [((7 << 32) if info.bits == 64 else (7 << 24)) + k
                for k in (9, 2, 5, 2, 9, 4)]
    vals = [v for v in vals if info.min <= v <= info.max]
    x = np.array((vals * 8)[:8], dtype=dtype)
    mesh = Mesh(np.array(jax.devices()[:8]), ("s",))

    def f(v):
        return (collectives.pmin(v[0], "s")[None],
                collectives.pmax(v[0], "s")[None])

    lo, hi = jax.jit(shard_map(f, mesh=mesh, in_specs=P("s"),
                               out_specs=P("s")))(jnp.asarray(x))
    assert (np.asarray(lo) == x.min()).all() and lo.dtype == x.dtype
    assert (np.asarray(hi) == x.max()).all() and hi.dtype == x.dtype
