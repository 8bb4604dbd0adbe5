"""The main path's TPU kernels, compiled for a described v5e chip.

Nothing runs: the TPU compiler installed here compiles for a chip that
is described, not attached, and refuses what the chip's compiler would
refuse (unaligned DMA slices, too much fast memory, a program that
does not fit). The code under test picks its TPU branches from
jax.default_backend(), which is the CPU here, so the insert test
steers that call itself.

The topology is described inside a fixture, never while a module is
imported: one process at a time may load the TPU library, and every
test worker imports this file.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from shadow_tpu.core import events, insert_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip; keep these out
    of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("hosts", [10_240, 102_400])
def test_mailbox_gather_compiles_for_v5e(one_chip, hosts):
    """The Pallas mailbox at the PHOLD cell's width (K = 48 outbox
    slots per host) and at the 100k north-star width."""
    Wn = events.INSERT_SWEEP
    n = hosts * 48
    assert insert_pallas.mailbox_available(hosts)
    stream = _spec((n + Wn, 128), jnp.int32, one_chip)
    start = _spec((hosts,), jnp.int32, one_chip)
    compiled = insert_pallas.mailbox_gather.lower(
        stream, start, Wn=Wn).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sort2_insert_takes_the_mailbox_on_tpu(one_chip, monkeypatch):
    """events.insert_flat with no impl, steered onto its TPU branch:
    sort2 with the mailbox kernel, at 10,240 hosts x K = 48."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    H, K, W = 10_240, 48, events.NWORDS
    n = H * K
    assert events._insert_impl(n, H) == "sort2"

    q = jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip),
                     jax.eval_shape(lambda: events.EventQueue.create(H, K)))
    flat = [_spec((n,), dt, one_chip) for dt in
            (jnp.bool_, jnp.int32, jnp.int64, jnp.int32, jnp.int32,
             jnp.int32)]
    words = _spec((n, W), jnp.int32, one_chip)
    compiled = jax.jit(events.insert_flat).lower(q, *flat, words).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sort2_count_step_is_a_dot_at_the_narrow_shape(one_chip,
                                                       monkeypatch):
    """The insert of the PHOLD cells' narrow route tier (10,240 hosts x
    ROUTE_NARROW = 24 outbox columns): its per-row arrival counts are
    an MXU product, with no scatter left in the count step."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    H, K, W = 10_240, 48, events.NWORDS
    n = H * events.ROUTE_NARROW
    assert n < events.MXU_COUNT_LIMIT
    q = jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip),
                     jax.eval_shape(lambda: events.EventQueue.create(H, K)))
    flat = [_spec((n,), dt, one_chip) for dt in
            (jnp.bool_, jnp.int32, jnp.int64, jnp.int32, jnp.int32,
             jnp.int32)]
    words = _spec((n, W), jnp.int32, one_chip)
    text = jax.jit(events.insert_flat, static_argnames="impl").lower(
        q, *flat, words, impl="sort2").compile().as_text()
    count_ops = [ln for ln in text.splitlines()
                 if re.search(r'op_name="[^"]*/count/', ln)]
    assert not any(re.search(r"\bscatter\(", ln) for ln in count_ops)
    assert any(re.search(r"\b(dot|convolution)\(", ln) for ln in count_ops)


def test_key_counts_materialises_no_one_hot(one_chip):
    """The one-hot operands stay inside the dot's fusion: the compiled
    histogram at the narrow shape needs far less scratch than one
    [n, 128] bf16 one-hot."""
    H, n = 10_240, 10_240 * events.ROUTE_NARROW
    keys = _spec((n,), jnp.int32, one_chip)
    compiled = jax.jit(events.key_counts, static_argnums=1).lower(
        keys, H).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < n * 128 * 2 // 8


def test_route_scopes_keep_the_mailbox_kernel_name(one_chip, monkeypatch):
    """The route's step scopes (core/events.py) reach the compiled TPU
    program's op names, and the Pallas kernel keeps its instruction
    name, which the benchmark's mailbox_roofline finds it by."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    H, K = 1_024, 48
    q, out = (jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip),
                           jax.eval_shape(lambda: make(H, K)))
              for make in (events.EventQueue.create, events.Outbox.create))

    def route(q, out):
        with jax.named_scope("shadow_route"):
            return events.route_outbox(q, out)

    text = jax.jit(route).lower(q, out).compile().as_text()
    kernel = [ln for ln in text.splitlines()
              if re.match(r"\s*%?mailbox_gather[.\d]* = ", ln)]
    assert kernel and all("/mailbox/" in ln for ln in kernel)
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for step in ("sort", "permute", "count", "sweep", "mailbox"):
        assert any("shadow_route/" in n and f"/{step}/" in n
                   for n in names), step


@pytest.mark.parametrize("op", ["pmin", "pmax"])
def test_64_bit_min_max_collectives_compile_for_v5e(topo, op):
    """The window barrier's int64 pmin over a 4-chip mesh: the TPU
    compiler refuses a plain lax.pmin on s64 ("Supported lowering only
    of Sum all reduce"); core.collectives splits it into 32-bit
    words."""
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from shadow_tpu.core import collectives

    mesh = Mesh(np.array(topo.devices), ("s",))
    fn = shard_map(lambda v: getattr(collectives, op)(v, "s"),
                   mesh=mesh, in_specs=P("s"), out_specs=P("s"))
    x = _spec((len(topo.devices),), jnp.int64, NamedSharding(mesh, P("s")))
    compiled = jax.jit(fn).lower(x).compile()
    assert "all-reduce" in compiled.as_text()
