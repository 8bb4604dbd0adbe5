"""Plain reference for PHOLD on a lossless network whose every hop
takes the same latency. It imports nothing of the simulator.

Semantics (Fujimoto 1990; Shadow's src/test/phold): at time 0 every
host sends `load` messages; every message received sends one message
to a peer drawn uniformly from the other hosts. Each send uses two
draws of the sending host's counter stream: draw c of host h is
uniform(fold_in(fold_in(key(seed), h), c)) in float32, the even draw
picks the peer and the odd one is the link's loss draw (a lossless
link never drops). With one latency for every hop, messages move in
rounds: all messages sent at t arrive at t + latency, and every
message of a round is processed at time r * latency <= end.

Since a host's draws are consumed in order, a host that receives c
messages in a round sends them to the peers of its next c even
draws, whatever the order it handles them in. So the reference
keeps no queue: one round is a sort of the senders, each host's
count and each message's rank from the edges of its host's run, and
one draw per message.

`run(...)` returns, per host: messages received and sent, the
stream's counter, and the messages still in flight at the end (all
due at (R + 1) * latency); and the number of events the simulation
commits (every message received, plus the `load` injections at
time 0). `lose_one_in_round` is the control: one message sent in
that round never arrives, which breaks the lossless guarantee.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _host_keys(seed: int, hosts: int):
    base = jax.random.key(seed)
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        base, jnp.arange(hosts, dtype=jnp.uint32))


def _peer(u, sender, hosts: int):
    """Uniform peer other than `sender`, from a float32 draw."""
    p = jnp.minimum((u * jnp.int32(hosts - 1)).astype(jnp.int32),
                    hosts - 2)
    return jnp.where(p >= sender, p + 1, p)


@functools.partial(jax.jit, static_argnames=("hosts", "load", "rounds",
                                             "lose_round"))
def _rounds(keys, *, hosts: int, load: int, rounds: int, lose_round: int):
    H, n = hosts, hosts * load
    lost = jnp.int32(H)          # a sender index that is no host

    def body(r, carry):
        senders, ctr, sent, rcvd, events = carry
        # messages are alike but for their sender: sort the senders,
        # count each host's by the edges of its run, rank within it
        s = jnp.sort(senders)
        edges = jnp.searchsorted(s, jnp.arange(H + 1, dtype=jnp.int32),
                                 side="left").astype(jnp.int32)
        cnt = edges[1:] - edges[:-1]                       # [H]
        sc = jnp.clip(s, 0, H - 1)
        rank = jnp.arange(n, dtype=jnp.int32) - edges[sc]
        draw = ctr[sc] + 2 * rank.astype(jnp.uint32)
        k = jax.vmap(jax.random.fold_in)(keys[sc], draw)
        u = jax.vmap(lambda kk: jax.random.uniform(kk, dtype=jnp.float32))(k)
        dest = jnp.where(s < H, _peer(u, sc, H), lost)
        dest = jnp.where((r == lose_round) & (jnp.arange(n) == 0), lost,
                         dest)
        rcvd = rcvd + jnp.where(r > 0, cnt, 0).astype(jnp.int64)
        ctr = ctr + 2 * cnt.astype(jnp.uint32)
        sent = sent + cnt.astype(jnp.int64)
        events = events + jnp.sum(cnt, dtype=jnp.int64)
        return dest, ctr, sent, rcvd, events

    senders = jnp.repeat(jnp.arange(H, dtype=jnp.int32), load)
    carry = (senders, jnp.zeros((H,), jnp.uint32),
             jnp.zeros((H,), jnp.int64), jnp.zeros((H,), jnp.int64),
             jnp.zeros((), jnp.int64))
    dest, ctr, sent, rcvd, events = jax.lax.fori_loop(
        0, rounds + 1, body, carry)
    d = jnp.sort(dest)
    pending = jnp.diff(jnp.searchsorted(
        d, jnp.arange(H + 1, dtype=jnp.int32), side="left"))
    return rcvd, sent, ctr, pending, events


def run(seed: int, *, hosts: int, load: int, end_ns: int,
        latency_ns: int, lose_one_in_round: int = -1) -> dict:
    """Final per-host state of one PHOLD simulation (numpy)."""
    rounds = end_ns // latency_ns
    rcvd, sent, ctr, pending, events = jax.device_get(_rounds(
        _host_keys(seed, hosts), hosts=hosts, load=load, rounds=rounds,
        lose_round=lose_one_in_round))
    return {"rcvd": np.asarray(rcvd), "sent": np.asarray(sent),
            "rng_ctr": np.asarray(ctr), "pending": np.asarray(pending),
            "events": int(events)}
