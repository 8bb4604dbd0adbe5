"""Prefix-bisect net/bulk.py's bulk_fn: re-create its body with a
cut-point argument; time each prefix. The returned value folds every
live intermediate into a scalar so XLA cannot dead-code-eliminate the
prefix under test."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from shadow_tpu.core import rng, simtime
from shadow_tpu.core.events import EventKind, _tie_key
from shadow_tpu.net import bulk as bulkmod
from shadow_tpu.net import packetfmt as pf
from shadow_tpu.net.state import TB_REFILL_INTERVAL, host_of_ip

I32 = jnp.int32
I64 = jnp.int64


from tools.perfutil import timeit  # noqa: E402


def make_prefix(cfg, app_bulk, wend, stop):
    """bulk_fn body up to stage `stop`; returns a scalar folding all
    live values."""

    def fn(sim):
        acc = jnp.zeros((), I64)

        def fold(*xs):
            nonlocal acc
            for x in xs:
                acc = acc + jnp.sum(x).astype(I64)

        net = sim.net
        q = sim.events
        H, K = q.time.shape
        GH = net.host_ip.shape[0]
        lane = net.lane_id

        t = q.time
        inwin = t < jnp.asarray(wend, simtime.DTYPE)
        tie = _tie_key(q.src, q.seq)
        length = q.words[:, :, pf.W_LEN]
        wl_all = pf.wire_length(
            jnp.full((H, K), pf.PROTO_UDP, I32), length).astype(I64)
        wl = jnp.where(inwin, wl_all, 0)
        nonboot = t >= cfg.bootstrap_end
        app_ok = app_bulk.precheck(cfg, sim)
        sndbuf_ok = jnp.min(net.sk_sndbuf, axis=1) > app_bulk.max_send_len
        if stop == "head":
            fold(wl, nonboot, app_ok, sndbuf_ok)
            return acc

        src = q.src
        pw = q.words[:, :, pf.W_PORTS]
        src_port = pw & 0xFFFF
        dst_port = (pw >> 16) & 0xFFFF
        dst_ip = q.words[:, :, pf.W_DSTIP].astype(jnp.uint32).astype(I64)
        src_ip = net.host_ip[jnp.clip(src, 0, GH - 1)]
        payref = q.words[:, :, pf.W_PAYREF]
        slot = bulkmod._lookup_bulk(net, inwin, dst_ip, dst_port, src_ip,
                                    src_port)
        rcvbuf_at = bulkmod._gather_hs_bulk(net.sk_rcvbuf, slot)
        rcv_fit = jnp.all(~inwin | (slot < 0) | (length <= rcvbuf_at), axis=1)
        if stop == "lookup":
            fold(slot, rcv_fit)
            return acc

        elig = bulkmod._eligibility(cfg, sim, inwin, t, wl, nonboot,
                                    app_ok & sndbuf_ok & rcv_fit)
        ev = inwin & elig[:, None]
        n_ev = jnp.sum(ev, axis=1, dtype=I32)
        order = bulkmod.make_order(t, tie)
        matched = ev & (slot >= 0)
        nosock = ev & (slot < 0)
        S = net.sk_type.shape[1]
        arr_per_sock = jnp.sum(
            matched[:, :, None]
            & (slot[:, :, None] == jnp.arange(S)[None, None, :]),
            axis=1, dtype=I32)
        if stop == "elig":
            fold(elig, n_ev, arr_per_sock,
                 order.perm if order.perm is not None else order.prec)
            return acc

        d = bulkmod.BulkDeliveries(
            mask=matched, time=t, tie=tie, order=order, slot=slot,
            src_ip=src_ip, src_port=src_port, length=length, payref=payref)
        sim2, sends = app_bulk.run(cfg, sim, d)
        net = sim2.net
        smask = sends.mask & elig[:, None]
        sport = bulkmod._gather_hs_bulk(net.sk_bound_port, sends.slot)
        send_per_sock = jnp.sum(
            smask[:, :, None]
            & (sends.slot[:, :, None] == jnp.arange(S)[None, None, :]),
            axis=1, dtype=I32)
        n_send = jnp.sum(smask, axis=1, dtype=I32)
        if stop == "app":
            fold(smask, sport, send_per_sock, n_send)
            return acc

        dsth = jnp.where(sends.dst_host >= 0, sends.dst_host,
                         host_of_ip(net, sends.dst_ip))
        known = smask & (dsth >= 0)
        u2 = rng.uniform_at(net.rng_keys, sends.nic_draw_ctr)
        V = net.latency_ns.shape[0]
        if V == 1:
            rel = net.reliability[0, 0]
            lat = net.latency_ns[0, 0]
        else:
            vsrc = net.vertex_of_host[lane][:, None]
            vdst = net.vertex_of_host[jnp.clip(dsth, 0, GH - 1)]
            rel = net.reliability[vsrc, vdst]
            lat = net.latency_ns[vsrc, vdst]
        drop = known & nonboot & (sends.length > 0) & (u2 > rel)
        emit_ok = known & ~drop
        if stop == "nic":
            fold(emit_ok, drop)
            return acc

        nosock_status = (
            q.words[:, :, pf.W_STATUS]
            | pf.PDS_ROUTER_ENQUEUED | pf.PDS_ROUTER_DEQUEUED
            | pf.PDS_RCV_INTERFACE_RECEIVED | pf.PDS_RCV_SOCKET_DROPPED)
        reply_drop_status = jnp.full(
            (H, K), pf.PDS_SND_CREATED | pf.PDS_SND_SOCKET_BUFFERED
            | pf.PDS_SND_INTERFACE_SENT | pf.PDS_INET_DROPPED, I32)
        drop_any = nosock | drop
        drop_status = jnp.where(nosock, nosock_status, reply_drop_status)
        n_drop = jnp.sum(drop_any, axis=1, dtype=I32)
        drop_rank = bulkmod.rank_in_order(order, drop_any)
        last_col = drop_any & (drop_rank == (n_drop[:, None] - 1))
        picked_drop = jnp.sum(jnp.where(last_col, drop_status, 0), axis=1,
                              dtype=I32)
        new_last_drop = jnp.where(elig & (n_drop > 0), picked_drop,
                                  net.last_drop_status)
        swl = jnp.where(smask, pf.wire_length(
            jnp.full((H, K), pf.PROTO_UDP, I32), sends.length), 0).astype(I64)
        if stop == "audit":
            fold(new_last_drop, swl)
            return acc

        qq = jnp.where(ev, t // TB_REFILL_INTERVAL, 0)
        q_last = jnp.maximum(jnp.max(qq, axis=1), net.tb_quantum)
        q_last = jnp.where(n_ev > 0, q_last, net.tb_quantum)
        qv = jnp.where(ev, qq, q_last[:, None])
        w_recv = jnp.where(nonboot, wl, 0)
        w_send = jnp.where(nonboot & smask, swl, 0)
        suff_recv = bulkmod.suffix_sum(order, w_recv)
        suff_send = bulkmod.suffix_sum(order, w_send)
        cap_r = net.tb_recv_refill + pf.MTU
        cap_s = net.tb_send_refill + pf.MTU
        big = jnp.iinfo(jnp.int64).max // 2
        dq_total = (q_last - net.tb_quantum)

        def bucket_final(s0, cap, refill, w, suffw):
            straight = s0 + dq_total * refill - jnp.sum(w, axis=1)
            clamp = jnp.where(
                ev,
                cap[:, None] - w + (q_last[:, None] - qv) * refill[:, None]
                - suffw, big)
            return jnp.minimum(straight, jnp.min(clamp, axis=1))

        new_recv_tok = bucket_final(net.tb_recv_tokens, cap_r,
                                    net.tb_recv_refill, w_recv, suff_recv)
        new_send_tok = bucket_final(net.tb_send_tokens, cap_s,
                                    net.tb_send_refill, w_send, suff_send)
        if stop == "bucket":
            fold(new_recv_tok, new_send_tok)
            return acc

        ord_col = bulkmod.rank_in_order(order, ev)
        send_rank = bulkmod.rank_in_order(order, emit_ok)
        seq = q.next_seq[:, None] + send_rank
        M = sim.outbox.capacity
        lane_h = jnp.arange(H)[:, None]
        col = jnp.where(emit_ok, ord_col, M)

        def place(val, fill, dtype):
            base = jnp.full((H, M), fill, dtype)
            return base.at[lane_h, col].set(jnp.asarray(val, dtype),
                                            mode="drop")

        got_col = jnp.zeros((H, M), bool).at[lane_h, col].set(
            True, mode="drop")
        o_dst = place(dsth, -1, I32)
        o_time = place(t + lat, simtime.INVALID, I64)
        o_src = place(jnp.broadcast_to(lane[:, None], (H, K)), 0, I32)
        o_seq = place(seq, 0, I32)
        o_kind = jnp.where(got_col, EventKind.PACKET, 0).astype(I32)
        if stop == "place":
            fold(got_col, o_dst, o_time, o_src, o_seq, o_kind)
            return acc

        wds = jnp.zeros((H, K, q.words.shape[2]), I32)
        wds = wds.at[:, :, pf.W_PROTO].set(pf.PROTO_UDP)
        wds = wds.at[:, :, pf.W_LEN].set(sends.length)
        wds = wds.at[:, :, pf.W_PORTS].set(pf.pack_ports(sport, sends.dst_port))
        wds = wds.at[:, :, pf.W_PAYREF].set(sends.payref)
        wds = wds.at[:, :, pf.W_DSTIP].set(
            sends.dst_ip.astype(jnp.uint32).astype(I32))
        wds = wds.at[:, :, pf.W_STATUS].set(
            pf.PDS_SND_CREATED | pf.PDS_SND_SOCKET_BUFFERED
            | pf.PDS_SND_INTERFACE_SENT | pf.PDS_INET_SENT)
        o_words = jnp.zeros((H, M, q.words.shape[2]), I32).at[
            lane_h, col].set(wds, mode="drop")
        if stop == "words":
            fold(got_col, o_dst, o_time, o_src, o_seq, o_kind, o_words)
            return acc
        raise ValueError(stop)

    return fn


def main():
    H = int(os.environ.get("PB_HOSTS", "10240"))
    load = int(os.environ.get("PB_LOAD", "8"))
    print(f"backend: {jax.default_backend()}  H={H}")

    from shadow_tpu.apps import phold
    from tools.perfutil import build_warm_phold

    w = build_warm_phold(H, load)
    b, sim, wstart = w["bundle"], w["sim"], w["wstart"]
    cfg, bulk_fn = b.cfg, w["bulk_fn"]
    wend = int(wstart) + b.min_jump

    prev = 0.0
    for stage in ["head", "lookup", "elig", "app", "nic", "audit",
                  "bucket", "place", "words"]:
        fn = jax.jit(make_prefix(cfg, phold.BULK, wend, stage))
        t = timeit(fn, sim)
        print(f"prefix {stage:8s}: {t*1e3:8.2f} ms  (+{(t-prev)*1e3:7.2f})")
        prev = t

    bj = jax.jit(lambda s: bulk_fn(s, wend))
    print(f"full bulk_fn   : {timeit(bj, sim)*1e3:8.2f} ms")


if __name__ == "__main__":
    main()
