"""Time the window-loop components in isolation on the current backend.

Answers "where do the ms/window go" at step_window granularity: each
phase is jitted alone and timed on a representative mid-run PHOLD
snapshot. For op-level attribution use tools/profile_trace.py; for
stage-level bisection inside the bulk pass use tools/profile_bulk2.py.

Usage:  python tools/profile_window.py [--hosts 10240] [--load 8]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from tools.perfutil import build_warm_phold, timeit


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hosts", type=int, default=10240)
    ap.add_argument("--load", type=int, default=8)
    ap.add_argument("--sim-s", type=int, default=5)
    args = ap.parse_args()

    print(f"backend: {jax.default_backend()}  devices: {jax.devices()}")

    from shadow_tpu.core import engine, events

    H = args.hosts
    w = build_warm_phold(H, args.load, args.sim_s)
    b, sim, wstart = w["bundle"], w["sim"], w["wstart"]
    one_window, step, bulk_fn = w["one_window"], w["step"], w["bulk_fn"]
    cfg = b.cfg
    print(f"H={H} K={cfg.event_capacity} min_jump={b.min_jump}")
    nev = int(jnp.sum(sim.events.fill_count()))
    print(f"mid-run state: {nev} queued events "
          f"({nev / H:.1f}/host), wstart={int(wstart)}")

    wend = wstart + b.min_jump

    t_full = timeit(lambda: one_window(sim, wstart), n=20)
    print(f"\nfull step_window:      {t_full * 1e3:8.2f} ms")

    bulk_j = jax.jit(lambda s: bulk_fn(s, wend))
    t_bulk = timeit(lambda: bulk_j(sim), n=20)
    print(f"bulk_fn only:          {t_bulk * 1e3:8.2f} ms")

    sim_b, _ = jax.block_until_ready(bulk_j(sim))

    fix_j = jax.jit(lambda s: engine.window_fixpoint(
        s, engine.EngineStats.create(), step, wend, cfg.emit_capacity,
        s.net.lane_id))
    t_fix = timeit(lambda: fix_j(sim_b), n=20)
    print(f"fixpoint (post-bulk):  {t_fix * 1e3:8.2f} ms")

    route_j = jax.jit(lambda s: engine._default_route(s))
    sim_f, _ = jax.block_until_ready(fix_j(sim_b))
    t_route = timeit(lambda: route_j(sim_f), n=20)
    print(f"route_outbox:          {t_route * 1e3:8.2f} ms")

    min_j = jax.jit(lambda s: jnp.min(s.events.min_time()))
    t_min = timeit(lambda: min_j(sim), n=20)
    print(f"min_time reduce:       {t_min * 1e3:8.2f} ms")

    def micro(s):
        q, popped = events.pop_earliest(s.events, wend)
        s = s.replace(events=q)
        buf = events.EmitBuffer.create(H, cfg.emit_capacity,
                                       nwords=s.events.words.shape[-1])
        s, buf = step(s, popped, buf)
        q, out = events.apply_emissions(s.events, s.outbox, buf,
                                        s.net.lane_id)
        return s.replace(events=q, outbox=out)

    micro_j = jax.jit(micro)
    t_micro = timeit(lambda: micro_j(sim), n=20)
    print(f"one micro-step:        {t_micro * 1e3:8.2f} ms")

    print(f"\naccounting: bulk {t_bulk*1e3:.1f} + fix {t_fix*1e3:.1f} "
          f"+ route {t_route*1e3:.1f} + min {t_min*1e3:.1f} = "
          f"{(t_bulk+t_fix+t_route+t_min)*1e3:.1f} ms "
          f"vs full {t_full*1e3:.1f} ms")


if __name__ == "__main__":
    main()
