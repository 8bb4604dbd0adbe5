"""Shared timing helper for the tools/profile_* scripts.

CAVEAT (observed on the chip used before PR 1): re-executing a
jitted program on bit-identical inputs can be served from a device
runtime execution-result cache, measuring nothing (observed: 0.02 ms
for programs whose real device time is >100 ms). timeit() is only
trustworthy when either the inputs change per call, the outputs are
large (cache declines), or the number is cross-checked against a
whole-run measurement. Prefer varying an input scalar per iteration
(see bench.py's distinct-seed pattern) when in doubt.
"""

from __future__ import annotations

import time

import jax


def build_warm_phold(H: int, load: int, sim_s: int = 5, windows: int = 3):
    """Build a PHOLD bundle at bench.py's capacity sizing and advance
    it `windows` windows to a representative mid-run state. Returns
    (bundle, sim, wstart, one_window) where one_window(sim, wstart) ->
    (sim, next_min) is the jitted full window round."""
    import jax.numpy as jnp

    from bench import _build_phold
    from shadow_tpu.apps import phold
    from shadow_tpu.core import engine
    from shadow_tpu.net import bulk as bulkmod
    from shadow_tpu.net.step import make_step_fn

    b = _build_phold(H, load, sim_s)   # includes phold.setup
    step = make_step_fn(b.cfg, (phold.handler,))
    bulk_fn = bulkmod.make_bulk_fn(b.cfg, phold.BULK)

    @jax.jit
    def one_window(sim, wstart):
        wend = wstart + b.min_jump
        sim, stats, next_min = engine.step_window(
            sim, engine.EngineStats.create(), step, wend,
            b.cfg.emit_capacity, sim.net.lane_id, bulk_fn=bulk_fn)
        return sim, next_min

    sim = b.sim
    wstart = jax.block_until_ready(jnp.min(sim.events.min_time()))
    for _ in range(windows):
        sim, wstart = one_window(sim, wstart)
    sim = jax.block_until_ready(sim)
    return {"bundle": b, "sim": sim, "wstart": wstart,
            "one_window": one_window, "step": step, "bulk_fn": bulk_fn}


def timeit(fn, *args, n=10, warm=2):
    """Average wall seconds per call of fn(*args) over n calls after
    warm warmup calls. All n calls dispatch asynchronously and are
    blocked on once, so this measures device throughput, not per-call
    dispatch latency. See module docstring for the result-cache trap."""
    for _ in range(warm):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n
