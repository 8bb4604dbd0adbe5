"""A cell's per-seed inputs are what a build for that seed would give:
the harness builds once and derives every simulation's input from it.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import pathlib
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmark import fixture, run  # noqa: E402

SEEDS = [3, 2**35 + 17]


def _deployment(config: str, traffic: str, **override):
    cfg = run.load_json(run.BENCH_DIR / "configs" / f"{config}.json")
    cfg.update(override)
    tr = run.load_json(run.BENCH_DIR / "traffic" / f"{traffic}.json")
    mod = run.load_module(run.BENCH_DIR / "configs" / f"{config}.py")
    return mod, cfg, tr, mod.prepare(cfg, tr, 1)


def _same(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("seed", SEEDS)
def test_phold_input_is_a_build_with_that_seed(seed):
    from shadow_tpu.apps import phold
    from shadow_tpu.compile import specialize
    from shadow_tpu.net.build import HostSpec, build
    from shadow_tpu.net.state import NetConfig

    mod, cfg, tr, dep = _deployment("phold-10k", "sync50ms", hosts=64)
    b = build(NetConfig(num_hosts=64, tcp=False, seed=seed,
                        end_time=tr["sim_seconds"] * 10**9,
                        event_capacity=cfg["event_capacity"],
                        outbox_capacity=cfg["outbox_capacity"],
                        router_ring=cfg["router_ring"],
                        in_ring=cfg["in_ring"]),
              fixture.one_vertex_graphml(cfg["topology"]),
              [HostSpec(name=f"peer{i}", proc_start_time=0)
               for i in range(64)])
    b.sim = phold.setup(b.sim, load=tr["load"])
    b = specialize.apply(b, app_handlers=(phold.handler,),
                         app_bulk=phold.BULK, mode="auto")
    _same(dep.input(seed), b.sim)
