"""The check that decides `correct`, driven through the whole harness
on the CPU at a small size (PHOLD: 64 hosts, 2 simulated seconds):
sound runs pass, and the control and each fault a one-chip cell can
have fail. (The exchange between chips left out is
a fault of four-chip cells only.)

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import pathlib
import sys

import jax
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmark import control, run  # noqa: E402

SMALL = {
    cell: dict(require_chip=False, config_override={"hosts": 64},
               traffic_override={"sim_seconds": 2})
    for cell in ("phold-10k.sync50ms", "phold-10k.load1")
}
CELLS = list(SMALL)
SEED = 2**33 + 12345


def _prepare(cell_name):
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = run.find(spec["workloads"], cell_name, "workload")
    return run.load_module(run.BENCH_DIR / "configs"
                           / f"{cell['config']}.py").prepare


def _broken(cell, fault):
    """The cell's deployment with its timed entry broken by `fault`,
    which maps (input, (sim, stats)) to what the entry returns."""
    prepare = _prepare(cell)

    def make(*a):
        dep = prepare(*a)
        good = dep.run
        dep.run = lambda x: fault(x, good(x))
        return dep
    return make


def _unchanged(x, out):
    """A step that returns its state unchanged."""
    from shadow_tpu.core.engine import EngineStats

    return x, EngineStats.create()


def _half(x, out):
    """Half of the hosts left out: their app state stays as it came
    in."""
    sim, stats = out
    h = sim.net.rng_ctr.shape[0] // 2
    app = jax.tree_util.tree_map(lambda o, i: o.at[h:].set(i[h:]),
                                 sim.app, x.app)
    return sim.replace(app=app), stats


def _altered(x, out):
    """One answer altered where it is produced: the last host's count
    of messages received."""
    sim, stats = out
    return sim.replace(app=sim.app.replace(
        rcvd=sim.app.rcvd.at[-1].add(1))), stats


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = run.run_cell(cell, SEED, 1.0, False, **SMALL[cell])
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2
    assert all(c["value"] == 0 for c in r["checks"].values())
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    r = control.run_control(cell, SEED, 0.5, **SMALL[cell])
    assert not r["correct"]
    assert max(c["value"] for c in r["checks"].values()) > 0


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered],
                         ids=["state_unchanged", "half_left_out",
                              "answer_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault):
    r = run.run_cell(cell, SEED + 1, 0.5, False,
                     prepare=_broken(cell, fault), **SMALL[cell])
    assert not r["correct"]


def _overflowed(x, out):
    """The router's ring latched an overflow: the simulation failed."""
    sim, stats = out
    return sim.replace(net=sim.net.replace(
        rq_overflow=sim.net.rq_overflow + 1)), stats


@pytest.mark.parametrize("cell", CELLS)
def test_overflow_counts_as_failed(cell):
    r = run.run_cell(cell, SEED + 2, 0.5, False,
                     prepare=_broken(cell, _overflowed), **SMALL[cell])
    assert r["attempted"] >= 2 and r["failed"] == r["attempted"]


@pytest.mark.parametrize("cell", CELLS)
def test_no_accelerator_exits_nonzero(cell):
    with pytest.raises(SystemExit) as e:
        run.run_cell(cell, SEED, 0.5, False)
    assert e.value.code != 0
