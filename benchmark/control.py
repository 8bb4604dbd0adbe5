#!/usr/bin/env python3
"""The control of the check that decides `correct`: the plain
reference, with one guarantee of the configuration broken, put in the
program's place. A run with it must come out not correct; its
readings are the upper ends from which the limits were set.

    python3 benchmark/control.py --workload <cell> --seed <n> [--seed <m> ...] --seconds <s>

runs the cell's whole harness once per seed with the control in the
program's place, at the cell's own size and under the window's own
rule (a short --seconds suffices), and prints one JSON line per seed
with the numbers compared. The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from benchmark import run  # noqa: E402


class Control:
    """A deployment whose every simulation is the reference's, run
    with `expected(seed, control=True)`: for phold-10k one message is
    lost halfway through, breaking lossless delivery. Everything else
    is the cell's own deployment."""

    def __init__(self, dep):
        self.dep = dep
        self.bundle = None

    def __getattr__(self, name):
        return getattr(self.dep, name)

    def input(self, seed):
        return int(seed)

    def run(self, seed):
        return self.dep.expected(seed, control=True)

    @staticmethod
    def scalars(out):
        return {"events": out.get("events", 0), "windows": 1,
                "micro_steps": 0, "overflow": 0}

    @staticmethod
    def kept(out):
        return out

    @staticmethod
    def observe(kept, scalars):
        return kept


def control_prepare(prepare):
    return lambda *a: Control(prepare(*a))


def run_control(workload: str, seed: int, seconds: float, **kw) -> dict:
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = run.find(spec["workloads"], workload, "workload")
    mod = run.load_module(run.BENCH_DIR / "configs" / f"{cell['config']}.py")
    return run.run_cell(workload, seed, seconds, False,
                        prepare=control_prepare(kw.pop("prepare",
                                                       mod.prepare)), **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    for s in args.seed:
        r = run_control(args.workload, s, args.seconds)
        print(json.dumps({"seed": s, "correct": r["correct"],
                          "attempted": r["attempted"],
                          "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
