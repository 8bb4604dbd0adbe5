#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to a cell is found by the names in
BENCHMARK.json: the configuration's sizes in
benchmark/configs/<config>.json and its deployment (build, entry,
what the check reads, the plain reference) in
benchmark/configs/<config>.py, the traffic in
benchmark/traffic/<traffic>.json, and one reader per per-layer metric
in benchmark/metrics/<metric>.py.

A run: set-up (import, build, the entry's compile or cache load, one
whole warm-up simulation, every simulation's input, a full garbage
collection), then the window, with the collector off: whole
simulations back to back, each on its own seed drawn from --seed, the
next always queued on the device behind the one running, and queued
while less than --seconds have passed; every one counts, and the
window ends when the last of them ends. Then, with the device's
peak memory read and the program's state dropped, every simulation's
final state is compared with the plain reference. With --trace 1 the window runs
under the profiler and the per-layer metrics are printed instead of
the end-to-end ones.

Exits non-zero and prints no result when JAX finds no accelerator or
fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# Fixed paths inside the checkout: the compile cache's path is part of
# its key, and the trace is overwritten by the next traced run.
CACHE_DIR = BENCH_DIR / ".jax_cache"
TRACE_DIR = BENCH_DIR / ".trace"
GIB = float(1 << 30)

# Trace and lowering events nest, so only the backend's own compile
# and its persistent-cache loads are summed.
_COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)

# The benchmark's own host spans, by which idle gaps are labelled.
SPAN_WINDOW = "bench.window"
SPAN_DISPATCH = "bench.dispatch"
SPAN_FETCH = "bench.fetch"
SPAN_CHECK = "bench.outcome_check"
HOST_SPANS = (SPAN_DISPATCH, SPAN_FETCH, SPAN_CHECK)


class CompileClock:
    """Seconds the XLA backend has spent compiling, or loading from
    the persistent cache, in this process (jax.monitoring)."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event, duration, **kwargs):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.count += 1


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in "
                     "BENCHMARK.json")


def sim_seeds(seed: int, count: int, start: int = 0) -> list[int]:
    """One 32-bit seed per simulation, drawn from the run's --seed
    (any whole number)."""
    import numpy as np

    if seed < 0:
        raise SystemExit(f"benchmark: --seed {seed} is negative")
    states = np.random.SeedSequence(seed).generate_state(start + count,
                                                         np.uint32)
    return [int(s) for s in states[start:]]


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peak_bytes(devices, required: bool) -> int:
    peaks = [0] if not required else []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    if not peaks:
        raise RuntimeError("the backend reports no peak_bytes_in_use")
    return max(peaks)


def window(dep, inputs: list, seconds: float, span):
    """Whole simulations back to back, the next always queued on the
    device behind the one running: simulation i + 1 is dispatched
    before the host fetches simulation i's counters and checked state.
    One more is queued while less than `seconds` have passed since the
    window opened; every one queued counts, and the window ends when
    the last has ended and been fetched. Returns (checked state,
    counters, wall seconds, seconds between successive fetches)."""
    import jax

    kept, scalars, each = [], [], []
    t0 = last = time.perf_counter()
    with span(SPAN_DISPATCH):
        running = dep.run(inputs[0])
    for i in range(1, len(inputs) + 1):
        queued = None
        if time.perf_counter() - t0 < seconds:
            if i == len(inputs):
                raise RuntimeError(
                    f"benchmark: {len(inputs)} prepared inputs ran out "
                    f"after {time.perf_counter() - t0:.3f} s of a "
                    f"{seconds} s window")
            with span(SPAN_DISPATCH):
                queued = dep.run(inputs[i])
        with span(SPAN_FETCH):
            scalars.append(jax.device_get(dep.scalars(running)))
        with span(SPAN_CHECK):   # to the host, so memory stays flat
            kept.append(jax.device_get(dep.kept(running)))
        del running
        t = time.perf_counter()
        each.append(t - last)
        last = t
        if queued is None:
            break
        running = queued
    return kept, scalars, last - t0, each


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, config_override: dict | None = None,
             traffic_override: dict | None = None, prepare=None) -> dict:
    """One run of one cell; returns the result line's object. The
    keyword arguments are for the benchmark's tests: a run on the CPU
    at a small size, and a deployment swapped for a broken one."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cell = find(spec["workloads"], workload, "workload")
    config = load_json(BENCH_DIR / "configs" / f"{cell['config']}.json")
    config.update(config_override or {})
    traffic = load_json(BENCH_DIR / "traffic" / f"{cell['traffic']}.json")
    traffic.update(traffic_override or {})
    chips = int(cell["chips"])

    import jax

    devices = jax.devices()
    if require_chip:
        if devices[0].platform == "cpu":
            raise SystemExit("benchmark: JAX found no accelerator")
        if len(devices) < chips:
            raise SystemExit(f"benchmark: {workload} needs {chips} chips, "
                             f"JAX found {len(devices)}")
        # the chip's programs only: a CPU run keeps no cache here
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = devices[:chips]
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    span = (jax.profiler.TraceAnnotation if trace
            else (lambda name: contextlib.nullcontext()))

    # ---- set-up ------------------------------------------------------
    t = time.perf_counter()
    mod = load_module(BENCH_DIR / "configs" / f"{cell['config']}.py")
    dep = (prepare or mod.prepare)(config, traffic, chips)
    build_s = time.perf_counter() - t
    # The entry's compile (or cache load) in one warm-up simulation
    # through every program the window calls, then one more that
    # compiles nothing and times a simulation.
    warm = dep.input(sim_seeds(seed, 1, start=0)[0])
    jax.block_until_ready(warm)
    for _ in range(3):   # again while the warm-up still compiled
        n_compiles = clock.count
        t = time.perf_counter()
        out = dep.run(warm)
        jax.device_get(dep.scalars(out))
        jax.block_until_ready(dep.kept(out))
        warm_s = time.perf_counter() - t
        if clock.count == n_compiles:
            break
    del warm, out
    # enough inputs for the window at the warm-up's pace, twice over
    count = max(4, 2 * math.ceil(seconds / max(warm_s, 1e-5)) + 2)
    seeds = sim_seeds(seed, count, start=1)
    inputs = [dep.input(s) for s in seeds]
    jax.block_until_ready(inputs)
    # A full garbage collection walks every object the traced program
    # left behind, and stalls whatever simulation it lands in by half a
    # second or more: collect now, keep what set-up made out of later
    # collections, and collect nothing inside the window.
    t = time.perf_counter()
    gc.collect()
    gc_s = time.perf_counter() - t
    gc.freeze()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # the benchmark's spans suffice
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    compile_s, compiles = clock.seconds, clock.count
    setup_s = time.perf_counter() - T_START

    # ---- window --------------------------------------------------------
    gc.disable()
    try:
        with span(SPAN_WINDOW):
            kept, scalars, wall, each = window(dep, inputs, seconds, span)
    finally:
        gc.enable()
    if trace:
        jax.profiler.stop_trace()
    if clock.count != compiles:
        raise RuntimeError(f"benchmark: {clock.count - compiles} compiles "
                           "inside the measured window")
    memory_peak = peak_bytes(devices, require_chip)
    del inputs
    dep.bundle = None

    # ---- check ----------------------------------------------------------
    t_check = time.perf_counter()
    n = len(kept)
    seeds = seeds[:n]
    failed, worst = 0, {k: 0 for k in mod.LIMITS}
    for s, k, sc in zip(seeds, kept, scalars):
        got = dep.observe(k, sc)
        overflow = sum(int(v) for v in jax.tree_util.tree_leaves(
            sc["overflow"]))
        if overflow or not dep.complete(got):
            failed += 1
        for name, v in dep.compare(got, dep.expected(s)).items():
            worst[name] = max(worst[name], v)
    correct = n > 0 and all(worst[k] <= lim for k, lim in mod.LIMITS.items())
    checks = {k: {"value": worst[k], "limit": lim}
              for k, lim in mod.LIMITS.items()}
    print(json.dumps({"build_s": build_s, "compile_s": compile_s,
                      "warm_simulation_s": warm_s, "gc_s": gc_s,
                      "setup_s": setup_s,
                      "window_s": wall, "simulations": n,
                      "simulation_s": each,
                      "micro_steps": [int(sc["micro_steps"]) for sc in scalars],
                      "check_s": time.perf_counter() - t_check}),
          file=sys.stderr)

    totals = {k: sum(int(sc[k]) for sc in scalars)
              for k in ("events", "windows", "micro_steps")}
    device = device_info(devices)
    device["memory_peak_bytes"] = memory_peak
    result = {"correct": bool(correct), "attempted": n, "failed": failed}
    if not trace:
        result["metrics"] = {
            "setup_s": {"value": setup_s, "unit": "s"},
            "events_per_s": {"value": totals["events"] / wall,
                             "unit": "events/s"},
            "sim_s_per_wall_s": {"value": n * dep.sim_seconds / wall,
                                 "unit": "sim-s/s"},
            "device_peak_gib": {"value": memory_peak / GIB, "unit": "GiB"},
        }
    else:
        from benchmark import trace_reduce

        reduced = trace_reduce.reduce(TRACE_DIR, window_span=SPAN_WINDOW,
                                      host_spans=HOST_SPANS)
        record = {"trace": reduced, "totals": totals, "wall_s": wall,
                  "spans": {"build_s": build_s, "compile_s": compile_s},
                  "shapes": dep.shapes, "device_kind": device["kind"]}
        result["metrics"] = per_layer(spec, workload, record)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["device"] = device
    result["checks"] = checks
    return result


def per_layer(spec: dict, workload: str, record: dict) -> dict:
    """Each per-layer metric of this cell, from its own reader; a
    reader that finds nothing to read leaves its metric out."""
    out = {}
    for m in spec["per_layer"]:
        if workload not in m.get("workloads", [workload]):
            continue
        reader = load_module(BENCH_DIR / "metrics" / f"{m['name']}.py")
        v = reader.read(record)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
