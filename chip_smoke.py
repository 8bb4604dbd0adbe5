#!/usr/bin/env python3
"""Chip smoke: drive the simulator's main path once on the accelerator,
through the entry points a user calls, and check what comes out.

Phases, all in this one process (nothing here starts a child that
needs the chip):

A. PHOLD at 10,240 hosts, load 8, 5 simulated seconds: bench.py's
   default cell and BASELINE.json's 10k shape, built by
   bench._build_phold and run through net.build.make_runner with the
   PHOLD bulk pass. On a TPU the default insert is "sort2" with the
   Pallas mailbox kernel, which must be in the compiled program.
   Checked: zero event and outbox overflow, every host received, and
   the final state bit-identical to the same seed run with
   route_impl="sort" (no kernel), the reference.
B. The reference's own `--test` deployment at full size: 1,000
   clients each moving 330 KiB to one server over TCP for 60
   simulated seconds (config/examples.py; ref examples.c:10-30),
   through shadow_tpu.cli.main in this process. Checked: exit 0, zero
   overflow, and whole downloads only (the run's app_rcvd). Then the
   same deployment at 20 clients, where every download must end: the
   TCP model completes 23 downloads in 60 s whatever the client count
   (see COMPLETE_CLIENTS).

`--chips 4` runs only the sharded path and what it is compared with:
PHOLD at 10,240 hosts over a 4-device mesh and the same run on one
device, bit-identical, with a quarter of the [H, K] event queue on
each device.

A failed check raises, so the script exits non-zero and prints no
result. Each phase prints one JSON line with its wall time, its
compile time (set-up) and events; its rate is a smoke reading of one
run, not a benchmark number. The
last line of stdout is the result:
{"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

# Phase A: bench.py's default PHOLD cell.
PHOLD_HOSTS = 10240
PHOLD_LOAD = 8
PHOLD_SIM_SECONDS = 5
PHOLD_SEED = 1
# Phase B: the reference's built-in --test deployment (examples.c).
TEST_CLIENTS = 1000
TEST_KIB = 330
# The most clients whose downloads all end within --test's 60
# simulated seconds under this repo's TCP model: the one server's
# listener refuses SYNs beyond an accept backlog of 4 and has 8 socket
# slots, and refused clients retry at 3, 5, 9, 17 and 33 s, so 23
# downloads end in time whatever the client count (CPU and chip alike;
# 20 of 20 end by 34.6 s).
COMPLETE_CLIENTS = 20

# Trace and lowering events nest (an outer trace's span holds its
# inner ones), so only the backend's own compile and cache loads are
# summed.
_COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


class CompileClock:
    """Seconds the XLA backend has spent compiling, or loading from
    the persistent cache, in this process (jax.monitoring), summed
    over threads: compiles that overlap each count in full."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event, duration, **kwargs):
        if event in _COMPILE_EVENTS:
            self.seconds += duration


def require_accelerator():
    """jax.devices(), or exit non-zero when JAX found no accelerator."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise SystemExit("chip_smoke: JAX found no accelerator")
    return devs


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: check failed: {what}")


def _phold_bundle():
    import bench

    return bench._build_phold(PHOLD_HOSTS, PHOLD_LOAD, PHOLD_SIM_SECONDS,
                              PHOLD_SEED)


def _compile_all(fns, sim):
    """Lower each fn for `sim`, then compile them all at once: the
    backend compile releases the GIL, so threads overlap what would
    otherwise be sequential minutes. (compiled programs, seconds)."""
    t0 = time.perf_counter()
    lowered = [fn.lower(sim) for fn in fns]
    with ThreadPoolExecutor(len(lowered)) as ex:
        compiled = list(ex.map(lambda low: low.compile(), lowered))
    return compiled, time.perf_counter() - t0


def _run(compiled, sim):
    """(sim, stats, seconds) of one run of a compiled program."""
    import jax

    t0 = time.perf_counter()
    out, stats = compiled(sim)
    jax.block_until_ready((out, stats))
    return out, stats, time.perf_counter() - t0


def _first_calls(fns, sim):
    """Call every fn on `sim` once, concurrently (each call traces,
    compiles and runs; the compiles overlap). Seconds per call."""
    import jax

    def call(fn):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(sim))
        return time.perf_counter() - t0

    with ThreadPoolExecutor(len(fns)) as ex:
        return list(ex.map(call, fns))


def _same_state(a, sa, b, sb, what: str) -> None:
    import numpy as np

    for name, x, y in (
            ("events_processed", sa.events_processed, sb.events_processed),
            ("app.rcvd", a.app.rcvd, b.app.rcvd),
            ("net.rng_ctr", a.net.rng_ctr, b.net.rng_ctr),
            ("events.time", a.events.time, b.events.time)):
        check(np.array_equal(np.asarray(x), np.asarray(y)),
              f"{what}: {name} differs")


def _phold_checks(sim, stats, what: str) -> int:
    import numpy as np

    check(int(sim.events.overflow) == 0, f"{what}: event overflow")
    check(int(sim.outbox.overflow) == 0, f"{what}: outbox overflow")
    rcvd = np.asarray(sim.app.rcvd)
    check(bool((rcvd > 0).all()),
          f"{what}: {int((rcvd == 0).sum())} of {rcvd.size} hosts "
          "received nothing")
    return int(stats.events_processed)


def phase_phold(platform: str, clock: CompileClock) -> dict:
    """Phase A: the default insert (sort2 + the Pallas mailbox on a
    TPU) against route_impl="sort" on the same seed."""
    from shadow_tpu.apps import phold
    from shadow_tpu.net.build import make_runner

    t0, c0 = time.perf_counter(), clock.seconds
    b = _phold_bundle()
    build_s = time.perf_counter() - t0
    (prog, ref_prog), compile_s = _compile_all(
        [make_runner(b, app_handlers=(phold.handler,),
                     app_bulk=phold.BULK, route_impl=impl)
         for impl in (None, "sort")], b.sim)
    kernel = "tpu_custom_call" in prog.as_text()
    if platform == "tpu":
        check(kernel, "sort2 insert compiled without the Pallas mailbox "
              "kernel (no tpu_custom_call)")
        check("tpu_custom_call" not in ref_prog.as_text(),
              "the route_impl='sort' reference carries a kernel")
    sim, stats, run_s = _run(prog, b.sim)
    ref, ref_stats, ref_run_s = _run(ref_prog, b.sim)
    events = _phold_checks(sim, stats, "phold")
    _phold_checks(ref, ref_stats, "phold reference (sort)")
    _same_state(sim, stats, ref, ref_stats,
                "phold default insert vs route_impl='sort'")
    return {"phase": "A_phold", "hosts": PHOLD_HOSTS, "load": PHOLD_LOAD,
            "sim_seconds": PHOLD_SIM_SECONDS,
            "wall_s": time.perf_counter() - t0,
            "build_s": build_s, "compile_s": compile_s,
            "backend_compile_s": clock.seconds - c0,
            "events": events, "mailbox_kernel": kernel,
            "run_s": run_s, "reference_run_s": ref_run_s,
            "smoke_events_per_s": events / run_s,
            "bit_identical_to_sort": True}


def phase_test_deployment(clock: CompileClock, clients: int,
                          every_download: bool) -> dict:
    """Phase B: `shadow-tpu --test --test-clients N` in this process.
    Every download that ends must end whole; `every_download` also
    requires all N to end within the 60 simulated seconds."""
    from shadow_tpu.cli import main as cli_main

    t0, c0 = time.perf_counter(), clock.seconds
    out = io.StringIO()
    with tempfile.TemporaryDirectory() as data_dir, \
            contextlib.redirect_stdout(out):
        rc = cli_main(["--test", "--test-clients", str(clients),
                       "-d", data_dir])
    wall, backend_s = time.perf_counter() - t0, clock.seconds - c0
    lines = out.getvalue().strip().splitlines()
    print("\n".join(lines[-12:]), file=sys.stderr)  # the run's own log
    check(rc == 0, f"--test exited {rc}")
    report = json.loads(lines[-1])
    check(report["overflow"] == 0, f"--test overflow {report['overflow']}")
    done, part = divmod(report.get("app_rcvd", 0), TEST_KIB * 1024)
    check(part == 0 and 0 < done <= clients,
          f"--test delivered {report.get('app_rcvd')} bytes, not a "
          f"whole number of {TEST_KIB} KiB downloads")
    if every_download:
        check(done == clients, f"--test completed {done} of {clients} "
              "downloads")
    return {"phase": f"B_test_{clients}_clients", "clients": clients,
            "kib_per_client": TEST_KIB,
            "sim_seconds": report["sim_seconds"],
            "downloads_completed": done,
            "every_download_required": every_download,
            "wall_s": wall, "backend_compile_s": backend_s,
            "events": report["events"], "windows": report["windows"],
            "smoke_events_per_wall_s": report["events"] / wall}


def phase_sharded(devs, clock: CompileClock, shards: int = 4) -> dict:
    """--chips 4: PHOLD over a `shards`-device mesh vs one device."""
    import bench
    from shadow_tpu.apps import phold
    from shadow_tpu.net.build import make_runner

    check(len(devs) >= shards, f"{shards} devices needed, "
          f"JAX found {len(devs)}")
    t0, c0 = time.perf_counter(), clock.seconds
    b = _phold_bundle()
    kw = dict(app_handlers=(phold.handler,), app_bulk=phold.BULK)
    fns = (bench.make_shard_aware_runner(b, shards, **kw),
           make_runner(b, **kw))
    first_s, ref_first_s = _first_calls(fns, b.sim)
    (sim, stats, run_s), (ref, ref_stats, ref_run_s) = (
        _run(fn, b.sim) for fn in fns)
    H, K = sim.events.time.shape
    parts = sim.events.time.addressable_shards
    check(len({p.device for p in parts}) == shards
          and all(p.data.shape == (H // shards, K) for p in parts),
          "the event queue is not split in equal row blocks over "
          f"{shards} devices: {[(str(p.device), p.data.shape) for p in parts]}")
    events = _phold_checks(sim, stats, "sharded phold")
    _same_state(sim, stats, ref, ref_stats,
                f"phold on {shards} devices vs one")
    return {"phase": f"phold_{shards}_devices", "hosts": PHOLD_HOSTS,
            "load": PHOLD_LOAD, "sim_seconds": PHOLD_SIM_SECONDS,
            "wall_s": time.perf_counter() - t0,
            "backend_compile_s": clock.seconds - c0, "events": events,
            "first_call_s": first_s, "run_s": run_s,
            "one_device_first_call_s": ref_first_s,
            "one_device_run_s": ref_run_s,
            "smoke_events_per_s": events / run_s,
            "queue_rows_per_device": H // shards,
            "bit_identical_to_one_device": True}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded PHOLD path on a "
                         "4-device mesh and its one-device comparison")
    args = ap.parse_args(argv)

    devs = require_accelerator()
    import jax

    from shadow_tpu.utils.compcache import enable_compile_cache

    enable_compile_cache()
    platform = devs[0].platform
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        if args.chips == 4:
            rows = [phase_sharded(devs, clock)]
        else:
            rows = [phase_phold(platform, clock)]
            print(json.dumps(rows[-1]), flush=True)
            rows.append(phase_test_deployment(clock, TEST_CLIENTS, False))
            print(json.dumps(rows[-1]), flush=True)
            rows.append(phase_test_deployment(clock, COMPLETE_CLIENTS,
                                              True))
        print(json.dumps(rows[-1]), flush=True)
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
