"""Command-line entry point — the analog of the reference's bootstrap
+ Options layer (ref: main.c:734-802, options.c). No TLS/relaunch
dance (SURVEY.md §7.5): parse flags, load the XML config, build device
state, run, report.

Flag parity with options.c (flags whose mechanism has no TPU analog
are accepted and mapped or no-op'd, so reference invocations keep
working):
  --workers       -> number of mesh shards (device axis size)
  --scheduler-policy -> accepted; all policies map to the one device
                     scheduler (ref policies are pthread shardings)
  --seed, --runahead, --bootstrap-end, --interface-qdisc,
  --socket-recv-buffer, --socket-send-buffer, --log-level,
  --heartbeat-frequency, --tcp-congestion-control (reno only)
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import numpy as np


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="shadow-tpu",
        description="TPU-native parallel discrete-event network simulator",
    )
    p.add_argument("config", nargs="?", help="shadow.config.xml path")
    p.add_argument("--test", action="store_true",
                   help="run the built-in example config (ref: --test)")
    p.add_argument("--test-clients", type=int, default=1000,
                   help="clients in the built-in --test config; the "
                        "reference bakes in 1000 (examples.c:10-12)")
    p.add_argument("-w", "--workers", type=int, default=1,
                   help="device shards (ref: worker threads)")
    p.add_argument("-s", "--seed", type=int, default=1)
    p.add_argument("--scheduler-policy", default="device",
                   choices=["device", "host", "steal", "thread",
                            "threadXthread", "threadXhost"],
                   help="accepted for config compatibility; one device "
                        "scheduler implements the window semantics")
    p.add_argument("--runahead", type=int, default=0,
                   help="minimum window (ms), 0 = derive from topology "
                        "min latency (ref: master.c:133-159)")
    p.add_argument("--bootstrap-end", type=int, default=0,
                   help="unlimited-bandwidth bootstrap period (s)")
    p.add_argument("--interface-qdisc", default="fifo",
                   choices=["fifo", "rr"])
    p.add_argument("--router-qdisc", default="codel",
                   choices=["codel", "single", "static"],
                   help="upstream router queue manager (ref: the "
                        "QueueManagerHooks vtable, router.c; CoDel "
                        "default per host.c:205)")
    p.add_argument("--socket-recv-buffer", type=int, default=174760)
    p.add_argument("--socket-send-buffer", type=int, default=131072)
    p.add_argument("--tcp-congestion-control", default="reno",
                   choices=["reno", "aimd", "cubic"],
                   help="congestion algorithm (ref: the tcp_cong.h "
                        "hook vtable; the reference implements only "
                        "reno, the vtable was designed for all three)")
    p.add_argument("--tcp-ssthresh", type=int, default=0,
                   help="initial slow-start threshold in packets, "
                        "0 = discover via loss (ref: options.c:137)")
    p.add_argument("--tcp-windows", type=int, default=0,
                   help="pin the initial congestion window in packets, "
                        "0 = protocol default (ref: options.c:138)")
    p.add_argument("--cpu-threshold", type=int, default=-1,
                   help="virtual-CPU blocking threshold in microseconds, "
                        "negative disables the CPU model "
                        "(ref: options.c:130)")
    p.add_argument("--cpu-precision", type=int, default=200,
                   help="round CPU delays to this many microseconds "
                        "(ref: options.c:129)")
    p.add_argument("-l", "--log-level", default="message",
                   choices=["error", "critical", "warning", "message",
                            "info", "debug"])
    p.add_argument("--heartbeat-frequency", type=int, default=60,
                   help="tracker heartbeat interval (s)")
    p.add_argument("--heartbeat-log-level", default="message")
    p.add_argument("-i", "--heartbeat-log-info",
                   default="node,socket,ram",
                   help="comma list of heartbeat sections "
                        "('node','socket','ram'); the reference "
                        "defaults to 'node' alone (options.c:92)")
    # Accepted for reference-invocation compatibility; their mechanism
    # has no analog here (no native binaries to preload or debug, no
    # data template tree, interface batching is the fixed 1 ms
    # token-bucket refill) — see the module docstring.
    for flag in ("--preload", "--data-template"):
        p.add_argument(flag, default=None, help=argparse.SUPPRESS)
    for flag in ("--gdb", "--valgrind"):
        p.add_argument(flag, action="store_true", help=argparse.SUPPRESS)
    for flag in ("--interface-batch", "--interface-buffer"):
        p.add_argument(flag, type=int, default=None,
                       help=argparse.SUPPRESS)
    p.add_argument("-d", "--data-directory", default="shadow.data")
    # default None = let the plugin capacity hints size these
    # (loader.py hints; an explicit value always wins, matching the
    # reference's Options-beats-everything precedence)
    p.add_argument("--sockets-per-host", type=int, default=None)
    p.add_argument("--platform", default="auto",
                   help="JAX backend to run on ('auto' = honor "
                        "JAX_PLATFORMS, else JAX's default; 'cpu' "
                        "runs on the CPU backend)")
    p.add_argument("--track-paths", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="count packets per (src,dst) topology vertex "
                        "pair, logged at shutdown (ref: topology.c "
                        "per-path counters); works serial and sharded "
                        "(per-shard partials psum at the barrier); "
                        "--no-track-paths overrides a config that "
                        "enables it")
    p.add_argument("--event-capacity", type=int, default=None)
    p.add_argument("--outbox-capacity", type=int, default=None)
    p.add_argument("--router-ring", type=int, default=None)
    # --- open-system injection (shadow_tpu/inject) -------------------
    p.add_argument("--inject-trace", default=None, metavar="PATH",
                   help="stream an injection trace (newline-JSON or "
                        "binary, see docs/9-injection.md) into the "
                        "simulated hosts; overrides a config's "
                        "<traffic> elements. The injected kinds must "
                        "have a device handler (the tgen plugin, or "
                        "tools/trace_gen.py targeting one)")
    p.add_argument("--inject-lanes", type=int, default=None,
                   help="device staging lanes for injection "
                        "(power of two; default sized from the trace "
                        "length, capped at 1024 — longer traces "
                        "stream through a host-driven loop)")
    # --- window telemetry (shadow_tpu/telemetry) ---------------------
    p.add_argument("--trace-out", default=None,
                   help="write a Chrome-trace/Perfetto JSON of "
                        "per-window telemetry records (sim-time track) "
                        "plus wall-clock phase spans; enables the "
                        "device-resident telemetry ring")
    p.add_argument("--metrics-out", default=None,
                   help="write final counters as Prometheus text "
                        "exposition; enables the telemetry ring")
    p.add_argument("--telemetry-capacity", type=int, default=None,
                   help="telemetry ring capacity in window records "
                        "(default 4096); overruns are latched as a "
                        "health warning, never silently")
    p.add_argument("--flow-sample", type=int, default=0, metavar="N",
                   help="sample 1-in-N cross-host packets into the "
                        "per-flow latency flight recorder "
                        "(telemetry/flows.py): deterministic "
                        "(time,dst,src,seq)-hash sampling, per-lane "
                        "latency histograms and a cross-shard traffic "
                        "matrix in the manifest. 0 (default) = off, "
                        "byte-identical to builds without the recorder")
    p.add_argument("--flow-capacity", type=int, default=None,
                   help="flow ring capacity in sampled records "
                        "(default 4096); window-clamp and overrun "
                        "losses are accounted, never silent")
    p.add_argument("--causality-sample", type=int, default=0, metavar="N",
                   help="sample 1-in-N emitted events into the causal "
                        "lineage recorder (telemetry/causality.py): "
                        "parent/child event keys, window-advance "
                        "attribution (which clamp decided every window "
                        "end), top-K critical chains and a binding-"
                        "cause histogram in the manifest, a critical-"
                        "path track in --trace-out, and the input "
                        "tools/critpath.py turns into a speed-of-light "
                        "report. 0 (default) = off, byte-identical to "
                        "builds without the recorder")
    p.add_argument("--causality-capacity", type=int, default=None,
                   help="per-host lineage sub-ring capacity in sampled "
                        "events (default 64); overruns are accounted "
                        "in the manifest, never silently")
    p.add_argument("--profile-dir", default=None, metavar="DIR",
                   help="capture a jax.profiler trace of the window "
                        "loop into DIR (view with TensorBoard / "
                        "Perfetto); the artifact path is recorded in "
                        "run_manifest.json")
    # --- run supervisor (faults/supervisor.py) -----------------------
    p.add_argument("--host-kernel", choices=("run", "diff"), default=None,
                   help="execute the config's .py-plugin processes on "
                        "the REAL host kernel (hostrun backend): 'run' "
                        "executes there only; 'diff' runs both backends "
                        "and diffs normalized syscall traces, writing a "
                        "conformance block into run_manifest.json "
                        "(exit 4 on divergence; docs/7-conformance.md)")
    p.add_argument("--host-time-scale", type=float, default=0.05,
                   help="host-kernel backend: simulated seconds -> real "
                        "seconds for sleeps/timers (default 0.05)")
    p.add_argument("--supervise", action="store_true",
                   help="host-driven window loop with health latches, "
                        "periodic checkpoints, and checkpoint-backed "
                        "retry on a latch trip (exit 3 + structured "
                        "failure report when retries are exhausted)")
    p.add_argument("--chunk-windows", type=int, default=None,
                   metavar="K",
                   help="windows per device dispatch for the "
                        "supervised/host-driven loop: K window rounds "
                        "run on device between host barriers, "
                        "amortizing dispatch overhead when windows are "
                        "small (health checks, harvest and checkpoint "
                        "cadence then run per chunk; default 1)")
    p.add_argument("--adaptive-jump", action="store_true", default=None,
                   help="derive each window's span from the LIVE "
                        "latency/reliability tables instead of the "
                        "static precomputed minimum — fault plans that "
                        "raise latencies let windows grow (fewer "
                        "windows, same final state; supervised/"
                        "host-driven loop only)")
    p.add_argument("--checkpoint-every-windows", type=int, default=64,
                   help="supervisor snapshot cadence in windows")
    p.add_argument("--checkpoint-path", default=None,
                   help="snapshot path prefix (default: "
                        "<data-directory>/checkpoint)")
    p.add_argument("--max-retries", type=int, default=2,
                   help="resume attempts after a latch trip before "
                        "giving up")
    p.add_argument("--retry-backoff", type=float, default=0.25,
                   help="base seconds of exponential backoff between "
                        "retries")
    p.add_argument("--max-run-wallclock", type=float, default=None,
                   metavar="SECONDS",
                   help="supervised runs: per-run wallclock deadline "
                        "— when a round barrier finds it spent, take "
                        "the preemption-style final snapshot, latch a "
                        "'deadline' health fault, and exit 3 with the "
                        "snapshot path in the report (--resume "
                        "continues); the in-process counterpart of "
                        "the fleet watchdog (docs/8-fleet.md)")
    p.add_argument("--stall-windows", type=int, default=512,
                   help="consecutive zero-event windows before the "
                        "stall latch trips")
    p.add_argument("--lane-isolation", type=int, default=None,
                   metavar="R",
                   help="partition the hosts into R contiguous lanes "
                        "with lane-scoped health latches "
                        "(core/lanes.py): a capacity trip quarantines "
                        "only the tripped lane — its hosts freeze at "
                        "the window barrier while healthy lanes run to "
                        "completion (blast-radius containment for "
                        "packed ensemble runs; supervised runs salvage "
                        "the sick lane's slice from the last clean "
                        "checkpoint). Lanes must not exchange traffic "
                        "for healthy-lane bit-exactness; single-shard "
                        "only (docs/6-robustness.md)")
    p.add_argument("--resident", action="store_true",
                   help="attach resident-admission lease planes to a "
                        "lane-isolated run (requires --lane-isolation; "
                        "core/lanes.py LaneAdmission): every lane "
                        "boots with an open lease, barriers enforce "
                        "free-lane flush + completion latching, and "
                        "the manifest gains an 'admission' block. "
                        "This is the static-population twin of "
                        "`fleet run --resident`, whose lease table "
                        "churns lanes at barriers (docs/8-fleet.md)")
    p.add_argument("--auto-grow", action="store_true",
                   help="supervisor escalation: a fatal capacity "
                        "overflow (event queue / outbox / router ring) "
                        "doubles the tripped knob, rebuilds at the "
                        "grown shapes, and transplants the last clean "
                        "checkpoint instead of consuming a retry "
                        "(faults/escalate.py)")
    p.add_argument("--max-grow", type=int, default=8,
                   help="escalation budget: total capacity doublings "
                        "allowed across the run (chain-wide)")
    p.add_argument("--specialize", choices=("auto", "off"),
                   default="auto",
                   help="compile-time program specialization "
                        "(compile/specialize.py): auto (default) "
                        "proves capabilities statically dead for this "
                        "build (all-ones reliability table with no "
                        "fault plan touching it; no handler that can "
                        "arm a host timer) and trims their subgraphs "
                        "out of the traced program, keying the "
                        "variant separately in the warm program "
                        "store; a device guard latch turns any "
                        "violated assumption into a fatal health "
                        "fault. off always runs the full program")
    p.add_argument("--resume", default=None, metavar="PATH",
                   help="continue a previous run from its checkpoint: "
                        "a snapshot file, a checkpoint path prefix, or "
                        "a data directory (newest snapshot wins). "
                        "Implies --supervise; capacities recorded in "
                        "the snapshot metadata are applied "
                        "automatically, and a different --workers "
                        "count is fine (snapshots are global-layout)")
    p.add_argument("--version", action="version",
                   version="shadow-tpu 0.1 (capability target: shadow 1.x)")
    return p


def overrides_from_args(args) -> dict:
    """Map parsed CLI flags onto config-loader overrides (None values
    mean "keep the config/default"). Reference units: the CPU knobs
    are microseconds (options.c:129-130), negative threshold = CPU
    model disabled."""
    overrides = {
        "tcp_ssthresh": args.tcp_ssthresh or None,
        "tcp_windows": args.tcp_windows or None,
        "cpu_threshold_ns": (args.cpu_threshold * 1000
                             if args.cpu_threshold >= 0 else None),
        "cpu_precision_ns": (args.cpu_precision * 1000
                             if args.cpu_precision >= 0 else None),
        "interface_qdisc": args.interface_qdisc,
        "router_qdisc": args.router_qdisc,
        "socket_recv_buffer": args.socket_recv_buffer,
        "socket_send_buffer": args.socket_send_buffer,
        "tcp_congestion_control": args.tcp_congestion_control,
        "runahead": args.runahead,
        "sockets_per_host": args.sockets_per_host,
        "event_capacity": args.event_capacity,
        "outbox_capacity": args.outbox_capacity,
        "router_ring": args.router_ring,
        "track_paths": args.track_paths,
        "windows_per_dispatch": args.chunk_windows,
        "adaptive_jump": args.adaptive_jump,
        "inject_lanes": args.inject_lanes,
    }
    return {k: v for k, v in overrides.items() if v is not None}


def _resolve_resume(path: str) -> str | None:
    """--resume accepts a snapshot file, a checkpoint prefix, or a
    data directory; returns the newest matching snapshot path."""
    import os

    from shadow_tpu.utils import checkpoint as ckpt

    if os.path.isdir(path):
        return ckpt.latest_checkpoint(os.path.join(path, "checkpoint"))
    if os.path.isfile(path):
        return path
    return ckpt.latest_checkpoint(path)


def _host_kernel_mode(args, b, loaded, logger) -> int:
    """--host-kernel: execute the config's virtual processes on the
    real OS (hostrun backend). 'diff' additionally runs the simulation
    and compares normalized syscall traces — the dual-mode conformance
    check (docs/7-conformance.md). Exit codes: 0 agree/ran, 2 sandbox
    has no bindable localhost ports, 4 divergence."""
    import os

    from shadow_tpu import hostrun
    from shadow_tpu.hostrun.trace import TraceRecorder

    try:
        hostrun.PortAllocator.preflight()
    except hostrun.PortsUnavailable as e:
        print(f"error: host-kernel backend unavailable: {e}",
              file=sys.stderr)
        return 2

    ip_names = {int(b.ip_of(n)): n for n in b.host_names}
    host_rec = TraceRecorder(ip_names=ip_names)
    ex = hostrun.HostKernelExecutor(
        b, time_scale=args.host_time_scale, trace=host_rec)
    for hi, fn, st, sp in loaded.vprocs:
        ex.spawn(hi, fn, start_time=st, stop_time=sp)
    t0 = time.time()
    ex.run()
    wall = time.time() - t0
    logger.message(0, "shadow-tpu",
                   f"host-kernel run complete: {len(ex.procs)} "
                   f"process(es), {wall:.2f}s wall")
    if args.host_kernel == "run":
        print(json.dumps({"mode": "host-kernel-run",
                          "processes": len(ex.procs),
                          "wall_seconds": round(wall, 3)}))
        return 0

    # diff: the same generators through the simulation, then compare
    from shadow_tpu import telemetry
    from shadow_tpu.process.vproc import ProcessRuntime

    sim_rec = TraceRecorder(ip_names=ip_names)
    rt = ProcessRuntime(b, app_handlers=loaded.handlers)
    rt.trace = sim_rec
    for hi, fn, st, sp in loaded.vprocs:
        rt.spawn(hi, fn, start_time=st, stop_time=sp)
    sim, stats = rt.run()
    res = hostrun.diff_traces(sim_rec.normalized(), host_rec.normalized())
    print(hostrun.render(res))
    name = os.path.basename(args.config) if args.config else "config"
    conf = {"workloads": {name: "agree" if res.agree else "diverge"},
            "agree": int(res.agree), "diverge": int(not res.agree),
            "total": 1}
    man = telemetry.run_manifest(
        cfg=b.cfg, seed=args.seed, shards=1, sim=sim, stats=stats,
        fault_plan=b.fault_plan, conformance=conf)
    os.makedirs(args.data_directory, exist_ok=True)
    mpath = telemetry.write_manifest(
        os.path.join(args.data_directory, "run_manifest.json"), man)
    logger.message(0, "shadow-tpu", f"run manifest -> {mpath}")
    print(json.dumps({"mode": "host-kernel-diff", "agree": res.agree,
                      "manifest": mpath}))
    return 0 if res.agree else 4


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "fleet":
        # `shadow-tpu fleet ...` is its own sub-CLI (fleet/cli.py);
        # delegate before the single-run parser sees the argv
        from shadow_tpu.fleet.cli import main as fleet_main

        return fleet_main(argv[1:])
    if argv and argv[0] == "sweep":
        # `shadow-tpu sweep ...` — the counterfactual sweep engine
        # (sweep/cli.py); same delegation rule as fleet
        from shadow_tpu.sweep.cli import main as sweep_main

        return sweep_main(argv[1:])
    args = make_parser().parse_args(argv)

    # persist compiled device programs across CLI invocations (the
    # netstack step compiles in minutes cold; seconds warm)
    import jax

    from shadow_tpu.utils.compcache import enable_compile_cache

    enable_compile_cache()
    # select the backend through jax.config before anything starts
    # one (JAX reads JAX_PLATFORMS itself; --platform beats it)
    import os

    if args.platform != "auto":
        jax.config.update("jax_platforms", args.platform)

    from shadow_tpu.config.examples import example_config
    from shadow_tpu.config.loader import load
    from shadow_tpu.config.xmlconfig import parse_config
    from shadow_tpu.utils.shadowlog import SimLogger, level_from_name

    if args.test:
        text = example_config(clients=args.test_clients)
    elif args.config:
        with open(args.config) as f:
            text = f.read()
    else:
        print("error: provide a config path or --test", file=sys.stderr)
        return 1

    logger = SimLogger(level=level_from_name(args.log_level))
    # jax.profiler capture state (--profile-dir): started just before
    # the run branch, stopped at convergence and again (idempotently)
    # in the finally so an abort never leaves the tracer running
    _prof = {"on": False}

    def _stop_profile():
        if _prof["on"]:
            _prof["on"] = False
            with contextlib.suppress(Exception):
                jax.profiler.stop_trace()

    # flush on every exit path so a mid-run failure still
    # surfaces the buffered sim log (the reference flushes
    # each round, slave.c:446-450)
    try:
        cfg = parse_config(text)
        # --resume: find the snapshot BEFORE building, because its
        # recorded capacities must size the build (a post-escalation
        # snapshot is larger than the config says; a mismatch is
        # diagnosed by name either way, never resumed into garbage)
        resume_ckpt = None
        resume_meta = None
        overrides = overrides_from_args(args)
        if args.resume:
            resume_ckpt = _resolve_resume(args.resume)
            if resume_ckpt is None:
                print(f"error: no checkpoint found at {args.resume}",
                      file=sys.stderr)
                return 1
            args.supervise = True
            from shadow_tpu.utils import checkpoint as ckpt_mod

            resume_meta = ckpt_mod.peek_meta(resume_ckpt)
            for k, v in (resume_meta.get("capacities") or {}).items():
                if k in ("event_capacity", "outbox_capacity",
                         "router_ring"):
                    overrides[k] = max(int(overrides.get(k) or 0), int(v))
        if args.inject_trace and "inject_lanes" not in overrides:
            # size the staging buffer from the trace before the build
            # (the same default the loader applies to <traffic>
            # elements); one extra sequential read of the file is
            # cheap next to the device build
            from shadow_tpu.apps.tgen import lanes_for
            from shadow_tpu.inject import read_trace

            n_ev = sum(1 for _ in read_trace(args.inject_trace))
            overrides["inject_lanes"] = lanes_for(n_ev)
        # relative <topology path> / <plugin path="*.py"> entries are
        # relative to the CONFIG FILE, not the cwd (the reference
        # resolves the same way) — load() handles both via base_dir
        loaded = load(cfg, seed=args.seed,
                      overrides=overrides,
                      base_dir=os.path.dirname(os.path.abspath(args.config))
                      if args.config else None)
        b = loaded.bundle
        if resume_meta is not None and resume_meta.get("config_digest"):
            from shadow_tpu.telemetry.export import config_hash

            if resume_meta["config_digest"] != config_hash(b.cfg):
                logger.warning(
                    0, "shadow-tpu",
                    "resume snapshot was taken under a different "
                    "config digest — continuing, but the runs are "
                    "not the same simulation")
        logger.message(0, "shadow-tpu", f"built {b.cfg.num_hosts} hosts, "
                       f"min window {b.min_jump} ns, "
                       f"end {b.cfg.end_time} ns")

        # open-system injection: an explicit --inject-trace beats the
        # config's compiled <traffic> trace (the CLI-beats-XML
        # precedence every other knob follows)
        feeder = None
        if args.inject_trace or loaded.inject_events:
            from shadow_tpu.inject import Feeder

            if loaded.vprocs:
                print("error: event injection needs the on-device "
                      "window loop; .py-plugin virtual processes "
                      "cannot consume injected events",
                      file=sys.stderr)
                logger.flush()
                return 1
            if args.inject_trace and loaded.inject_events:
                logger.warning(
                    0, "shadow-tpu",
                    "--inject-trace overrides the config's <traffic> "
                    "elements")
            feeder = Feeder(args.inject_trace
                            or list(loaded.inject_events))
            logger.message(
                0, "shadow-tpu",
                f"injection staging: {b.sim.inject.lanes} lanes, "
                f"source "
                f"{args.inject_trace or '<traffic> elements'}")

        t0 = time.time()

        # periodic run-time progress records (the reference's per-round
        # heartbeat, slave.c:390-411, feeding plot-shadow's tick plot).
        # Host-driven window loops call this per window; the whole-run
        # device path reports a single final tick instead (a per-window
        # host callback would forfeit its on-device speed).
        prog_state = {"last": -1}

        def progress_hook(s, wend):
            sec = int(wend) // 10**9
            bucket = sec // max(args.heartbeat_frequency, 1)
            if bucket > prog_state["last"]:
                prog_state["last"] = bucket
                logger.message(
                    int(wend), "shadow-tpu", "[shadow-progress] "
                    + json.dumps({
                        "sim_seconds": round(int(wend) / 1e9, 3),
                        "wall_seconds": round(time.time() - t0, 3)}))

        # lane-isolated health (core/lanes.py): attach BEFORE the
        # telemetry ring — the ring sizes its per-lane fan-out planes
        # off sim.lanes. Single-shard, on-device window loop only.
        if args.lane_isolation:
            if loaded.vprocs:
                logger.warning(0, "shadow-tpu",
                               "--lane-isolation is unavailable with "
                               ".py plugins (ProcessRuntime window "
                               "loop); ignored")
            elif args.workers > 1:
                logger.warning(0, "shadow-tpu",
                               "--lane-isolation is single-shard only; "
                               f"--workers {args.workers} wins, lane "
                               "isolation disabled")
            else:
                from shadow_tpu.core import lanes as lanes_mod

                try:
                    b.sim = lanes_mod.attach(b.sim, args.lane_isolation)
                except ValueError as e:
                    print(f"error: --lane-isolation: {e}",
                          file=sys.stderr)
                    logger.flush()
                    return 1
                logger.message(
                    0, "shadow-tpu",
                    f"lane isolation: {args.lane_isolation} lanes x "
                    f"{b.cfg.num_hosts // args.lane_isolation} hosts")
                if args.resident:
                    # static-population resident planes: all lanes
                    # admitted at t=0 with open leases; the window
                    # barrier now also enforces the admission rules
                    # (free-lane flush, completion latch) and the
                    # manifest carries the lease-conservation block
                    b.sim = lanes_mod.admit_all(
                        lanes_mod.attach_admission(b.sim))
                    logger.message(
                        0, "shadow-tpu",
                        f"resident admission: "
                        f"{args.lane_isolation} lanes admitted with "
                        f"open leases")
        if args.resident and getattr(b.sim, "admission", None) is None:
            logger.warning(0, "shadow-tpu",
                           "--resident requires --lane-isolation "
                           "(admission is lease bookkeeping over "
                           "lanes); ignored")

        # window telemetry (shadow_tpu/telemetry): attach the on-device
        # ring BEFORE any run path branches so checkpoint templates,
        # the supervisor's resume template, and the compiled programs
        # all see the same pytree. A None ring costs literally zero
        # compiled ops (make_telem_fn is a trace-time no-op), so runs
        # without these flags are untouched.
        telem_on = bool(args.trace_out or args.metrics_out
                        or args.telemetry_capacity)
        flows_on = bool(args.flow_sample and args.flow_sample > 0)
        caus_on = bool(args.causality_sample
                       and args.causality_sample > 0)
        harvester = None
        timers = None
        if (telem_on or flows_on or caus_on) and loaded.vprocs:
            logger.warning(0, "shadow-tpu",
                           "window telemetry is unavailable with .py "
                           "plugins (ProcessRuntime drives its own "
                           "window loop); --trace-out/--metrics-out/"
                           "--flow-sample/--causality-sample ignored")
            telem_on = False
            flows_on = False
            caus_on = False
        if telem_on:
            from shadow_tpu import telemetry

            b.sim = telemetry.attach(
                b.sim,
                capacity=args.telemetry_capacity
                or telemetry.DEFAULT_CAPACITY)
        if flows_on:
            # flow flight-recorder (telemetry/flows.py): deterministic
            # 1-in-N packet sampling at the window barrier; drained by
            # the same harvester as the window ring
            from shadow_tpu import telemetry
            from shadow_tpu.telemetry import flows as flows_mod

            try:
                b.sim = telemetry.attach_flows(
                    b.sim, sample_period=args.flow_sample,
                    capacity=args.flow_capacity
                    or flows_mod.DEFAULT_CAPACITY)
            except ValueError as e:
                print(f"error: --flow-sample: {e}", file=sys.stderr)
                logger.flush()
                return 1
            logger.message(
                0, "shadow-tpu",
                f"flow tracing: 1-in-{args.flow_sample} packet "
                f"sampling, ring capacity "
                f"{args.flow_capacity or flows_mod.DEFAULT_CAPACITY}")
        if caus_on:
            # causal lineage recorder (telemetry/causality.py): the
            # same deterministic hash sampling discipline as the flow
            # recorder, plus per-window advance attribution at the
            # barrier; drained by the same harvester
            from shadow_tpu import telemetry
            from shadow_tpu.telemetry import causality as caus_mod

            try:
                b.sim = telemetry.attach_causality(
                    b.sim, sample_period=args.causality_sample,
                    capacity=args.causality_capacity
                    or caus_mod.DEFAULT_CAPACITY)
            except ValueError as e:
                print(f"error: --causality-sample: {e}",
                      file=sys.stderr)
                logger.flush()
                return 1
            logger.message(
                0, "shadow-tpu",
                f"causality tracing: 1-in-{args.causality_sample} "
                f"event sampling, per-host lineage capacity "
                f"{args.causality_capacity or caus_mod.DEFAULT_CAPACITY}")
        if telem_on or flows_on or caus_on:
            from shadow_tpu import telemetry

            harvester = telemetry.Harvester()
            timers = telemetry.PhaseTimers()

        # compile-time program specialization (compile/specialize.py):
        # derive the capability vector from the CONCRETE build — after
        # every optional attachment, so the analysis sees the final
        # sim composition — and trim statically-dead subgraphs from
        # the trace. The guard latch attached here turns a violated
        # assumption into a fatal health fault (exit 3), never silent
        # drift. .py-plugin runtimes arm host timers outside the
        # handler declaration surface, so they run the full program.
        from shadow_tpu.compile import specialize

        if loaded.vprocs or args.host_kernel:
            b = specialize.apply(b, mode="off")
        else:
            b = specialize.apply(b, loaded.handlers,
                                 app_bulk=b.app_bulk,
                                 mode=args.specialize)
        if b.caps is not None and b.caps.dropped():
            logger.message(
                0, "shadow-tpu",
                "specialization: trimmed "
                + ",".join(b.caps.dropped())
                + f" (program-key extra {b.caps.key_extra()!r}; "
                  f"guard latch armed)")

        cap = None
        if b.cfg.pcap:
            # pcap capture needs a host-driven window loop to drain
            # the ring (ref: per-interface PCapWriter, pcap_writer.c)
            from shadow_tpu.utils.pcap import CaptureSession

            cap = CaptureSession(b, args.data_directory)
        mesh = None
        sup_result = None  # set by the --supervise branch
        # warm-program serving (compile/serve.py): every run path
        # hands this dict to its runner; the manifest records the
        # realized {key, hit, load_s|compile_s} block from it (the
        # supervised path uses the supervisor's own copy instead)
        cinfo: dict = {}
        # --profile-dir: bracket the device work with a jax.profiler
        # trace; the manifest's "profile" block records where the
        # artifact landed so tooling can find it without guessing
        profile_info = None
        if args.profile_dir:
            try:
                os.makedirs(args.profile_dir, exist_ok=True)
                jax.profiler.start_trace(args.profile_dir)
                _prof["on"] = True
                profile_info = {"dir": os.path.abspath(args.profile_dir),
                                "tool": "jax.profiler"}
            except Exception as e:  # profiler backend is optional
                logger.warning(0, "shadow-tpu",
                               f"--profile-dir: capture unavailable "
                               f"({e}); continuing without profile")
        # track_paths no longer forces serial: shard-local [V,V]
        # partials are psummed at the window barrier
        # (parallel/shard.py _replicate_scalars)
        if args.workers > 1 and b.cfg.pcap:
            logger.warning(0, "shadow-tpu",
                           f"logpcap forces the serial window loop; "
                           f"--workers {args.workers} ignored")
        elif args.workers > 1:
            from jax.sharding import Mesh

            # one shard per device: asking for more workers than
            # there are devices is an error, never a silent clamp
            ndev = len(jax.devices())
            if args.workers > ndev:
                print(f"error: --workers {args.workers} exceeds the "
                      f"{ndev} {jax.devices()[0].platform} device(s) "
                      "JAX found", file=sys.stderr)
                logger.flush()
                return 1
            # contiguous-block sharding needs hosts % shards == 0; the
            # reference accepts any worker count for any host count
            # (scheduler.c round-robins), so adapt rather than error:
            # largest divisor of H within the request
            wmax = min(args.workers, b.cfg.num_hosts)
            w = max(d for d in range(1, wmax + 1)
                    if b.cfg.num_hosts % d == 0)
            if w != args.workers:
                logger.warning(
                    0, "shadow-tpu",
                    f"--workers {args.workers} does not divide "
                    f"{b.cfg.num_hosts} hosts; using {w}")
            if w > 1:
                mesh = Mesh(np.array(jax.devices()[:w]), ("hosts",))
        if args.host_kernel:
            if not loaded.vprocs:
                print("error: --host-kernel needs a config with .py "
                      "plugins (virtual processes)", file=sys.stderr)
                logger.flush()
                return 1
            code = _host_kernel_mode(args, b, loaded, logger)
            logger.flush()
            return code
        if loaded.vprocs:
            # .py plugins: coroutine processes over the simulated
            # syscall surface — the config-reachable form of the
            # reference's plugin loading (SURVEY §7.1). Composes with
            # pcap: the runtime's window loop drains the capture ring.
            from shadow_tpu.process.vproc import ProcessRuntime

            if b.app_bulk is not None:
                # ProcessRuntime's window loop has no bulk-pass hook
                # yet; a mixed .py-plugin + bulk-capable-app config
                # falls back to per-event micro-steps.
                logger.warning(0, "shadow-tpu",
                               "bulk window pass unavailable with .py "
                               "plugins; using per-event micro-steps")
            rt = ProcessRuntime(b, app_handlers=loaded.handlers,
                                mesh=mesh)
            for hi, fn, st, sp in loaded.vprocs:
                rt.spawn(hi, fn, start_time=st, stop_time=sp)
            def vproc_hook(s, wend, _cap=cap):
                if _cap is not None:
                    _cap.drain(s)
                progress_hook(s, wend)

            sim, stats = rt.run(on_window=vproc_hook)
        elif args.supervise:
            import signal

            from shadow_tpu.faults.escalate import EscalationPolicy
            from shadow_tpu.faults.supervisor import run_supervised
            from shadow_tpu.telemetry.export import config_hash

            ckpt_prefix = args.checkpoint_path or os.path.join(
                args.data_directory, "checkpoint")
            os.makedirs(os.path.dirname(os.path.abspath(ckpt_prefix)),
                        exist_ok=True)

            def sup_hook(s, wend, _cap=cap):
                if _cap is not None:
                    _cap.drain(s)
                progress_hook(s, wend)

            # preemption safety: the first SIGTERM/SIGINT asks the
            # supervisor for a final atomic snapshot at the next window
            # barrier (exit 5); the handler restores the previous
            # disposition immediately, so a second signal kills a hung
            # run the ordinary way
            stop_flag = {"v": False}
            prev_handlers = {}

            def _on_signal(signum, frame):
                stop_flag["v"] = True
                signal.signal(signum, prev_handlers[signum])

            for _sg in (signal.SIGTERM, signal.SIGINT):
                try:
                    prev_handlers[_sg] = signal.signal(_sg, _on_signal)
                except ValueError:
                    pass  # not the main thread (embedded use)

            nshards = mesh.shape["hosts"] if mesh is not None else 1
            try:
                with (timers.phase("supervised-run") if timers is not None
                      else contextlib.nullcontext()):
                    result = run_supervised(
                        b, app_handlers=loaded.handlers,
                        checkpoint_path=ckpt_prefix,
                        checkpoint_every_windows=(
                            args.checkpoint_every_windows),
                        max_retries=args.max_retries,
                        backoff_s=args.retry_backoff,
                        stall_windows=args.stall_windows,
                        escalation=(EscalationPolicy(
                            max_grow=args.max_grow)
                            if args.auto_grow else None),
                        stop=lambda: stop_flag["v"],
                        resume_from=resume_ckpt,
                        max_run_wallclock=args.max_run_wallclock,
                        mesh=mesh,
                        config_digest=config_hash(b.cfg),
                        log=lambda m: logger.message(0, "shadow-tpu", m),
                        on_window=sup_hook, harvester=harvester,
                        feeder=feeder)
            finally:
                for _sg, _h in prev_handlers.items():
                    with contextlib.suppress(ValueError, TypeError):
                        signal.signal(_sg, _h)
            sup_result = result

            def _sup_manifest(sim_, health_, stats_=None):
                from shadow_tpu import telemetry

                harvester.drain(sim_)
                wpd = max(1, int(getattr(b.cfg, "windows_per_dispatch",
                                         1) or 1))
                disp = {"windows_per_dispatch": wpd,
                        "dispatches": result.dispatches}
                # the per-dispatch window list only equals the chain's
                # window total for a clean single-attempt run (retries
                # replay dispatches; resumes offset the counters) —
                # omit it otherwise so the lint invariant stays exact
                if (wpd > 1 and result.dispatch_windows
                        and result.attempts == 1
                        and result.resume_of is None):
                    disp["windows"] = list(result.dispatch_windows)
                if getattr(b.cfg, "adaptive_jump", False):
                    m = harvester.mean_window_ns()
                    if m is not None:
                        disp["adaptive_jump_mean_ns"] = m
                inj_blk = None
                if feeder is not None:
                    from shadow_tpu import inject as inject_mod

                    inj_blk = inject_mod.manifest_block(sim_, feeder)
                from shadow_tpu.telemetry.export import (
                    admission_manifest_block,
                    lanes_manifest_block,
                )
                from shadow_tpu.telemetry.flows import \
                    flows_manifest_block
                from shadow_tpu.telemetry.causality import \
                    causality_manifest_block

                caus_blk = causality_manifest_block(
                    harvester, num_hosts=b.cfg.num_hosts,
                    shards=nshards,
                    sample_period=args.causality_sample or None)
                man = telemetry.run_manifest(
                    cfg=b.cfg, seed=args.seed, shards=nshards,
                    sim=sim_, stats=stats_, health=health_,
                    fault_plan=b.fault_plan,
                    harvester=harvester, timers=timers,
                    run_id=result.run_id, resume_of=result.resume_of,
                    escalations=result.escalations,
                    preempted=result.preempted or None,
                    dispatch=disp, injection=inj_blk,
                    compile_info=result.compile_info,
                    lanes=lanes_manifest_block(
                        health_, result.lane_incidents),
                    flows=flows_manifest_block(
                        harvester, num_hosts=b.cfg.num_hosts,
                        shards=nshards,
                        sample_period=args.flow_sample or None),
                    admission=admission_manifest_block(health_),
                    profile=profile_info,
                    causality=caus_blk,
                    specialization=specialize.specialization_block(
                        getattr(b, "caps", None), sim_,
                        mode=args.specialize))
                os.makedirs(args.data_directory, exist_ok=True)
                telemetry.write_manifest(
                    os.path.join(args.data_directory,
                                 "run_manifest.json"), man)
                if args.trace_out:
                    telemetry.write_trace(
                        args.trace_out, harvester.records, timers,
                        nshards,
                        flow_records=harvester.flow_records,
                        adv_records=harvester.adv_records or None,
                        chains=(caus_blk or {}).get("chains"))
                if args.metrics_out:
                    telemetry.write_metrics(args.metrics_out, man)
                return man

            if result.preempted:
                # interrupted, not failed: the final snapshot is on
                # disk and `--resume <data-directory>` continues the
                # run (distinct exit code so wrappers can requeue)
                report = {
                    "preempted": True,
                    "checkpoint": result.final_checkpoint,
                    "run_id": result.run_id,
                    "escalations": len(result.escalations),
                    "resume": f"--resume {args.data_directory}",
                }
                if (telem_on or flows_on or caus_on) \
                        and result.sim is not None:
                    report["manifest"] = _sup_manifest(
                        result.sim, None, result.stats)
                logger.message(0, "shadow-tpu", "run preempted "
                               + json.dumps(report))
                logger.flush()
                print(json.dumps(report))
                return 5
            if not result.ok:
                failure = result.failure_report()
                # critical, not error: SimLogger.error raises (the
                # abort path); here we must keep control to emit the
                # structured report and choose the exit code.
                for _, msg in result.health.diagnostics():
                    logger.critical(0, "shadow-tpu", msg)
                report = {"failure": failure,
                          "attempts": result.attempts}
                if result.deadline_exceeded:
                    # not a corruption: the final snapshot is clean
                    # and --resume continues the chain
                    report["checkpoint"] = result.final_checkpoint
                    report["resume"] = f"--resume {args.data_directory}"
                # the trip carries the sim, so the shutdown
                # diagnostics the success path prints still run:
                # object accounting (ref: slave.c:237-241) and the
                # run manifest — a failed run is exactly when you
                # want them
                if result.sim is not None:
                    from shadow_tpu.utils import objcount

                    oc = objcount.gather(result.sim)
                    logger.message(0, "shadow-tpu", oc.format())
                    logger.message(0, "shadow-tpu", oc.format_diff())
                    if telem_on or flows_on or caus_on:
                        report["manifest"] = _sup_manifest(
                            result.sim, result.health)
                logger.flush()
                print(json.dumps(report))
                return 3
            sim, stats = result.sim, result.stats
        elif b.cfg.pcap:
            from shadow_tpu.utils import checkpoint as ckpt

            def pcap_hook(s, wend):
                cap.drain(s)
                if harvester is not None:
                    # the host already regains control every window
                    # here; draining per window keeps ring loss at zero
                    harvester.drain(s)
                progress_hook(s, wend)

            with (timers.phase("window-loop") if timers is not None
                  else contextlib.nullcontext()):
                sim, stats, _ = ckpt.run_windows(
                    b, app_handlers=loaded.handlers, on_window=pcap_hook,
                    feeder=feeder, compile_info=cinfo)
        elif mesh is not None:
            from shadow_tpu.parallel.shard import run_sharded

            if feeder is not None:
                # whole-run jitted path: the entire trace must fit the
                # staging lanes (fill_all errors with the streaming
                # alternative spelled out when it does not)
                b.sim = feeder.fill_all(b.sim)
            if timers is not None:
                with timers.phase("device-execute"):
                    sim, stats = run_sharded(
                        b, mesh, app_handlers=loaded.handlers,
                        app_bulk=b.app_bulk, compile_info=cinfo)
                    jax.block_until_ready(sim)
            else:
                sim, stats = run_sharded(
                    b, mesh, app_handlers=loaded.handlers,
                    app_bulk=b.app_bulk, compile_info=cinfo)
        else:
            if feeder is not None:
                b.sim = feeder.fill_all(b.sim)
            if timers is not None:
                # split trace+compile from device execution so the
                # wall-time trace track shows where a cold start went
                from shadow_tpu.net.build import make_runner

                runner = make_runner(b, app_handlers=loaded.handlers,
                                     app_bulk=b.app_bulk,
                                     compile_info=cinfo)
                with timers.phase("trace-compile"):
                    # a warm-serving runner (compile/serve.WarmFn)
                    # resolves load-or-compile here via its lower()
                    # adapter, so a store hit shows up as a short
                    # trace-compile phase
                    compiled = runner.lower(b.sim).compile()
                with timers.phase("device-execute"):
                    sim, stats = compiled(b.sim)
                    jax.block_until_ready(sim)
            else:
                from shadow_tpu.net.build import run

                sim, stats = run(b, app_handlers=loaded.handlers,
                                 app_bulk=b.app_bulk)
        _stop_profile()
        if cap is not None:
            cap.drain(sim)
            cap.close()
            if cap.dropped:
                logger.warning(b.cfg.end_time, "shadow-tpu",
                               f"pcap ring overran: {cap.dropped} records "
                               f"lost (raise NetConfig.pcap_ring)")
        wall = time.time() - t0

        # end-of-run heartbeat + object accounting (ref: the tracker
        # heartbeat subsystem, tracker.c:419-607, and the shutdown object
        # counter dump, slave.c:237-241)
        from shadow_tpu.utils import objcount
        from shadow_tpu.utils.tracker import Tracker

        tracker = Tracker(
            logger, b.host_names,
            interval_s=args.heartbeat_frequency,
            level=level_from_name(args.heartbeat_log_level),
            sections=tuple(
                x.strip() for x in args.heartbeat_log_info.split(",")
                if x.strip()))
        tracker.heartbeat(sim, b.cfg.end_time)
        oc = objcount.gather(sim, stats=stats)
        logger.message(b.cfg.end_time, "shadow-tpu", oc.format())
        logger.message(b.cfg.end_time, "shadow-tpu", oc.format_diff())

        # per-host executed-event lines (ref: the per-host execution
        # timer logged at shutdown, host.c:314-317) + per-path packet
        # counts (ref: topology.c:2053-2063), info level
        exec_h = np.asarray(sim.net.ctr_events_exec)
        for hi in np.argsort(-exec_h)[: min(len(exec_h), 10)]:
            if exec_h[hi] > 0:
                logger.info(b.cfg.end_time, b.host_names[hi],
                            f"executed {int(exec_h[hi])} events")
        if b.cfg.track_paths:
            mat = np.asarray(sim.net.ctr_path_packets)
            vs, vd = np.nonzero(mat)
            for a, c in zip(vs, vd):
                logger.message(
                    b.cfg.end_time, "shadow-tpu",
                    f"path {a}->{c}: {int(mat[a, c])} packets")

        # health-latch enforcement (faults/health.py): the sticky
        # overflow counters stop being silent integers — every run
        # ends with an explicit verdict, and a fatal latch means a
        # non-zero exit with a structured failure report instead of
        # corrupted-but-plausible results.
        from shadow_tpu.faults import health as health_mod

        if harvester is not None:
            with timers.phase("harvest"):
                harvester.drain(sim)
        run_health = health_mod.gather(
            sim,
            telemetry_lost=(harvester.records_lost
                            + getattr(harvester, "flow_lost", 0)
                            if harvester is not None else 0))
        # critical, not error: SimLogger.error raises, and the fatal
        # path below must still print the structured report + exit 3.
        for sev, msg in run_health.diagnostics():
            if sev == "fatal":
                logger.critical(b.cfg.end_time, "shadow-tpu", msg)
            else:
                logger.warning(b.cfg.end_time, "shadow-tpu", msg)

        ev = int(stats.events_processed)
        sim_s = b.cfg.end_time / 1e9
        report = {
            "events": ev,
            "windows": int(stats.windows),
            "sim_seconds": round(sim_s, 3),
            # verification hook (ref: the reference's example config
            # downloads are verified by their sizes): the app's own rcvd
            # units — bytes for bulk, replies for pingpong
            **({"app_rcvd": int(np.asarray(sim.app.rcvd).sum())}
               if getattr(sim, "app", None) is not None
               and hasattr(sim.app, "rcvd") else {}),
            "wall_seconds": round(wall, 3),
            "events_per_second": round(ev / wall, 1) if wall > 0 else None,
            "simulated_seconds_per_wall_second":
                round(sim_s / wall, 3) if wall > 0 else None,
            "overflow": int(sim.events.overflow) + int(sim.outbox.overflow)
            + int(sim.net.rq_overflow),
        }
        inj_blk = None
        if feeder is not None:
            from shadow_tpu import inject as inject_mod

            inj_blk = inject_mod.manifest_block(sim, feeder)
            if inj_blk is not None:
                report["injection"] = inj_blk
        if sup_result is not None:
            if sup_result.escalations:
                report["escalations"] = [
                    e.as_dict() for e in sup_result.escalations]
            if sup_result.resume_of:
                report["resume_of"] = sup_result.resume_of
        if telem_on or flows_on or caus_on:
            from shadow_tpu import telemetry

            nshards = mesh.shape["hosts"] if mesh is not None else 1
            with timers.phase("export"):
                disp = None
                if sup_result is not None:
                    wpd = max(1, int(getattr(
                        b.cfg, "windows_per_dispatch", 1) or 1))
                    disp = {"windows_per_dispatch": wpd,
                            "dispatches": sup_result.dispatches}
                    # only a clean single-attempt run's per-dispatch
                    # list sums to the chain's window counter — see
                    # _sup_manifest
                    if (wpd > 1 and sup_result.dispatch_windows
                            and sup_result.attempts == 1
                            and sup_result.resume_of is None):
                        disp["windows"] = list(
                            sup_result.dispatch_windows)
                    if (getattr(b.cfg, "adaptive_jump", False)
                            and harvester is not None):
                        m = harvester.mean_window_ns()
                        if m is not None:
                            disp["adaptive_jump_mean_ns"] = m
                from shadow_tpu.telemetry.export import (
                    admission_manifest_block,
                    lanes_manifest_block,
                )
                from shadow_tpu.telemetry.flows import \
                    flows_manifest_block
                from shadow_tpu.telemetry.causality import \
                    causality_manifest_block

                caus_blk = causality_manifest_block(
                    harvester, num_hosts=b.cfg.num_hosts,
                    shards=nshards,
                    sample_period=args.causality_sample or None)
                man = telemetry.run_manifest(
                    cfg=b.cfg, seed=args.seed, shards=nshards, sim=sim,
                    stats=stats, health=run_health,
                    fault_plan=b.fault_plan, harvester=harvester,
                    timers=timers, wall_seconds=wall,
                    injection=inj_blk,
                    compile_info=(sup_result.compile_info
                                  if sup_result is not None
                                  else (cinfo or None)),
                    lanes=lanes_manifest_block(
                        run_health,
                        sup_result.lane_incidents
                        if sup_result is not None else ()),
                    flows=flows_manifest_block(
                        harvester, num_hosts=b.cfg.num_hosts,
                        shards=nshards,
                        sample_period=args.flow_sample or None),
                    admission=admission_manifest_block(run_health),
                    profile=profile_info,
                    causality=caus_blk,
                    specialization=specialize.specialization_block(
                        b.caps, sim, mode=args.specialize),
                    **({} if sup_result is None else {
                        "run_id": sup_result.run_id,
                        "resume_of": sup_result.resume_of,
                        "escalations": sup_result.escalations,
                        "dispatch": disp}))
                os.makedirs(args.data_directory, exist_ok=True)
                mpath = telemetry.write_manifest(
                    os.path.join(args.data_directory,
                                 "run_manifest.json"), man)
                logger.message(b.cfg.end_time, "shadow-tpu",
                               f"run manifest -> {mpath}")
                if args.trace_out:
                    telemetry.write_trace(
                        args.trace_out, harvester.records, timers,
                        nshards,
                        flow_records=harvester.flow_records,
                        adv_records=harvester.adv_records or None,
                        chains=(caus_blk or {}).get("chains"))
                    logger.message(b.cfg.end_time, "shadow-tpu",
                                   f"trace -> {args.trace_out} (load in "
                                   f"chrome://tracing or ui.perfetto.dev)")
                if args.metrics_out:
                    telemetry.write_metrics(args.metrics_out, man)
            report["telemetry"] = man["telemetry"]
        if run_health.fatal:
            report["failure"] = run_health.failure_report()
            logger.critical(b.cfg.end_time, "shadow-tpu",
                            "simulation FAILED " + json.dumps(report))
            logger.flush()
            print(json.dumps(report))
            return 3
        logger.message(b.cfg.end_time, "shadow-tpu", "simulation complete "
                       + json.dumps(report))
        logger.flush()
        print(json.dumps(report))
        return 0
    finally:
        _stop_profile()
        logger.flush()


if __name__ == "__main__":
    sys.exit(main())
