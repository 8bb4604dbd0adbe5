#!/usr/bin/env python3
"""Offline telemetry-output validator — CI gate for the trace /
manifest files the CLI and bench emit, so a malformed export is caught
by the test suite instead of by a blank Perfetto tab.

Checks:

- Trace JSON (--trace): Chrome Trace Event Format schema — top-level
  {"traceEvents": [...]}; every event carries "ph"; "X" (complete)
  events carry numeric ts/dur with dur > 0 and int pid/tid; "C"
  (counter) events carry a name, numeric ts and a non-empty args
  series; "M" (metadata) events carry the known metadata names;
  window events' args hold the per-window counters with sane values
  (events >= 0, qocc_min <= qocc_max); sim-time windows are sorted by
  ts and non-overlapping (warns otherwise — a ring overrun leaves
  gaps, which are legal).
- Manifest JSON (--manifest): required identity keys present
  (config_hash, seed, shards, counters); the telemetry block's
  records_lost is SURFACED — a nonzero loss count without a matching
  health warning in the manifest is an error (silent observability
  loss is exactly what the latch design forbids). The optional
  "dispatch" block (chunked window loop) must be internally coherent:
  windows_per_dispatch >= 1, every per-dispatch window count fits the
  chunk, and the counts sum to counters.windows when both are present.
  The optional "injection" block (open-system traffic) must reconcile
  (injected + dropped + deferred == trace_events), its drops must be
  latched in health, and the per-window injected plane must sum to
  the device latch when no telemetry records were lost.
  The optional "lanes" block (lane-isolated packed runs) must carry
  one per_lane entry per replica whose overflow shares sum to the
  run-total latch counters exactly, and every quarantined lane must
  name its trips and (when the supervisor's lane surgery ran) its
  salvage pointer + requeue context.
  The optional "causality" block (causal critical-path profiling)
  must conserve its sampling accounting (harvested + lost_ring <=
  sampled <= emitted), its binding-cause counts must cover the
  attributed windows exactly, its chains must be time-contiguous with
  same-host depth strictly increasing, and its traffic matrix must
  agree with the flow recorder's on a lossless equal-period run.

- Fleet manifest JSON (--fleet-manifest): shadow_tpu/fleet schema —
  attempt histories monotone non-decreasing with attempts at the
  high-water mark, every terminal job carries the matching verdict,
  every quarantined job carries its salvage pointers, the counts
  block agrees with the per-job statuses, and packed jobs' lane
  requeues are replicas=1 children back-linked via lane_of.

Usage: telemetry_lint.py [--trace trace.json]
                         [--manifest run_manifest.json]
                         [--fleet-manifest fleet_manifest.json]
Exit 0 = clean (warnings allowed), 1 = errors.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# metadata record names Chrome/Perfetto understand (trace event format
# spec §Metadata Events) — anything else is silently ignored by the
# viewers, which usually means a typo here
KNOWN_METADATA = {
    "process_name", "process_labels", "process_sort_index",
    "thread_name", "thread_sort_index",
}
WINDOW_ARGS = ("events", "micro_steps", "routed_local", "routed_cross",
               "drops", "retx", "active_lanes", "fastpath")

# canonical id formats (shadow_tpu/compile/buckets.py program_key,
# shadow_tpu/fleet/affinity.py affinity_key) — validated by regex so
# the lint stays importable without the engine's jax dependency
_PROGRAM_KEY = re.compile(r"^pk[0-9a-f]{16}$")
_AFFINITY_KEY = re.compile(r"^ak[0-9a-f]{16}$")

# the resident-admission degradation ladder, in order
# (fleet/admission.py LADDER) — duplicated literally so the lint stays
# importable without the engine
_LEASE_LADDER = ("nominal", "stride", "defer", "evict", "quarantine")


def _lint_compile_block(comp, where: str) -> tuple[list, list]:
    """(errors, warnings) for one program-store accounting block
    (compile/serve.py WarmFn info; nested once under "warmup" for the
    bench's fresh-vs-cached pairing)."""
    errors: list = []
    warnings: list = []
    if not isinstance(comp, dict):
        return ([f"{where} must be an object"], [])
    key = comp.get("key")
    if key is not None and (not isinstance(key, str)
                            or not _PROGRAM_KEY.match(key)):
        errors.append(f'{where}.key must match "pk" + 16 hex chars '
                      f"(compile/buckets.py program_key), got {key!r}")
    for k in ("warm", "hit", "stored"):
        v = comp.get(k)
        if v is not None and not isinstance(v, bool):
            errors.append(f"{where}.{k} must be a bool, got {v!r}")
    for k in ("load_s", "lower_s", "compile_s", "warm_speedup"):
        v = comp.get(k)
        if v is not None and (not isinstance(v, (int, float))
                              or isinstance(v, bool) or v < 0):
            errors.append(f"{where}.{k} must be a non-negative "
                          f"number, got {v!r}")
    fb = comp.get("fallback")
    if fb is not None and (not isinstance(fb, str) or not fb):
        errors.append(f"{where}.fallback must be a non-empty string")
    # hit/miss consistency: a hit is a store load (load_s, no compile
    # timings); a clean miss compiled fresh (lower_s/compile_s, no
    # load_s); a fallback may carry neither
    hit = comp.get("hit")
    if hit is True:
        if comp.get("load_s") is None:
            errors.append(f"{where}: hit=true must record load_s "
                          f"(the warm load IS the claimed saving)")
        for k in ("lower_s", "compile_s"):
            if comp.get(k) is not None:
                errors.append(f"{where}: hit=true cannot also carry "
                              f"{k} — a warm serve never compiled")
    elif hit is False and comp.get("warm") and fb is None:
        if comp.get("compile_s") is None:
            errors.append(f"{where}: a warm-serving miss must record "
                          f"its fresh compile_s")
        if comp.get("load_s") is not None:
            errors.append(f"{where}: hit=false cannot carry load_s")
    if hit is True and key is None:
        errors.append(f"{where}: hit=true without a program key")
    # bucket plan: every quantized knob's bucket must be a power of
    # two (or 0 = knob off) and must never shrink the request
    bk = comp.get("buckets")
    if bk is not None:
        if not isinstance(bk, dict):
            errors.append(f"{where}.buckets must be an object")
            bk = {}
        for knob, ent in sorted(bk.items()):
            w2 = f"{where}.buckets.{knob}"
            if not isinstance(ent, dict):
                errors.append(f"{w2} must be an object with "
                              f"requested/bucketed")
                continue
            req, got = ent.get("requested"), ent.get("bucketed")
            for k, v in (("requested", req), ("bucketed", got)):
                if (not isinstance(v, int) or isinstance(v, bool)
                        or v < 0):
                    errors.append(f"{w2}.{k} must be a non-negative "
                                  f"integer, got {v!r}")
            if isinstance(req, int) and isinstance(got, int) \
                    and not isinstance(req, bool) \
                    and not isinstance(got, bool):
                if got < req:
                    errors.append(f"{w2}: bucketed={got} < requested="
                                  f"{req} — quantization only pads, "
                                  f"never shrinks")
                if got and got & (got - 1):
                    errors.append(f"{w2}: bucketed={got} is not a "
                                  f"power of two")
    return errors, warnings


_SPEC_TRIMMABLE = ("loss", "timers")


def _lint_specialization(spec, ctr, health) -> tuple[list, list]:
    """(errors, warnings) for a manifest's "specialization" block
    (compile/specialize.py specialization_block). The invariants are
    the safety contract of capability trimming: the dropped list must
    be the trimmable subset of the capability vector's False flags,
    the program-key extra must be derived from exactly that list, a
    dropped loss capability means the reliability drop counter was
    structurally never written (so it is exactly zero), and a tripped
    guard latch is a FATAL health verdict — never a silent integer."""
    errors: list = []
    warnings: list = []
    w = "specialization"
    if not isinstance(spec, dict):
        return ([f"{w} must be an object"], [])
    mode = spec.get("mode")
    if mode != "auto":
        errors.append(f'{w}.mode must be "auto" (a --specialize off '
                      f"run writes no block), got {mode!r}")
    caps = spec.get("capabilities")
    if not isinstance(caps, dict):
        errors.append(f"{w}.capabilities must be an object")
        caps = {}
    for k, v in sorted(caps.items()):
        if not isinstance(v, bool):
            errors.append(f"{w}.capabilities.{k} must be a bool, "
                          f"got {v!r}")
    dropped = spec.get("dropped")
    if not isinstance(dropped, list):
        errors.append(f"{w}.dropped must be a list")
        dropped = []
    for n in dropped:
        if n not in _SPEC_TRIMMABLE:
            errors.append(f"{w}.dropped contains {n!r} — only "
                          f"{list(_SPEC_TRIMMABLE)} are trimmable")
        elif caps.get(n) is not False:
            errors.append(
                f"{w}: {n!r} is dropped but capabilities.{n} is "
                f"{caps.get(n)!r} — a dropped capability must be "
                f"recorded dead in the vector")
    for n in _SPEC_TRIMMABLE:
        if caps.get(n) is False and n not in dropped:
            errors.append(
                f"{w}: capabilities.{n}=false but {n!r} is not in "
                f"dropped — a dead trimmable capability is always "
                f"trimmed")
    want_extra = "-".join(
        "no_" + n for n in sorted(x for x in dropped
                                  if x in _SPEC_TRIMMABLE)) or None
    if spec.get("key_extra") != want_extra:
        errors.append(
            f"{w}.key_extra={spec.get('key_extra')!r} does not match "
            f"the dropped list (expected {want_extra!r}) — the store "
            f"key and the manifest must name the same variant")
    # guard latch: one watch per dropped capability, counters are
    # non-negative, and a nonzero counter MUST coincide with a fatal
    # health verdict (the whole point of the latch)
    g = spec.get("guard")
    tripped = 0
    if g is not None:
        if not isinstance(g, dict):
            errors.append(f"{w}.guard must be an object")
            g = {}
        watched = g.get("watched")
        if isinstance(watched, list) and sorted(watched) != \
                sorted(x for x in dropped if x in _SPEC_TRIMMABLE):
            errors.append(
                f"{w}.guard.watched={watched} must equal the dropped "
                f"list {sorted(dropped)} — every trimmed capability "
                f"is watched, nothing else is")
        for k in ("loss_trips", "timer_trips"):
            v = g.get(k)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                errors.append(f"{w}.guard.{k} must be a non-negative "
                              f"integer, got {v!r}")
            else:
                tripped += v
        if tripped:
            hg = (health or {}).get("guard", {}) \
                if isinstance(health, dict) else {}
            surfaced = bool(hg.get("tripped")) or any(
                "specialization guard tripped" in d
                for d in (health or {}).get("diagnostics", [])
                if isinstance(d, str))
            if not surfaced:
                errors.append(
                    f"{w}.guard counters are nonzero "
                    f"(loss={g.get('loss_trips')}, "
                    f"timer={g.get('timer_trips')}) but the health "
                    f"block does not report the trip as fatal — a "
                    f"violated trim assumption must fail the run, "
                    f"never degrade it silently")
            else:
                warnings.append(
                    f"{w}: guard latch tripped {tripped} window(s) — "
                    f"the run was (correctly) reported fatal; rerun "
                    f"with --specialize off")
    elif dropped:
        warnings.append(
            f"{w}: dropped={dropped} but no guard block — the final "
            f"sim was not available to the manifest writer")
    if "loss" in dropped and not tripped:
        dr = (ctr or {}).get("drops_reliability_total")
        if dr is not None and dr != 0:
            errors.append(
                f"counters.drops_reliability_total={dr} but the loss "
                f"capability was trimmed — the trimmed program cannot "
                f"write that counter; the manifest is lying about "
                f"which program ran")
    return errors, warnings


_FLOW_HIST_KEY = re.compile(r"^lane\d+/\d+->\d+/k-?\d+$")


def _lint_flows(fl, ctr, tel) -> tuple[list, list]:
    """(errors, warnings) for a manifest's "flows" block
    (telemetry/flows.py flows_manifest_block). The invariants are the
    flow ring's accounting identities: the device splits every sampled
    packet into appended-or-clamped (recorded + lost_window_clamp ==
    sampled), the harvester splits every recorded slot into
    pulled-or-overrun (harvested + lost_ring <= recorded; < only
    after a checkpoint rewind discarded replayed records), and every
    harvested record lands in exactly one histogram key, one lane,
    and one traffic-matrix cell."""
    errors: list = []
    warnings: list = []
    if not isinstance(fl, dict):
        return (["flows must be an object"], [])
    for k in ("sample_period", "path_shards"):
        v = fl.get(k)
        if not isinstance(v, int) or isinstance(v, bool) or v < 1:
            errors.append(f"flows.{k} must be an integer >= 1, "
                          f"got {v!r}")
    counts = {}
    for k in ("sampled", "recorded", "harvested", "lost_ring",
              "lost_window_clamp"):
        v = fl.get(k)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            errors.append(f"flows.{k} must be a non-negative integer, "
                          f"got {v!r}")
        else:
            counts[k] = v
    if len(counts) == 5:
        if counts["recorded"] + counts["lost_window_clamp"] \
                != counts["sampled"]:
            errors.append(
                f"flows accounting broken: recorded="
                f"{counts['recorded']} + lost_window_clamp="
                f"{counts['lost_window_clamp']} != sampled="
                f"{counts['sampled']} — the device splits every "
                f"sampled packet into appended or clamped, never "
                f"drops one silently")
        if counts["harvested"] + counts["lost_ring"] \
                > counts["recorded"]:
            errors.append(
                f"flows: harvested={counts['harvested']} + lost_ring="
                f"{counts['lost_ring']} exceeds recorded="
                f"{counts['recorded']}")
        if counts["lost_ring"]:
            warnings.append(
                f"{counts['lost_ring']} flow record(s) lost to ring "
                f"overrun (raise --flow-capacity or drain more often)")
        if counts["lost_window_clamp"]:
            warnings.append(
                f"{counts['lost_window_clamp']} sampled flow(s) "
                f"clamped on device (one window sampled more than the "
                f"ring holds; raise --flow-capacity or the sample "
                f"period)")
    ev = (ctr or {}).get("events_processed")
    if isinstance(ev, int) and not isinstance(ev, bool) \
            and isinstance(fl.get("sampled"), int) \
            and fl.get("sample_period") == 1 and fl["sampled"] > ev:
        # at 1-in-1 sampling every cross-host send is sampled, and a
        # send needs an executed event behind it; coarser periods make
        # the bound vacuous, so only the exhaustive case is checked
        errors.append(
            f"flows.sampled={fl['sampled']} exceeds "
            f"counters.events_processed={ev} at sample_period=1 — "
            f"more packets sampled than events executed")
    if isinstance(tel, dict) and tel.get("flows_sampled") is not None:
        for mk, fk in (("flows_sampled", "sampled"),
                       ("flows_harvested", "harvested"),
                       ("flows_lost_ring", "lost_ring"),
                       ("flows_lost_window_clamp", "lost_window_clamp")):
            if (isinstance(tel.get(mk), int)
                    and isinstance(fl.get(fk), int)
                    and tel[mk] != fl[fk]):
                errors.append(
                    f"telemetry.{mk}={tel[mk]} disagrees with "
                    f"flows.{fk}={fl[fk]} — one harvester fills both "
                    f"blocks, they cannot diverge")
    harvested = fl.get("harvested")
    hist = fl.get("histograms")
    hist_total = 0
    if hist is not None:
        if not isinstance(hist, dict):
            errors.append("flows.histograms must be an object")
            hist = {}
        for key in sorted(hist):
            where = f"flows.histograms[{key}]"
            if not _FLOW_HIST_KEY.match(key):
                errors.append(
                    f'{where}: key must look like '
                    f'"lane<r>/<src_shard>-><dst_shard>/k<kind>"')
            h = hist[key]
            if not isinstance(h, dict):
                errors.append(f"{where}: must be an object")
                continue
            c = h.get("count")
            if not isinstance(c, int) or isinstance(c, bool) or c < 1:
                errors.append(f"{where}: count must be an integer "
                              f">= 1 (empty keys are omitted)")
                c = 0
            hist_total += c
            pcts = [h.get(k) for k in ("p50_ns", "p95_ns", "p99_ns")]
            for k, v in zip(("p50_ns", "p95_ns", "p99_ns"), pcts):
                if (not isinstance(v, int) or isinstance(v, bool)
                        or v < 0):
                    errors.append(f"{where}: {k} must be a "
                                  f"non-negative integer, got {v!r}")
            if all(isinstance(v, int) and not isinstance(v, bool)
                   for v in pcts) and not (pcts[0] <= pcts[1]
                                           <= pcts[2]):
                errors.append(f"{where}: percentiles must be "
                              f"monotone (p50 <= p95 <= p99), "
                              f"got {pcts}")
            bk = h.get("buckets")
            if not isinstance(bk, dict) or not bk:
                errors.append(f"{where}: buckets must be a non-empty "
                              f"object")
                continue
            los, bsum, ok = [], 0, True
            for lo, n in bk.items():
                try:
                    lov = int(lo)
                except (TypeError, ValueError):
                    errors.append(f"{where}: bucket key {lo!r} is not "
                                  f"an integer lower bound")
                    ok = False
                    continue
                if lov != 0 and (lov < 0 or lov & (lov - 1)):
                    errors.append(f"{where}: bucket lower bound {lov} "
                                  f"is neither 0 nor a power of two "
                                  f"(log2 latency buckets)")
                if (not isinstance(n, int) or isinstance(n, bool)
                        or n < 1):
                    errors.append(f"{where}: bucket[{lo}] count must "
                                  f"be an integer >= 1")
                    ok = False
                else:
                    los.append(lov)
                    bsum += n
            if los != sorted(los):
                errors.append(f"{where}: bucket bounds must be "
                              f"ascending, got {los}")
            if ok and isinstance(c, int) and c and bsum != c:
                errors.append(f"{where}: buckets sum to {bsum} but "
                              f"count={c}")
        if isinstance(harvested, int) and hist \
                and hist_total != harvested:
            errors.append(
                f"flows.histograms cover {hist_total} record(s) but "
                f"harvested={harvested} — every harvested record "
                f"lands in exactly one (lane, path, kind) key")
    per_lane = fl.get("per_lane")
    if per_lane is not None:
        if not isinstance(per_lane, dict):
            errors.append("flows.per_lane must be an object")
            per_lane = {}
        lane_total = 0
        for lane in sorted(per_lane):
            where = f"flows.per_lane[{lane}]"
            try:
                int(lane)
            except (TypeError, ValueError):
                errors.append(f"{where}: lane key must be an integer")
            d = per_lane[lane]
            if not isinstance(d, dict) or not isinstance(
                    d.get("count"), int):
                errors.append(f"{where}: must carry an integer count")
                continue
            lane_total += d["count"]
        if isinstance(harvested, int) and per_lane \
                and lane_total != harvested:
            errors.append(
                f"flows.per_lane counts sum to {lane_total} but "
                f"harvested={harvested} — every record has exactly "
                f"one lane")
    tm = fl.get("traffic_matrix")
    if tm is not None:
        S = fl.get("path_shards")
        if not isinstance(tm, list) or (
                isinstance(S, int) and len(tm) != S) or not all(
                isinstance(row, list)
                and (not isinstance(S, int) or len(row) == S)
                and all(isinstance(c, int) and not isinstance(c, bool)
                        and c >= 0 for c in row)
                for row in tm):
            errors.append(f"flows.traffic_matrix must be a "
                          f"path_shards x path_shards grid of "
                          f"non-negative integers")
        elif isinstance(harvested, int) and sum(
                c for row in tm for c in row) != harvested:
            errors.append(
                f"flows.traffic_matrix sums to "
                f"{sum(c for row in tm for c in row)} but harvested="
                f"{harvested} — every record crosses exactly one "
                f"(src_shard, dst_shard) cell")
    return errors, warnings


# binding-cause names (telemetry/causality.py CAUSE_NAMES) —
# duplicated literally so the lint stays importable without jax
_CAUSE_NAMES = {"min_jump_floor", "adaptive_edge", "fault_record",
                "inject_horizon", "end_time"}
_BINDING_EDGE_KEY = re.compile(r"^v\d+->v\d+$")


def _lint_causality(cz, tel, flows) -> tuple[list, list]:
    """(errors, warnings) for a manifest's "causality" block
    (telemetry/causality.py causality_manifest_block). The invariants:
    every sampled emission was appended to its per-host sub-ring, so
    the harvester splits sampled into pulled-or-overrun (harvested +
    lost_ring <= sampled; < only after a checkpoint rewind discarded
    replayed records); the device kept at most what it saw (sampled <=
    emitted); every attributed window carries exactly one binding
    cause (cause counts sum to windows_attributed); chains are
    time-ordered with same-host depth strictly increasing; and the
    lineage traffic matrix agrees with the flow recorder's when both
    ran lossless at the same sampling period."""
    errors: list = []
    warnings: list = []
    if not isinstance(cz, dict):
        return (["causality must be an object"], [])
    for k in ("sample_period", "path_shards"):
        v = cz.get(k)
        if not isinstance(v, int) or isinstance(v, bool) or v < 1:
            errors.append(f"causality.{k} must be an integer >= 1, "
                          f"got {v!r}")
    counts = {}
    for k in ("sampled", "emitted", "harvested", "lost_ring",
              "cross_host_harvested", "windows_attributed",
              "windows_lost"):
        v = cz.get(k)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            errors.append(f"causality.{k} must be a non-negative "
                          f"integer, got {v!r}")
        else:
            counts[k] = v
    if len(counts) == 7:
        if counts["sampled"] > counts["emitted"]:
            errors.append(
                f"causality: sampled={counts['sampled']} exceeds "
                f"emitted={counts['emitted']} — the recorder cannot "
                f"keep more emissions than it observed")
        if counts["harvested"] + counts["lost_ring"] \
                > counts["sampled"]:
            errors.append(
                f"causality: harvested={counts['harvested']} + "
                f"lost_ring={counts['lost_ring']} exceeds sampled="
                f"{counts['sampled']} — every sampled emission is "
                f"appended exactly once")
        if counts["cross_host_harvested"] > counts["harvested"]:
            errors.append(
                f"causality: cross_host_harvested="
                f"{counts['cross_host_harvested']} exceeds harvested="
                f"{counts['harvested']}")
        if counts["lost_ring"]:
            warnings.append(
                f"{counts['lost_ring']} lineage record(s) lost to "
                f"ring overrun (raise --causality-capacity or the "
                f"sample period) — chains may be truncated")
        if counts["windows_lost"]:
            warnings.append(
                f"{counts['windows_lost']} window attribution(s) "
                f"lost to advance-ring overrun")
    if isinstance(tel, dict) \
            and tel.get("causality_sampled") is not None:
        for mk, ck in (("causality_sampled", "sampled"),
                       ("causality_harvested", "harvested"),
                       ("causality_lost_ring", "lost_ring"),
                       ("causality_windows_attributed",
                        "windows_attributed")):
            if (isinstance(tel.get(mk), int)
                    and isinstance(cz.get(ck), int)
                    and tel[mk] != cz[ck]):
                errors.append(
                    f"telemetry.{mk}={tel[mk]} disagrees with "
                    f"causality.{ck}={cz[ck]} — one harvester fills "
                    f"both blocks, they cannot diverge")
    # binding-cause histogram: every attributed window has exactly one
    # cause, so the counts must cover windows_attributed exactly
    causes = cz.get("causes")
    cause_total = 0
    if causes is not None:
        if not isinstance(causes, dict):
            errors.append("causality.causes must be an object")
            causes = {}
        for name, n in sorted(causes.items()):
            if name not in _CAUSE_NAMES:
                errors.append(f"causality.causes[{name!r}]: unknown "
                              f"binding cause (expected one of "
                              f"{sorted(_CAUSE_NAMES)})")
            if not isinstance(n, int) or isinstance(n, bool) or n < 1:
                errors.append(f"causality.causes[{name!r}] must be an "
                              f"integer >= 1 (empty causes are "
                              f"omitted)")
            else:
                cause_total += n
        if isinstance(cz.get("windows_attributed"), int) \
                and cause_total != cz["windows_attributed"]:
            errors.append(
                f"causality.causes cover {cause_total} window(s) but "
                f"windows_attributed={cz['windows_attributed']} — "
                f"every attributed window has exactly one binding "
                f"cause")
    edges = cz.get("edges")
    edge_total = 0
    if edges is not None:
        if not isinstance(edges, dict):
            errors.append("causality.edges must be an object")
            edges = {}
        for key, n in sorted(edges.items()):
            if not _BINDING_EDGE_KEY.match(key):
                errors.append(f'causality.edges key {key!r} must look '
                              f'like "v<a>->v<b>"')
            if not isinstance(n, int) or isinstance(n, bool) or n < 1:
                errors.append(f"causality.edges[{key!r}] must be an "
                              f"integer >= 1")
            else:
                edge_total += n
        adaptive = (causes or {}).get("adaptive_edge", 0)
        if isinstance(adaptive, int) and edge_total > adaptive:
            errors.append(
                f"causality.edges cover {edge_total} window(s) but "
                f"only {adaptive} window(s) were adaptive-edge bound "
                f"— a binding edge exists only where the live table "
                f"was the constraint")
    # per-window advance records: one per attributed window, each
    # jump within its unclamped lookahead
    advances = cz.get("advances")
    if advances is not None:
        if not isinstance(advances, list):
            errors.append("causality.advances must be an array")
            advances = []
        if isinstance(cz.get("windows_attributed"), int) \
                and len(advances) != cz["windows_attributed"]:
            errors.append(
                f"causality.advances holds {len(advances)} record(s) "
                f"but windows_attributed={cz['windows_attributed']}")
        for i, a in enumerate(advances):
            where = f"causality.advances[{i}]"
            if not isinstance(a, dict):
                errors.append(f"{where}: must be an object")
                continue
            if a.get("cause") not in _CAUSE_NAMES:
                errors.append(f"{where}: unknown cause "
                              f"{a.get('cause')!r}")
            for k in ("jump", "raw"):
                v = a.get(k)
                if not isinstance(v, int) or isinstance(v, bool) \
                        or v < 0:
                    errors.append(f"{where}: {k} must be a "
                                  f"non-negative integer, got {v!r}")
            if isinstance(a.get("jump"), int) \
                    and isinstance(a.get("raw"), int) \
                    and a["raw"] > 0 and a["jump"] > a["raw"]:
                errors.append(
                    f"{where}: jump={a['jump']} exceeds the unclamped "
                    f"lookahead raw={a['raw']} — clamps only shrink "
                    f"windows")
            u = a.get("utilization_pct")
            if u is not None and (not isinstance(u, int)
                                  or isinstance(u, bool)
                                  or not 0 <= u <= 100):
                errors.append(f"{where}: utilization_pct must be an "
                              f"integer in [0, 100], got {u!r}")
    # critical chains: root-first, time-contiguous joins (child t_emit
    # == parent t_due), same-host depth strictly increasing
    for ci, ch in enumerate(cz.get("chains") or []):
        where = f"causality.chains[{ci}]"
        if not isinstance(ch, dict):
            errors.append(f"{where}: must be an object")
            continue
        ln = ch.get("length")
        if not isinstance(ln, int) or isinstance(ln, bool) or ln < 1:
            errors.append(f"{where}: length must be an integer >= 1")
            continue
        span = ch.get("span_ns")
        if not isinstance(span, int) or isinstance(span, bool) \
                or span < 0:
            errors.append(f"{where}: span_ns must be a non-negative "
                          f"integer, got {span!r}")
        ph = ch.get("per_host") or {}
        if isinstance(ph, dict) and ph \
                and sum(ph.values()) != ln:
            errors.append(f"{where}: per_host counts sum to "
                          f"{sum(ph.values())} but length={ln}")
        pk = ch.get("per_kind") or {}
        if isinstance(pk, dict) and pk \
                and sum(pk.values()) != ln:
            errors.append(f"{where}: per_kind counts sum to "
                          f"{sum(pk.values())} but length={ln}")
        evs = ch.get("events") or []
        if not isinstance(evs, list) or len(evs) > ln:
            errors.append(f"{where}: events must be an array of at "
                          f"most length={ln} records (tail-truncated)")
            continue
        depth_of: dict = {}
        for ei, ev in enumerate(evs):
            w2 = f"{where}.events[{ei}]"
            if not isinstance(ev, dict):
                errors.append(f"{w2}: must be an object")
                continue
            if isinstance(ev.get("t_emit"), int) \
                    and isinstance(ev.get("t_due"), int) \
                    and ev["t_due"] < ev["t_emit"]:
                errors.append(f"{w2}: t_due={ev['t_due']} precedes "
                              f"t_emit={ev['t_emit']} — an event "
                              f"cannot be due before it was emitted")
            if ei > 0 and isinstance(evs[ei - 1], dict):
                prev = evs[ei - 1]
                if isinstance(prev.get("t_due"), int) \
                        and isinstance(ev.get("t_emit"), int) \
                        and ev["t_emit"] != prev["t_due"]:
                    errors.append(
                        f"{w2}: t_emit={ev['t_emit']} breaks the join "
                        f"(parent t_due={prev['t_due']}) — a chain "
                        f"edge requires the child to be emitted at "
                        f"its parent's execution time")
            h = ev.get("host")
            d = ev.get("depth")
            if isinstance(h, int) and isinstance(d, int):
                if h in depth_of and d <= depth_of[h]:
                    errors.append(
                        f"{w2}: depth={d} not strictly greater than "
                        f"the previous depth {depth_of[h]} on host "
                        f"{h} — per-host execution order is total, "
                        f"so same-host chain depth must increase")
                depth_of[h] = d
    # lineage traffic matrix: the cross-host cell sums must cover the
    # cross-host harvested records exactly
    tm = cz.get("traffic_matrix")
    if tm is not None:
        S = cz.get("path_shards")
        if not isinstance(tm, list) or (
                isinstance(S, int) and len(tm) != S) or not all(
                isinstance(row, list)
                and (not isinstance(S, int) or len(row) == S)
                and all(isinstance(c, int) and not isinstance(c, bool)
                        and c >= 0 for c in row)
                for row in tm):
            errors.append("causality.traffic_matrix must be a "
                          "path_shards x path_shards grid of "
                          "non-negative integers")
        elif isinstance(counts.get("cross_host_harvested"), int) \
                and sum(c for row in tm for c in row) \
                != counts["cross_host_harvested"]:
            errors.append(
                f"causality.traffic_matrix sums to "
                f"{sum(c for row in tm for c in row)} but "
                f"cross_host_harvested="
                f"{counts['cross_host_harvested']}")
        # cross-check against the flow recorder (PR 15): both samplers
        # hash the same (time, dst, src, seq) identity, so two
        # LOSSLESS recorders at the SAME period must agree on the
        # cross-shard traffic matrix (warning: bulk-pass emissions
        # bypass the lineage hook, so a bulk-heavy run can diverge
        # legitimately)
        if (isinstance(flows, dict)
                and flows.get("sample_period") == cz.get("sample_period")
                and flows.get("path_shards") == cz.get("path_shards")
                and flows.get("lost_ring") == 0
                and flows.get("lost_window_clamp") == 0
                and cz.get("lost_ring") == 0
                and isinstance(flows.get("traffic_matrix"), list)
                and flows["traffic_matrix"] != tm):
            warnings.append(
                "causality.traffic_matrix disagrees with "
                "flows.traffic_matrix on a lossless run at equal "
                "sample periods — expected only when bulk-pass "
                "events (which bypass the lineage hook) carried "
                "cross-host traffic")
    return errors, warnings


# elastic degradation-ladder actions (faults/supervisor.py
# _elastic_step) — duplicated literally so the lint stays importable
# without the engine
_ELASTIC_ACTIONS = ("retry", "shrink", "serial")


def _is_pow2(n) -> bool:
    return (isinstance(n, int) and not isinstance(n, bool)
            and n >= 1 and not (n & (n - 1)))


def _lint_elastic(el, health) -> tuple[list, list]:
    """(errors, warnings) for an "elastic" block (faults/supervisor.py
    _elastic_block; rides the run manifest and the fleet manifest's
    per-job entries). The invariants are the degradation ladder's
    contract: mesh widths are powers of two that only hold or shrink
    (monotone transitions, contiguous chain), every recorded fault is
    answered by at most one ladder step (losses + divergences ==
    ladder steps, short exactly one when the ladder exhausted),
    mesh_transitions is exactly the width-changing subset of the
    steps, and a divergence's verified frontier can never pass its own
    trip point."""
    errors: list = []
    warnings: list = []
    if not isinstance(el, dict):
        return (["elastic must be an object"], [])
    w = "elastic"
    init, fin = el.get("initial_shards"), el.get("final_shards")
    for k, v in (("initial_shards", init), ("final_shards", fin)):
        if not _is_pow2(v):
            errors.append(f"{w}.{k} must be a positive power of two, "
                          f"got {v!r}")
    if _is_pow2(init) and _is_pow2(fin) and fin > init:
        errors.append(f"{w}: final_shards={fin} exceeds initial_"
                      f"shards={init} — the ladder only holds or "
                      f"shrinks the mesh, never grows it")
    lists = {}
    for k in ("losses", "divergences", "ladder_steps",
              "mesh_transitions"):
        v = el.get(k)
        if not isinstance(v, list):
            errors.append(f"{w}.{k} must be an array")
            lists[k] = []
        else:
            lists[k] = v
    for i, ls in enumerate(lists["losses"]):
        where = f"{w}.losses[{i}]"
        if not isinstance(ls, dict) \
                or ls.get("fault") != "DEVICE_LOST":
            errors.append(f'{where}: must be an object with '
                          f'fault="DEVICE_LOST"')
            continue
        sh = ls.get("shard")
        if not isinstance(sh, int) or isinstance(sh, bool) or sh < -1:
            errors.append(f"{where}: shard must be an integer >= -1 "
                          f"(-1 = unattributed), got {sh!r}")
    for i, dv in enumerate(lists["divergences"]):
        where = f"{w}.divergences[{i}]"
        if not isinstance(dv, dict) \
                or dv.get("fault") != "SHARD_DIVERGENCE":
            errors.append(f'{where}: must be an object with '
                          f'fault="SHARD_DIVERGENCE"')
            continue
        sh = dv.get("shard")
        if not isinstance(sh, int) or isinstance(sh, bool) or sh < 0:
            errors.append(f"{where}: shard must name the offending "
                          f"shard (integer >= 0), got {sh!r}")
        va, ta = dv.get("verified_through_ns"), dv.get("tripped_at_ns")
        for k, v in (("verified_through_ns", va),
                     ("tripped_at_ns", ta)):
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                errors.append(f"{where}: {k} must be a non-negative "
                              f"integer, got {v!r}")
        if isinstance(va, int) and isinstance(ta, int) \
                and not isinstance(va, bool) \
                and not isinstance(ta, bool) and va >= ta > 0:
            errors.append(
                f"{where}: verified_through_ns={va} reaches its own "
                f"trip point (tripped_at_ns={ta}) — the verified "
                f"frontier stops strictly before the first tripped "
                f"barrier")
    cur = init if _is_pow2(init) else None
    for i, st in enumerate(lists["ladder_steps"]):
        where = f"{w}.ladder_steps[{i}]"
        if not isinstance(st, dict):
            errors.append(f"{where}: must be an object")
            cur = None
            continue
        action = st.get("action")
        if action not in _ELASTIC_ACTIONS:
            errors.append(f"{where}: unknown action {action!r} "
                          f"(expected one of {_ELASTIC_ACTIONS})")
        f_, t_ = st.get("from"), st.get("to")
        if not _is_pow2(f_) or not _is_pow2(t_):
            errors.append(f"{where}: from/to must be positive powers "
                          f"of two, got {f_!r} -> {t_!r}")
            cur = None
            continue
        if action == "retry" and t_ != f_:
            errors.append(f"{where}: a retry holds the mesh, got "
                          f"{f_} -> {t_}")
        if action == "shrink" and t_ >= f_:
            errors.append(f"{where}: a shrink must strictly reduce "
                          f"the width, got {f_} -> {t_}")
        if action == "serial" and t_ != 1:
            errors.append(f"{where}: serial means one shard, got "
                          f"to={t_}")
        if cur is not None and f_ != cur:
            errors.append(f"{where}: from={f_} breaks the chain "
                          f"(previous width {cur}) — ladder steps "
                          f"must be contiguous")
        cur = t_
        rt = st.get("resume_time_ns")
        if not isinstance(rt, int) or isinstance(rt, bool) or rt < 0:
            errors.append(f"{where}: resume_time_ns must be a "
                          f"non-negative integer, got {rt!r}")
    if lists["ladder_steps"] and cur is not None \
            and _is_pow2(fin) and cur != fin:
        errors.append(f"{w}: final_shards={fin} but the last ladder "
                      f"step left the mesh at {cur}")
    want_trans = [s for s in lists["ladder_steps"]
                  if isinstance(s, dict) and s.get("from") != s.get("to")]
    if isinstance(el.get("mesh_transitions"), list) \
            and lists["mesh_transitions"] != want_trans:
        errors.append(
            f"{w}.mesh_transitions must be exactly the width-changing "
            f"subset of ladder_steps ({len(want_trans)} step(s)), got "
            f"{len(lists['mesh_transitions'])}")
    n_faults = len(lists["losses"]) + len(lists["divergences"])
    n_steps = len(lists["ladder_steps"])
    if n_steps > n_faults:
        errors.append(
            f"{w}: {n_steps} ladder step(s) but only {n_faults} "
            f"recorded fault(s) — every step answers exactly one "
            f"loss or divergence")
    elif n_faults - n_steps > 1:
        errors.append(
            f"{w}: {n_faults} fault(s) but only {n_steps} ladder "
            f"step(s) — the ladder answers every fault except, at "
            f"most, the one that exhausted it")
    elif n_faults == n_steps + 1:
        warnings.append(f"{w}: the ladder exhausted on the final "
                        f"fault (the run ended degraded-and-failed; "
                        f"the fleet layer owns the next requeue)")
    sent = (health or {}).get("sentinel") \
        if isinstance(health, dict) else None
    if lists["divergences"] and health is not None and not sent:
        errors.append(
            f"{w}: divergence records but no sentinel block in "
            f"health — a SHARD_DIVERGENCE verdict can only come from "
            f"the integrity sentinel latch")
    return errors, warnings


def _lint_health_sentinel(sent) -> list:
    """Errors for a health block's "sentinel" latch report
    (faults/health.py failure_report): trips never exceed checks, a
    tripped latch names its suspect shard, and the verified frontier
    stops strictly before the first tripped barrier."""
    errors: list = []
    w = "health.sentinel"
    if not isinstance(sent, dict):
        return [f"{w} must be an object"]
    vals = {}
    for k in ("checks", "trips", "tripped_at_ns",
              "verified_through_ns"):
        v = sent.get(k)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            errors.append(f"{w}.{k} must be a non-negative integer, "
                          f"got {v!r}")
        else:
            vals[k] = v
    sh = sent.get("shard")
    if not isinstance(sh, int) or isinstance(sh, bool) or sh < -1:
        errors.append(f"{w}.shard must be an integer >= -1, got {sh!r}")
    if vals.get("trips", 0) > vals.get("checks", 0):
        errors.append(f"{w}: trips={vals.get('trips')} exceeds "
                      f"checks={vals.get('checks')} — the latch "
                      f"counts a subset of the barrier checks")
    if vals.get("trips"):
        if isinstance(sh, int) and not isinstance(sh, bool) and sh < 0:
            errors.append(f"{w}: a tripped sentinel must name its "
                          f"suspect shard")
        if "tripped_at_ns" in vals and "verified_through_ns" in vals \
                and vals["tripped_at_ns"] > 0 \
                and vals["verified_through_ns"] >= vals["tripped_at_ns"]:
            errors.append(
                f"{w}: verified_through_ns="
                f"{vals['verified_through_ns']} reaches the trip "
                f"point tripped_at_ns={vals['tripped_at_ns']} — a "
                f"tripped barrier is never verified")
    return errors


def lint_checkpoint_elastic(path: str) -> tuple[list, list]:
    """(errors, warnings) for a snapshot's verified-state ledger
    stamp (utils/checkpoint.py elastic_meta / replan_shards). Pure
    numpy + json — no engine import. The invariants: the stamped
    shard_digests list carries exactly one digest per recorded shard,
    last_verified_window never passes the snapshot's own resume time
    (a snapshot cannot be verified past the moment it was taken), and
    every recorded replan is a pow2 -> pow2 restamp."""
    import numpy as np

    errors: list = []
    warnings: list = []
    p = path if path.endswith(".npz") else path + ".npz"
    try:
        z = np.load(p, allow_pickle=False)
    except (OSError, ValueError) as e:
        return ([f"{path}: unreadable npz: {e}"], [])
    with z:
        if "__meta__" not in z.files:
            return ([f"{path}: missing __meta__ — not a snapshot"], [])
        try:
            meta = json.loads(str(z["__meta__"]))
        except ValueError as e:
            return ([f"{path}: __meta__ is not JSON: {e}"], [])
    shards = meta.get("shards")
    t = meta.get("time_ns")
    if not _is_pow2(shards):
        errors.append(f"{path}: __meta__.shards must be a positive "
                      f"power of two, got {shards!r}")
    if not isinstance(t, int) or isinstance(t, bool) or t < 0:
        errors.append(f"{path}: __meta__.time_ns must be a "
                      f"non-negative integer, got {t!r}")
    el = meta.get("elastic")
    if el is None:
        warnings.append(f"{path}: snapshot carries no elastic stamp "
                        f"(no sentinel attached — trusted as-saved)")
        return errors, warnings
    if not isinstance(el, dict):
        return (errors + [f"{path}: __meta__.elastic must be an "
                          f"object"], warnings)
    digs = el.get("shard_digests")
    if not isinstance(digs, list) or not all(
            isinstance(d, str) and d for d in digs):
        errors.append(f"{path}: elastic.shard_digests must be a list "
                      f"of digest strings")
    elif _is_pow2(shards) and len(digs) != shards:
        errors.append(
            f"{path}: elastic.shard_digests holds {len(digs)} "
            f"digest(s) but the snapshot records shards={shards} — "
            f"one digest per shard, exactly")
    lvw = el.get("last_verified_window")
    if lvw is not None:
        if not isinstance(lvw, int) or isinstance(lvw, bool) or lvw < 0:
            errors.append(f"{path}: elastic.last_verified_window must "
                          f"be a non-negative integer or null, got "
                          f"{lvw!r}")
        elif isinstance(t, int) and not isinstance(t, bool) and lvw > t:
            errors.append(
                f"{path}: elastic.last_verified_window={lvw} passes "
                f"the snapshot's own resume time time_ns={t} — a "
                f"snapshot cannot be verified past the moment it was "
                f"taken")
    sent = el.get("sentinel")
    if sent is not None:
        errors += [f"{path}: {m.replace('health.sentinel', 'elastic.sentinel')}"
                   for m in _lint_health_sentinel(sent)]
    for i, rp in enumerate(el.get("replans") or []):
        where = f"{path}: elastic.replans[{i}]"
        if not isinstance(rp, dict) or not _is_pow2(rp.get("from")) \
                or not _is_pow2(rp.get("to")):
            errors.append(f"{where}: must record a pow2 -> pow2 "
                          f"restamp, got {rp!r}")
    return errors, warnings


def _lint_admission(adm) -> tuple[list, list]:
    """(errors, warnings) for an "admission" block — either a resident
    program's lease-table block (fleet/admission.py manifest_block,
    rides the fleet manifest) or the standalone resident run's
    device-plane fold (telemetry/export.py admission_manifest_block,
    rides the run manifest). The core invariant is lease-count
    conservation: every admitted lease is exactly one of completed,
    evicted, quarantined, or still resident — a lease can never
    vanish or be double-counted."""
    errors: list = []
    warnings: list = []
    if not isinstance(adm, dict):
        return (["admission must be an object"], [])
    counts = {}
    for k in ("admitted", "completed", "evicted", "quarantined",
              "resident"):
        v = adm.get(k)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            errors.append(f"admission.{k} must be a non-negative "
                          f"integer, got {v!r}")
        else:
            counts[k] = v
    for k in ("deferred", "lanes", "lane_width", "admission_events",
              "retraces"):
        v = adm.get(k)
        if v is not None and (not isinstance(v, int)
                              or isinstance(v, bool) or v < 0):
            errors.append(f"admission.{k} must be a non-negative "
                          f"integer, got {v!r}")
    if len(counts) == 5 and counts["admitted"] != (
            counts["completed"] + counts["evicted"]
            + counts["quarantined"] + counts["resident"]):
        errors.append(
            f"lease counts not conserved: admitted="
            f"{counts['admitted']} != completed={counts['completed']} "
            f"+ evicted={counts['evicted']} + quarantined="
            f"{counts['quarantined']} + resident={counts['resident']} "
            f"— every admitted lease must end in exactly one terminal "
            f"state or still hold its lane")
    # zero-retrace contract: a resident program that retraced (or
    # whose program key moved) broke the whole design — admission
    # events must be pure runtime-data mutation
    pk = adm.get("program_key")
    if pk is not None and (not isinstance(pk, str)
                           or not _PROGRAM_KEY.match(pk)):
        errors.append(f'admission.program_key must match "pk" + 16 '
                      f"hex chars, got {pk!r}")
    stable = adm.get("program_key_stable")
    if stable is not None and not isinstance(stable, bool):
        errors.append(f"admission.program_key_stable must be a bool, "
                      f"got {stable!r}")
    elif stable is False:
        errors.append(
            "admission.program_key_stable=false — the program key "
            "moved across an admission event (a join/leave must "
            "never change compiled shapes)")
    rt = adm.get("retraces")
    if isinstance(rt, int) and not isinstance(rt, bool) and rt > 0:
        errors.append(f"admission.retraces={rt} — a resident program "
                      f"must serve every admission event from the one "
                      f"warm trace")
    # degradation ladder: the recorded step must be a real rung and
    # agree with the level index
    lvl = adm.get("degrade_level")
    step = adm.get("degrade_step")
    if lvl is not None and (not isinstance(lvl, int)
                            or isinstance(lvl, bool)
                            or not 0 <= lvl < len(_LEASE_LADDER)):
        errors.append(f"admission.degrade_level must be an integer in "
                      f"[0, {len(_LEASE_LADDER)}), got {lvl!r}")
    if step is not None and step not in _LEASE_LADDER:
        errors.append(f"admission.degrade_step {step!r} is not a "
                      f"ladder rung {_LEASE_LADDER}")
    if (isinstance(lvl, int) and not isinstance(lvl, bool)
            and 0 <= lvl < len(_LEASE_LADDER)
            and step is not None and step != _LEASE_LADDER[lvl]):
        errors.append(f"admission.degrade_step={step!r} disagrees "
                      f"with degrade_level={lvl} "
                      f"({_LEASE_LADDER[lvl]!r})")
    if isinstance(lvl, int) and not isinstance(lvl, bool) and lvl > 0:
        warnings.append(f"admission gate degraded to "
                        f"{_LEASE_LADDER[lvl]!r} (protected-tenant "
                        f"SLO pressure)")
    hist = adm.get("degrade_history")
    if hist is not None:
        if not isinstance(hist, list):
            errors.append("admission.degrade_history must be an array")
        else:
            for i, h in enumerate(hist):
                if not isinstance(h, dict) \
                        or h.get("step") not in _LEASE_LADDER:
                    errors.append(f"admission.degrade_history[{i}] "
                                  f"must name a ladder rung")
    # per-lane lease planes (core/lanes.py admission_report)
    per = adm.get("per_lane")
    active = 0
    if per is not None:
        if not isinstance(per, list):
            errors.append("admission.per_lane must be an array")
            per = []
        nlanes = adm.get("lanes")
        if (isinstance(nlanes, int) and not isinstance(nlanes, bool)
                and per and len(per) != nlanes):
            errors.append(f"admission.per_lane has {len(per)} entries "
                          f"but lanes={nlanes}")
        for i, d in enumerate(per):
            where = f"admission.per_lane[{i}]"
            if not isinstance(d, dict):
                errors.append(f"{where}: must be an object")
                continue
            if d.get("lane") != i:
                errors.append(f"{where}: lane={d.get('lane')!r} out "
                              f"of order (expected {i})")
            for k in ("active", "completed"):
                if not isinstance(d.get(k), bool):
                    errors.append(f"{where}: {k} must be a bool")
            for k in ("epoch", "flushed"):
                v = d.get(k)
                if (not isinstance(v, int) or isinstance(v, bool)
                        or v < 0):
                    errors.append(f"{where}: {k} must be a "
                                  f"non-negative integer, got {v!r}")
            if d.get("active") is True:
                active += 1
        if per and "resident" in counts and active < counts["resident"]:
            errors.append(
                f"admission: {counts['resident']} resident lease(s) "
                f"but only {active} active device lane plane(s) — a "
                f"live lease must hold an active lane")
    # SLO gate snapshot
    slo = adm.get("slo")
    if slo is not None:
        if not isinstance(slo, dict):
            errors.append("admission.slo must be an object")
            slo = {}
        for k in ("eval_stride", "sustained"):
            v = slo.get(k)
            if v is not None and (not isinstance(v, int)
                                  or isinstance(v, bool) or v < 1):
                errors.append(f"admission.slo.{k} must be an integer "
                              f">= 1, got {v!r}")
        for lane, v in sorted((slo.get("last_p99_ns") or {}).items()):
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                errors.append(f"admission.slo.last_p99_ns[{lane}] "
                              f"must be a non-negative integer")
        for job, ratio in sorted((slo.get("breached_jobs")
                                  or {}).items()):
            if (not isinstance(ratio, (int, float))
                    or isinstance(ratio, bool) or ratio <= 1.0):
                errors.append(
                    f"admission.slo.breached_jobs[{job}]={ratio!r} — "
                    f"a recorded breach ratio must exceed 1.0 (p99 "
                    f"over objective), anything else is not a breach")
    if counts.get("evicted"):
        warnings.append(f"{counts['evicted']} lease(s) evicted "
                        f"(SLO shedding or operator churn; salvage "
                        f"artifacts in the lease history)")
    if counts.get("quarantined"):
        warnings.append(f"{counts['quarantined']} lane lease(s) "
                        f"quarantined (lanes stay parked until the "
                        f"program restarts)")
    lw = adm.get("lease_warnings")
    if lw:
        for w in lw:
            warnings.append(f"lease journal: {w}")
    return errors, warnings


def _lint_slo_verdict(slo, flows, where: str) -> list:
    """Errors for one scenario result's "slo" verdict
    (fleet/scenario.py slo_verdict): the verdict must be arithmetic
    over the flow percentiles it claims to summarize."""
    errors: list = []
    if not isinstance(slo, dict):
        return [f"{where} must be an object"]
    obj_ms = slo.get("objective_p99_ms")
    p99 = slo.get("p99_ns")
    met = slo.get("met")
    if (not isinstance(obj_ms, (int, float)) or isinstance(obj_ms, bool)
            or obj_ms <= 0):
        errors.append(f"{where}.objective_p99_ms must be a positive "
                      f"number, got {obj_ms!r}")
    if not isinstance(p99, int) or isinstance(p99, bool) or p99 < 0:
        errors.append(f"{where}.p99_ns must be a non-negative "
                      f"integer, got {p99!r}")
    if not isinstance(met, bool):
        errors.append(f"{where}.met must be a bool, got {met!r}")
    tc = slo.get("tenant_class")
    if tc is not None and tc not in ("protected", "best_effort"):
        errors.append(f"{where}.tenant_class must be 'protected' or "
                      f"'best_effort', got {tc!r}")
    if not errors and met != (p99 <= obj_ms * 1e6):
        errors.append(
            f"{where}: met={met} contradicts p99_ns={p99} vs "
            f"objective {obj_ms}ms ({int(obj_ms * 1e6)}ns) — the "
            f"verdict must be arithmetic over its own numbers")
    # the claimed p99 must be the worst per-lane flow p99 it
    # summarizes (slo_verdict takes the max across lanes)
    per_lane = (flows or {}).get("per_lane")
    if isinstance(per_lane, dict) and per_lane \
            and isinstance(p99, int) and not isinstance(p99, bool):
        worst = max((int(d.get("p99_ns", 0) or 0)
                     for d in per_lane.values()
                     if isinstance(d, dict) and d.get("count")),
                    default=None)
        if worst is not None and p99 != worst:
            errors.append(
                f"{where}.p99_ns={p99} but the flow per-lane "
                f"percentiles peak at {worst} — the verdict must "
                f"summarize the flow block it rides with")
    return errors


# sweep block (sweep/driver.py sweep_block): the ranking logic is
# duplicated literally from sweep/reduce.py so the lint can RE-DERIVE
# every recorded table and prune decision from the per-job entries
# without importing the engine — a recorded ranking that disagrees
# with its own inputs is tampering or a writer bug, not a style issue
_SWEEP_METRICS = ("flow_p50_ns", "flow_p95_ns", "flow_p99_ns",
                  "drops", "events", "events_per_sec")
_SWEEP_ELIGIBLE = ("ok", "warnings")
_SWEEP_CATS = ("completed", "failed", "quarantined", "pruned",
               "pending")


def _sweep_metric_value(entry, metric):
    """Mirror of sweep/reduce.py metric_value over one fleet-manifest
    job entry; None when the job carries no data for the metric."""
    result = entry.get("result") or {}
    counters = result.get("counters") or {}
    if metric == "events":
        v = counters.get("events_processed")
        return None if v is None else int(v)
    if metric == "drops":
        v = counters.get("drops_total")
        return None if v is None else int(v)
    if metric == "events_per_sec":
        v = result.get("events_per_sec")
        return None if v is None else float(v)
    pkey = {"flow_p50_ns": "p50_ns", "flow_p95_ns": "p95_ns",
            "flow_p99_ns": "p99_ns"}.get(metric)
    if pkey is None:
        return None
    per_lane = (result.get("flows") or {}).get("per_lane") or {}
    vals = [int(s.get(pkey, 0)) for s in per_lane.values()
            if isinstance(s, dict)
            and int(s.get("count", 0) or 0) > 0]
    return max(vals) if vals else None


def _sweep_rank(entries, objective):
    """Mirror of sweep/reduce.py rank: eligible rows by (value, point)
    under the objective's goal, ineligible rows after in point order."""
    need_clean = bool(objective.get("require_clean_health"))
    eligible, rest = [], []
    for pid in sorted(entries):
        entry = entries[pid]
        status = entry.get("status")
        if status in ("failed", "quarantined"):
            verdict = status
        elif status != "done":
            verdict = "pending"
        else:
            hv = (entry.get("result") or {}).get("health_verdict")
            if hv is not None and hv != "clean":
                verdict = "unhealthy" if need_clean else "warnings"
            else:
                verdict = "ok"
        value = (_sweep_metric_value(entry, objective.get("metric"))
                 if verdict in _SWEEP_ELIGIBLE else None)
        if verdict in _SWEEP_ELIGIBLE and value is None:
            verdict = "no_data"
        row = {"point": pid, "value": value, "verdict": verdict}
        (eligible if verdict in _SWEEP_ELIGIBLE else rest).append(row)
    sign = 1 if objective.get("goal") == "min" else -1
    eligible.sort(key=lambda r: (sign * r["value"], r["point"]))
    return eligible + rest


def _lint_sweep(sw, jobs) -> tuple[list, list]:
    """(errors, warnings) for a fleet manifest's "sweep" roll-up
    (sweep/driver.py sweep_block). The three core invariants:

      1. lattice conservation — every expanded point ends in exactly
         one of completed / failed / quarantined / pruned / pending,
         and a complete sweep has no pending points;
      2. ranking re-derivation — every recorded per-round ranking
         (and the final table, and "best") must re-derive from the
         per-job result blocks it claims to summarize;
      3. program-key census vs the prewarm log — every sweep job's
         affinity key is in the planned census, the census counts sum
         to the jobs expanded, and every realized program key was in
         the prewarm log (warning: the pool compiled something the
         census did not predict)."""
    errors: list = []
    warnings: list = []
    if not isinstance(sw, dict):
        return (["sweep must be an object"], [])
    if not isinstance(sw.get("id"), str) or not sw.get("id"):
        errors.append("sweep.id must be a non-empty string")
    obj = sw.get("objective")
    if not isinstance(obj, dict) \
            or obj.get("metric") not in _SWEEP_METRICS \
            or obj.get("goal") not in ("min", "max"):
        errors.append(f"sweep.objective must name a metric in "
                      f"{_SWEEP_METRICS} and a goal in "
                      f"('min', 'max'), got {obj!r}")
        obj = None
    lattice = sw.get("lattice")
    if not isinstance(lattice, int) or isinstance(lattice, bool) \
            or lattice < 1:
        errors.append(f"sweep.lattice must be a positive integer, "
                      f"got {lattice!r}")
        lattice = None
    rounds = sw.get("rounds")
    if not isinstance(rounds, list) or not rounds \
            or not all(isinstance(r, dict) for r in rounds):
        errors.append('sweep.rounds must be a non-empty array of '
                      'round objects')
        return errors, warnings
    # lattice conservation
    pts = sw.get("points")
    counts = {}
    if not isinstance(pts, dict):
        errors.append("sweep.points must be an object")
    else:
        for k in ("expanded",) + _SWEEP_CATS:
            v = pts.get(k)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                errors.append(f"sweep.points.{k} must be a "
                              f"non-negative integer, got {v!r}")
            else:
                counts[k] = v
        if len(counts) == 6 and counts["expanded"] != sum(
                counts[c] for c in _SWEEP_CATS):
            errors.append(
                f"lattice not conserved: expanded="
                f"{counts['expanded']} != completed="
                f"{counts['completed']} + failed={counts['failed']} "
                f"+ quarantined={counts['quarantined']} + pruned="
                f"{counts['pruned']} + pending={counts['pending']} — "
                f"every expanded point must end in exactly one "
                f"category")
        if sw.get("complete") and counts.get("pending"):
            errors.append(f"sweep claims complete but "
                          f"{counts['pending']} point(s) are pending")
        if lattice is not None and "expanded" in counts \
                and counts["expanded"] > lattice:
            errors.append(f"sweep.points.expanded="
                          f"{counts['expanded']} exceeds the lattice "
                          f"({lattice})")
        r0 = rounds[0].get("points")
        if isinstance(r0, list) and "expanded" in counts \
                and len(r0) != counts["expanded"]:
            errors.append(f"sweep.points.expanded="
                          f"{counts['expanded']} but round 0 planned "
                          f"{len(r0)} point(s)")
    # per-round: job linkage, count re-derivation, ranking
    # re-derivation from the per-job entries
    expanded_jobs = 0
    search = sw.get("search") if isinstance(sw.get("search"), dict) \
        else {}
    for k, rd in enumerate(rounds):
        where = f"sweep.rounds[{k}]"
        if rd.get("round") != k:
            errors.append(f"{where}: round={rd.get('round')!r} out of "
                          f"order (expected {k})")
        rpts = rd.get("points")
        if not isinstance(rpts, list) or not rpts:
            errors.append(f"{where}: points must be a non-empty array")
            continue
        expanded_jobs += len(rpts)
        entries = {}
        rcounts = {"done": 0, "failed": 0, "quarantined": 0,
                   "pending": 0}
        for pid in rpts:
            jid = f"r{k}-{pid}"
            j = jobs.get(jid)
            if not isinstance(j, dict):
                rcounts["pending"] += 1
                entries[pid] = {}
                continue
            entries[pid] = j
            st = j.get("status")
            rcounts[st if st in rcounts else "pending"] += 1
        rc = rd.get("counts")
        if isinstance(rc, dict) and rc != rcounts:
            errors.append(f"{where}.counts={rc} but the job statuses "
                          f"fold to {rcounts}")
        table = rd.get("ranking")
        if table is None:
            continue
        if not isinstance(table, list):
            errors.append(f"{where}.ranking must be an array")
            continue
        if obj is not None:
            want = _sweep_rank(entries, obj)
            if table != want:
                errors.append(
                    f"{where}.ranking does not re-derive from the "
                    f"per-job result blocks — recorded {table!r} vs "
                    f"derived {want!r} (the reducer is pure; a "
                    f"divergence means the table was not computed "
                    f"from these results)")
        # successive halving: round k+1's survivors and prune set
        # must be THE deterministic function of round k's table —
        # top ceil(n_eligible/eta), never below one survivor
        if search.get("strategy") == "halving" and k + 1 < len(rounds):
            eta = search.get("eta")
            eta = eta if isinstance(eta, int) \
                and not isinstance(eta, bool) and eta >= 2 else 2
            elig = [r.get("point") for r in table
                    if isinstance(r, dict)
                    and r.get("verdict") in _SWEEP_ELIGIBLE]
            keep = max(1, -(-len(elig) // eta))
            survive = elig[:keep]
            nxt = rounds[k + 1]
            if nxt.get("points") != survive:
                errors.append(
                    f"sweep.rounds[{k + 1}].points="
                    f"{nxt.get('points')!r} but round {k} ranking "
                    f"keeps {survive!r} (top ceil({len(elig)}/{eta})) "
                    f"— a halving round must re-derive from the "
                    f"journaled reduce output")
            want_pruned = sorted(set(elig) - set(survive))
            if sorted(nxt.get("pruned") or []) != want_pruned:
                errors.append(
                    f"sweep.rounds[{k + 1}].pruned="
                    f"{nxt.get('pruned')!r} but round {k} ranking "
                    f"prunes {want_pruned!r}")
    je = sw.get("jobs_expanded")
    if je is not None and je != expanded_jobs:
        errors.append(f"sweep.jobs_expanded={je!r} but the rounds "
                      f"planned {expanded_jobs} job(s)")
    # final table and best must restate the last reduced round
    final = next((rd.get("ranking") for rd in reversed(rounds)
                  if rd.get("ranking") is not None), None)
    if sw.get("ranking") != final:
        errors.append("sweep.ranking does not match the last reduced "
                      "round's table")
    if isinstance(final, list):
        top = next((r.get("point") for r in final
                    if isinstance(r, dict)
                    and r.get("verdict") in _SWEEP_ELIGIBLE), None)
        if sw.get("best") != top:
            errors.append(f"sweep.best={sw.get('best')!r} but the "
                          f"final ranking's top eligible point is "
                          f"{top!r}")
    # distinct-program census vs the sweep's jobs and the prewarm log
    census = sw.get("census")
    sweep_jobs = {jid: j for jid, j in sorted(jobs.items())
                  if isinstance(j, dict)
                  and re.match(r"^r\d+-p\d+$", jid)}
    if not isinstance(census, dict) \
            or not isinstance(census.get("programs"), dict):
        errors.append('sweep.census must carry a "programs" object')
    else:
        programs = census["programs"]
        for ak, n in sorted(programs.items()):
            if not _AFFINITY_KEY.match(ak):
                errors.append(f'sweep.census.programs key {ak!r} must '
                              f'match "ak" + 16 hex chars')
            if not isinstance(n, int) or isinstance(n, bool) or n < 1:
                errors.append(f"sweep.census.programs[{ak}]={n!r} "
                              f"must be a positive point count")
        if census.get("distinct") != len(programs):
            errors.append(f"sweep.census.distinct="
                          f"{census.get('distinct')!r} but "
                          f"{len(programs)} program(s) listed")
        total = sum(n for n in programs.values()
                    if isinstance(n, int) and not isinstance(n, bool))
        if total != expanded_jobs:
            errors.append(f"sweep.census counts sum to {total} but "
                          f"the rounds planned {expanded_jobs} "
                          f"job(s) — the census must partition the "
                          f"lattice")
        for jid, j in sweep_jobs.items():
            ak = j.get("affinity_key")
            if isinstance(ak, str) and ak not in programs:
                errors.append(f"jobs[{jid}].affinity_key {ak} is not "
                              f"in the sweep census — the plan must "
                              f"predict every program the pool loads")
    pw = sw.get("prewarm")
    if pw is not None:
        if not isinstance(pw, dict):
            errors.append("sweep.prewarm must be an object")
        else:
            for k in ("hits", "compiled"):
                v = pw.get(k)
                if not isinstance(v, int) or isinstance(v, bool) \
                        or v < 0:
                    errors.append(f"sweep.prewarm.{k} must be a "
                                  f"non-negative integer, got {v!r}")
            keys = pw.get("keys")
            if not isinstance(keys, list):
                errors.append("sweep.prewarm.keys must be an array")
                keys = []
            for pk in keys:
                if not isinstance(pk, str) \
                        or not _PROGRAM_KEY.match(pk):
                    errors.append(f'sweep.prewarm.keys entry {pk!r} '
                                  f'must match "pk" + 16 hex chars')
            warmed = {pk for pk in keys if isinstance(pk, str)}
            cold = sorted({j["program_key"]
                           for j in sweep_jobs.values()
                           if isinstance(j.get("program_key"), str)
                           and j["program_key"] not in warmed})
            if cold:
                warnings.append(
                    f"sweep jobs realized program key(s) the prewarm "
                    f"log never warmed: {cold} — the pool compiled "
                    f"cold (census prediction diverged from the "
                    f"build?)")
    return errors, warnings


def lint_salvage(path: str) -> list:
    """Errors for a lane-salvage artifact (utils/checkpoint.py
    save_salvage; faults/escalate.py extract_lane output). Pure
    numpy — no engine import — so the soak and CI can lint salvage
    evidence anywhere. Returns [] when clean."""
    import zlib

    import numpy as np

    errors: list = []
    try:
        z = np.load(path, allow_pickle=False)
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable npz: {e}"]
    with z:
        if "__meta__" not in z.files:
            return [f"{path}: missing __meta__ — not a salvage "
                    f"artifact"]
        try:
            meta = json.loads(str(z["__meta__"]))
        except ValueError as e:
            return [f"{path}: __meta__ is not JSON: {e}"]
        if meta.get("kind") != "lane_salvage":
            errors.append(f"{path}: kind={meta.get('kind')!r}, "
                          f"expected 'lane_salvage' (a resumable "
                          f"snapshot is not salvage evidence)")
        leaves = sorted(k for k in z.files if k != "__meta__")
        if not leaves:
            errors.append(f"{path}: artifact holds zero state leaves")
        keys = meta.get("keys")
        if isinstance(keys, list) and sorted(keys) != leaves:
            errors.append(f"{path}: __meta__.keys disagrees with the "
                          f"stored leaves")
        crcs = meta.get("crc32")
        if not isinstance(crcs, dict):
            errors.append(f"{path}: missing per-leaf crc32 map")
            crcs = {}
        for k in leaves:
            arr = z[k]
            if k in crcs and (zlib.crc32(
                    np.ascontiguousarray(arr).tobytes())
                    & 0xFFFFFFFF) != crcs[k]:
                errors.append(f"{path}: leaf {k} fails its CRC32 — "
                              f"salvage evidence is corrupt")
        t = meta.get("time_ns")
        if not isinstance(t, int) or isinstance(t, bool) or t < 0:
            errors.append(f"{path}: __meta__.time_ns must be a "
                          f"non-negative integer, got {t!r}")
        caps = meta.get("capacities")
        if not isinstance(caps, dict) or not caps.get("num_hosts"):
            errors.append(f"{path}: __meta__.capacities must name the "
                          f"slice's shapes (at least num_hosts)")
    return errors


def lint_trace_obj(obj) -> tuple[list, list]:
    """(errors, warnings) for a parsed Chrome-trace object."""
    errors: list = []
    warnings: list = []
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        return (['top level must be an object with "traceEvents" '
                 '(the JSON Object Format; Perfetto rejects bare '
                 'arrays with displayTimeUnit)'], [])
    evs = obj["traceEvents"]
    if not isinstance(evs, list):
        return (['"traceEvents" must be an array'], [])
    windows = []
    for i, e in enumerate(evs):
        where = f"traceEvents[{i}]"
        if not isinstance(e, dict) or "ph" not in e:
            errors.append(f'{where}: every event needs a "ph" phase')
            continue
        ph = e["ph"]
        if ph == "M":
            if e.get("name") not in KNOWN_METADATA:
                warnings.append(
                    f'{where}: metadata name {e.get("name")!r} is not '
                    f'one the viewers understand ({sorted(KNOWN_METADATA)})')
            continue
        if ph == "C":
            # counter events (the critical-path track's per-window
            # jump-utilization series, export.py pid 3): need a name,
            # a numeric ts, and a numeric-valued args series
            if not e.get("name"):
                errors.append(f'{where}: "C" event needs a name')
            if not isinstance(e.get("ts"), (int, float)):
                errors.append(f'{where}: "C" event needs numeric ts')
            a = e.get("args")
            if not isinstance(a, dict) or not a:
                errors.append(f'{where}: "C" event needs a non-empty '
                              f'args object (the counter series)')
            continue
        if ph != "X":
            warnings.append(f'{where}: unexpected phase {ph!r} (the '
                            f'exporter only emits "X", "C" and "M")')
            continue
        for k in ("ts", "dur"):
            if not isinstance(e.get(k), (int, float)):
                errors.append(f'{where}: "X" event needs numeric {k}')
        for k in ("pid", "tid"):
            if not isinstance(e.get(k), int):
                errors.append(f'{where}: "X" event needs integer {k}')
        if isinstance(e.get("dur"), (int, float)) and e["dur"] <= 0:
            errors.append(f'{where}: dur must be > 0 (zero-duration '
                          f'complete events render invisibly)')
        if e.get("pid") == 0 and isinstance(e.get("args"), dict):
            a = e["args"]
            for k in WINDOW_ARGS:
                if k in a and (not isinstance(a[k], int) or a[k] < 0):
                    errors.append(f"{where}: args.{k} must be a "
                                  f"non-negative integer")
            q = a.get("queue_occupancy")
            if isinstance(q, dict) and (
                    q.get("min", 0) > q.get("max", 0)):
                errors.append(f"{where}: queue_occupancy min > max")
            if isinstance(e.get("ts"), (int, float)):
                windows.append((e["ts"], e.get("dur", 0), i))
    # window ordering: the harvester emits records in ring order, so
    # an unsorted sim-time track means export corruption; gaps are
    # legal (ring overrun drops whole records, latched elsewhere)
    last_end = None
    for ts, dur, i in windows:
        if last_end is not None and ts < last_end:
            warnings.append(
                f"traceEvents[{i}]: sim-time window at ts={ts} starts "
                f"before the previous window ended ({last_end}) — "
                f"overlapping windows (supervisor replay after a "
                f"resume can legally do this; otherwise suspect)")
        last_end = ts + dur
    if not windows:
        warnings.append("no sim-time window events (pid 0) — empty "
                        "run or telemetry was off")
    return errors, warnings


def lint_manifest_obj(man) -> tuple[list, list]:
    """(errors, warnings) for a parsed run_manifest.json."""
    errors: list = []
    warnings: list = []
    if not isinstance(man, dict):
        return (["manifest must be a JSON object"], [])
    for k in ("config_hash", "seed", "shards", "counters"):
        if k not in man:
            errors.append(f'manifest missing "{k}"')
    tel = man.get("telemetry")
    if not isinstance(tel, dict):
        errors.append('manifest missing the "telemetry" block')
        return errors, warnings
    lost = tel.get("records_lost", 0)
    if lost:
        # the loss MUST be surfaced: either the health block carries
        # the latch or a diagnostic names it — never a silent integer
        health = man.get("health", {})
        latched = health.get("telemetry_lost", 0) == lost or any(
            "telemetry ring overran" in d
            for d in health.get("diagnostics", []))
        if not latched:
            errors.append(
                f"telemetry.records_lost={lost} but the health block "
                f"does not surface it — ring overruns must be latched "
                f"(faults/health.py), never silent")
        else:
            warnings.append(
                f"{lost} telemetry record(s) lost to ring overrun "
                f"(latched in health; trace has gaps)")
    rec = tel.get("windows_recorded", 0)
    cw = man.get("counters", {}).get("windows")
    if cw is not None and rec + lost > cw:
        errors.append(
            f"telemetry accounts for {rec}+{lost} windows but the "
            f"engine ran only {cw}")
    # compile accounting (VERDICT open item 6, first step): a bench /
    # CLI manifest that carries compile_s must make it a sane number,
    # and the fresh-vs-cache flag a bool
    cs = man.get("compile_s")
    if cs is not None and (not isinstance(cs, (int, float))
                           or isinstance(cs, bool) or cs < 0):
        errors.append(f"compile_s must be a non-negative number, "
                      f"got {cs!r}")
    cf = man.get("compile_fresh")
    if cf is not None and not isinstance(cf, bool):
        errors.append(f"compile_fresh must be a bool, got {cf!r}")
    # program-store accounting block (optional): the AOT warm-serving
    # record (compile/serve.py), with the bench's warm-up call nested
    # under "warmup" for one-row fresh-vs-cached scoring
    comp = man.get("compile")
    if comp is not None:
        e2, w2 = _lint_compile_block(comp, "compile")
        errors += e2
        warnings += w2
        if isinstance(comp, dict) and comp.get("warmup") is not None:
            e2, w2 = _lint_compile_block(comp["warmup"],
                                         "compile.warmup")
            errors += e2
            warnings += w2
    # sparse fast-path counters: non-negative, and hit+miss can never
    # exceed the windows the engine ran
    ctr = man.get("counters", {})
    fp = [ctr.get(k) for k in ("fastpath_hit", "fastpath_miss")]
    for k, v in zip(("fastpath_hit", "fastpath_miss"), fp):
        if v is not None and (not isinstance(v, int)
                              or isinstance(v, bool) or v < 0):
            errors.append(f"counters.{k} must be a non-negative "
                          f"integer, got {v!r}")
    if (cw is not None and all(isinstance(v, int) for v in fp)
            and fp[0] + fp[1] > cw):
        errors.append(
            f"fastpath_hit+miss = {fp[0]}+{fp[1]} exceeds the "
            f"{cw} windows the engine ran")
    # bulk-pass commits are a part of the events the engine committed
    be, ep = ctr.get("bulk_events"), ctr.get("events_processed")
    if be is not None and (not isinstance(be, int)
                           or isinstance(be, bool) or be < 0):
        errors.append(f"counters.bulk_events must be a non-negative "
                      f"integer, got {be!r}")
    elif be is not None and isinstance(ep, int) and be > ep:
        errors.append(f"bulk_events = {be} exceeds the {ep} events "
                      f"the engine committed")
    # dual-mode conformance block (optional): counts must be coherent
    # non-negative ints summing to the per-workload verdicts, and a
    # divergence is always SURFACED as a warning
    conf = man.get("conformance")
    if conf is not None:
        if not isinstance(conf, dict):
            errors.append("conformance must be an object")
        else:
            for k in ("workloads", "agree", "diverge", "total"):
                if k not in conf:
                    errors.append(f'conformance missing "{k}"')
            for k in ("agree", "diverge", "total"):
                v = conf.get(k)
                if k in conf and (not isinstance(v, int)
                                  or isinstance(v, bool) or v < 0):
                    errors.append(f"conformance.{k} must be a "
                                  f"non-negative integer, got {v!r}")
            wl = conf.get("workloads")
            if isinstance(wl, dict) and all(
                    isinstance(conf.get(k), int)
                    for k in ("agree", "diverge", "total")):
                if conf["agree"] + conf["diverge"] != conf["total"] \
                        or conf["total"] != len(wl):
                    errors.append(
                        f"conformance counts incoherent: agree="
                        f"{conf['agree']} + diverge={conf['diverge']} "
                        f"vs total={conf['total']} over "
                        f"{len(wl)} workload verdict(s)")
            if isinstance(conf.get("diverge"), int) and conf["diverge"]:
                bad = sorted(k for k, v in (wl or {}).items()
                             if v != "agree")
                warnings.append(
                    f"conformance: {conf['diverge']} workload(s) "
                    f"diverged between backends: {bad}")
    # compile-time specialization block (optional): vector/dropped
    # coherence, key derivation, guard-latch fatality
    spec = man.get("specialization")
    if spec is not None:
        e2, w2 = _lint_specialization(spec, man.get("counters"),
                                      man.get("health"))
        errors += e2
        warnings += w2
    # supervisor chain identity (optional): run_id / resume_of are
    # opaque id strings; a resume_of without a run_id is incoherent
    for k in ("run_id", "resume_of"):
        v = man.get(k)
        if v is not None and (not isinstance(v, str) or not v):
            errors.append(f"{k} must be a non-empty string, got {v!r}")
    if man.get("resume_of") is not None and man.get("run_id") is None:
        errors.append('manifest carries "resume_of" without "run_id" '
                      '— a chained run must identify itself')
    # escalation records (optional): the supervisor's healed capacity
    # trips. Each names a known grow knob, grows strictly (from < to),
    # and a run that escalated and ended clean must show zero on the
    # latch counter it healed — a surviving overflow means the heal
    # lied.
    esc = man.get("escalations")
    if esc is not None:
        if not isinstance(esc, list) or not esc:
            errors.append("escalations must be a non-empty array "
                          "(omit the key for runs that never healed)")
            esc = []
        known_knobs = {"event_capacity", "outbox_capacity",
                       "router_ring"}
        latch_of_knob = {"event_capacity": "events_overflow",
                         "outbox_capacity": "outbox_overflow",
                         "router_ring": "rq_overflow"}
        ctr = man.get("counters", {})
        verdict = man.get("health", {}).get("verdict")
        for i, e in enumerate(esc):
            where = f"escalations[{i}]"
            if not isinstance(e, dict):
                errors.append(f"{where}: must be an object")
                continue
            for k in ("time_ns", "latch", "knob", "from", "to"):
                if k not in e:
                    errors.append(f'{where}: missing "{k}"')
            for k in ("time_ns", "from", "to"):
                v = e.get(k)
                if k in e and (not isinstance(v, int)
                               or isinstance(v, bool) or v < 0):
                    errors.append(f"{where}: {k} must be a "
                                  f"non-negative integer, got {v!r}")
            knob = e.get("knob")
            if knob is not None and knob not in known_knobs:
                errors.append(f"{where}: unknown grow knob {knob!r} "
                              f"(expected one of {sorted(known_knobs)})")
            if (isinstance(e.get("from"), int)
                    and isinstance(e.get("to"), int)
                    and e["to"] <= e["from"]):
                errors.append(f"{where}: capacities only grow — "
                              f"from={e['from']} to={e['to']}")
            latch = latch_of_knob.get(knob)
            if (latch and verdict == "clean"
                    and isinstance(ctr.get(latch), int)
                    and ctr[latch] != 0):
                errors.append(
                    f"{where}: run escalated {knob} and reports a "
                    f"clean verdict, yet counters.{latch}="
                    f"{ctr[latch]} — the healed run must end with "
                    f"the latch at zero")
        if esc:
            warnings.append(
                f"{len(esc)} capacity escalation(s) healed this run "
                f"(final capacities grew; see escalations[])")
    pre = man.get("preempted")
    if pre is not None and not isinstance(pre, bool):
        errors.append(f"preempted must be a bool, got {pre!r}")
    # dispatch block (optional): the chunked window loop's shape.
    # windows_per_dispatch >= 1, dispatches >= 0, and when the
    # per-dispatch "windows" list is present (clean single-attempt
    # non-resumed runs only) each entry fits the chunk and the sum
    # equals the engine's executed-window counter exactly.
    disp = man.get("dispatch")
    if disp is not None:
        if not isinstance(disp, dict):
            errors.append("dispatch must be an object")
        else:
            wpd = disp.get("windows_per_dispatch")
            if (not isinstance(wpd, int) or isinstance(wpd, bool)
                    or wpd < 1):
                errors.append(f"dispatch.windows_per_dispatch must be "
                              f"an integer >= 1, got {wpd!r}")
            nd = disp.get("dispatches")
            if (not isinstance(nd, int) or isinstance(nd, bool)
                    or nd < 0):
                errors.append(f"dispatch.dispatches must be a "
                              f"non-negative integer, got {nd!r}")
            dw = disp.get("windows")
            if dw is not None:
                if not isinstance(dw, list) or not all(
                        isinstance(w, int) and not isinstance(w, bool)
                        and w >= 0 for w in dw):
                    errors.append("dispatch.windows must be a list of "
                                  "non-negative integers")
                else:
                    if isinstance(nd, int) and len(dw) != nd:
                        errors.append(
                            f"dispatch.windows has {len(dw)} entries "
                            f"but dispatches={nd}")
                    if isinstance(wpd, int) and any(
                            w > wpd for w in dw):
                        errors.append(
                            f"dispatch.windows entry exceeds "
                            f"windows_per_dispatch={wpd}: {dw}")
                    if cw is not None and sum(dw) != cw:
                        errors.append(
                            f"dispatch.windows sums to {sum(dw)} but "
                            f"counters.windows={cw} — per-dispatch "
                            f"accounting must cover every executed "
                            f"window exactly")
            aj = disp.get("adaptive_jump_mean_ns")
            if aj is not None and (
                    not isinstance(aj, (int, float))
                    or isinstance(aj, bool) or aj < 0):
                errors.append(f"dispatch.adaptive_jump_mean_ns must "
                              f"be a non-negative number, got {aj!r}")
    # injection block (optional): open-system traffic accounting
    # (inject/__init__.py manifest_block). The device latches must be
    # coherent ints, drops must be SURFACED in health (latch design:
    # never a silent integer), the per-window telemetry plane must sum
    # to the device total when no records were lost, and the feeder's
    # reconciliation must close: every trace event is injected,
    # dropped, or deferred past end-of-run — nothing vanishes.
    inj = man.get("injection")
    if inj is not None:
        if not isinstance(inj, dict):
            errors.append("injection must be an object")
            inj = {}
        for k in ("lanes", "injected", "dropped", "late"):
            v = inj.get(k)
            if (not isinstance(v, int) or isinstance(v, bool)
                    or v < 0):
                errors.append(f"injection.{k} must be a non-negative "
                              f"integer, got {v!r}")
        lanes = inj.get("lanes")
        if isinstance(lanes, int) and lanes >= 1 \
                and lanes & (lanes - 1):
            errors.append(f"injection.lanes must be a power of two "
                          f"(slot = trace position % lanes), got "
                          f"{lanes}")
        health = man.get("health", {})
        dropped = inj.get("dropped")
        if isinstance(dropped, int) and dropped:
            latched = health.get("inject_dropped", 0) == dropped \
                or any("injection drops" in d
                       for d in health.get("diagnostics", []))
            if not latched:
                errors.append(
                    f"injection.dropped={dropped} but the health "
                    f"block does not surface it — refused injections "
                    f"must be latched (faults/health.py), never "
                    f"silent")
            else:
                warnings.append(
                    f"{dropped} injected event(s) dropped by full "
                    f"host rows (latched in health; results are "
                    f"missing those trace events)")
        late = inj.get("late")
        if isinstance(late, int) and late:
            errors.append(
                f"injection.late={late}: events merged after their "
                f"window had run — the feeder's horizon contract "
                f"was violated (timestamps perturbed)")
        # per-window plane vs device latch: lossless telemetry must
        # account for every injected event window by window
        if (tel.get("records_lost", 0) == 0
                and isinstance(tel.get("injected_sum"), int)
                and isinstance(inj.get("injected"), int)
                and tel["injected_sum"] != inj["injected"]):
            errors.append(
                f"telemetry.injected_sum={tel['injected_sum']} but "
                f"injection.injected={inj['injected']} with zero "
                f"records lost — the per-window plane must sum to "
                f"the device latch")
        # feeder reconciliation (only defined once the trace drained
        # and latched its total)
        te = inj.get("trace_events")
        dfr = inj.get("deferred")
        if isinstance(te, int) and isinstance(dfr, int) and all(
                isinstance(inj.get(k), int)
                for k in ("injected", "dropped")):
            if inj["injected"] + inj["dropped"] + dfr != te:
                errors.append(
                    f"injection does not reconcile: injected="
                    f"{inj['injected']} + dropped={inj['dropped']} + "
                    f"deferred={dfr} != trace_events={te} — every "
                    f"trace event must be injected, dropped, or "
                    f"deferred, never silently lost")
            if dfr:
                warnings.append(
                    f"{dfr} trace event(s) deferred past end-of-run "
                    f"(timestamps beyond the simulation horizon)")
        bp = inj.get("backpressure")
        if bp is not None and (not isinstance(bp, int)
                               or isinstance(bp, bool) or bp < 0):
            errors.append(f"injection.backpressure must be a "
                          f"non-negative integer, got {bp!r}")
        elif isinstance(bp, int) and bp:
            warnings.append(
                f"feeder hit backpressure on {bp} refill(s) — the "
                f"staging buffer filled; raise --inject-lanes if "
                f"wallclock suffers")
    # lanes block (optional): lane-isolated packed-run accounting
    # (telemetry/export.py lanes_manifest_block). The per-lane counters
    # are [R] companion planes of the run-total latches, accumulated in
    # lockstep with the scalars — each latch's lane shares must sum to
    # the run total EXACTLY (the scalars stay authoritative). Every
    # quarantined lane must be fully described (trip names, quarantine
    # time), and when the supervisor's lane surgery ran, carry its
    # salvage pointer + requeue context.
    lb = man.get("lanes")
    if lb is not None:
        if not isinstance(lb, dict):
            errors.append("lanes must be an object")
            lb = {}
        nlanes = lb.get("replicas")
        if (not isinstance(nlanes, int) or isinstance(nlanes, bool)
                or nlanes < 1):
            errors.append(f"lanes.replicas must be an integer >= 1, "
                          f"got {nlanes!r}")
            nlanes = None
        if not isinstance(lb.get("contained"), bool):
            errors.append("lanes.contained must be a bool")
        per = lb.get("per_lane")
        if not isinstance(per, list) or not per:
            errors.append("lanes.per_lane must be a non-empty array")
            per = []
        if nlanes is not None and per and len(per) != nlanes:
            errors.append(f"lanes.per_lane has {len(per)} entries but "
                          f"replicas={nlanes}")
        quar = lb.get("quarantined")
        if not isinstance(quar, list) or not all(
                isinstance(q, int) and not isinstance(q, bool)
                for q in quar):
            errors.append("lanes.quarantined must be a list of lane "
                          "indices")
            quar = []
        lane_counts = ("events_overflow", "outbox_overflow",
                       "rq_overflow", "inj_dropped", "stall_streak",
                       "time_regression", "events_exec", "flushed")
        sums = dict.fromkeys(lane_counts, 0)
        rows_ok = bool(per)
        seen_quar = []
        for i, d in enumerate(per):
            where = f"lanes.per_lane[{i}]"
            if not isinstance(d, dict):
                errors.append(f"{where}: must be an object")
                rows_ok = False
                continue
            if d.get("lane") != i:
                errors.append(f"{where}: lane={d.get('lane')!r} out "
                              f"of order (expected {i})")
            for k in lane_counts:
                v = d.get(k)
                if (not isinstance(v, int) or isinstance(v, bool)
                        or v < 0):
                    errors.append(f"{where}: {k} must be a "
                                  f"non-negative integer, got {v!r}")
                    rows_ok = False
                else:
                    sums[k] += v
            if d.get("quarantined"):
                seen_quar.append(i)
                for k in ("quarantined_at_ns", "trip_bits"):
                    if not isinstance(d.get(k), int):
                        errors.append(f"{where}: quarantined lane "
                                      f"must carry {k}")
                if not d.get("trip"):
                    errors.append(f"{where}: quarantined lane must "
                                  f"name its trip(s)")
        if per and sorted(quar) != seen_quar:
            errors.append(f"lanes.quarantined={sorted(quar)} disagrees "
                          f"with the per-lane quarantined flags "
                          f"({seen_quar})")
        if rows_ok:
            for k in ("events_overflow", "outbox_overflow",
                      "rq_overflow"):
                total = ctr.get(k)
                if (isinstance(total, int)
                        and not isinstance(total, bool)
                        and sums[k] != total):
                    errors.append(
                        f"per-lane {k} sums to {sums[k]} but "
                        f"counters.{k}={total} — the [R] companion "
                        f"plane must cover the run-total latch "
                        f"exactly")
        # incidents = the supervisor's lane-surgery records: each one
        # merges into its per_lane entry as salvage + requeue context
        incs = lb.get("incidents")
        if incs is not None and not isinstance(incs, list):
            errors.append("lanes.incidents must be an array")
            incs = None
        if incs:
            inc_lanes = {d.get("lane") for d in incs
                         if isinstance(d, dict)}
            for i, d in enumerate(per):
                if not (isinstance(d, dict) and d.get("quarantined")
                        and d.get("lane") in inc_lanes):
                    continue
                where = f"lanes.per_lane[{i}]"
                if "salvage" not in d or "requeue" not in d:
                    errors.append(f"{where}: quarantined lane with an "
                                  f"incident must carry its salvage "
                                  f"pointer + requeue context")
                elif not d.get("salvage"):
                    warnings.append(f"{where}: lane surgery ran but "
                                    f"the salvage write failed (lane "
                                    f"requeues without clean-slice "
                                    f"evidence)")
                rq_ = d.get("requeue")
                if isinstance(rq_, dict) and not isinstance(
                        rq_.get("regrow"), dict):
                    errors.append(f"{where}: requeue.regrow must map "
                                  f"trip knobs to grown capacities")
            for q in seen_quar:
                if q not in inc_lanes:
                    warnings.append(
                        f"lane {q} quarantined with no incident "
                        f"record (unsupervised run, or quarantine "
                        f"predates this supervisor chain)")
        elif seen_quar:
            warnings.append(
                f"{len(seen_quar)} lane(s) quarantined with no "
                f"salvage (unsupervised run — nothing extracted)")
        # per-window telemetry fan-out vs the device counter: on a
        # lossless single-chain run the [W,R] ring plane's deltas must
        # sum to each lane's cumulative events_exec
        les = tel.get("lane_events_sum")
        if (isinstance(les, list) and rows_ok
                and tel.get("records_lost", 0) == 0
                and man.get("resume_of") is None
                and not man.get("escalations")):
            got = [d.get("events_exec", 0) for d in per
                   if isinstance(d, dict)]
            if len(les) == len(got) and les != got:
                warnings.append(
                    f"telemetry.lane_events_sum={les} vs per-lane "
                    f"events_exec={got} on a lossless run — the "
                    f"per-window fan-out should cover every executed "
                    f"event")
    # flows block (optional): per-flow latency tracing accounting
    fl = man.get("flows")
    if fl is not None:
        e2, w2 = _lint_flows(fl, man.get("counters"), tel)
        errors += e2
        warnings += w2
    # causality block (optional): causal critical-path accounting
    cz = man.get("causality")
    if cz is not None:
        e2, w2 = _lint_causality(cz, tel, fl)
        errors += e2
        warnings += w2
    # admission block (optional): standalone resident-run lease fold
    adm = man.get("admission")
    if adm is not None:
        e2, w2 = _lint_admission(adm)
        errors += e2
        warnings += w2
    # elastic block (optional): degraded-mesh recovery record
    el = man.get("elastic")
    if el is not None:
        e2, w2 = _lint_elastic(el, man.get("health"))
        errors += e2
        warnings += w2
    # sentinel latch report (optional, inside health): validated even
    # without an elastic block — a sentinel-armed run that never
    # degraded still stamps its check/trip accounting
    sent = (man.get("health") or {}).get("sentinel") \
        if isinstance(man.get("health"), dict) else None
    if sent is not None:
        errors += _lint_health_sentinel(sent)
    # profile block (optional): a pointer to a jax.profiler artifact
    prof = man.get("profile")
    if prof is not None:
        if not isinstance(prof, dict) or not prof.get("dir"):
            errors.append('profile must be an object naming its '
                          '"dir" — a capture nobody can find is no '
                          'capture')
    return errors, warnings


_FLEET_TERMINAL = {"done": "ok", "failed": "failed",
                   "quarantined": "quarantined"}
_FLEET_STATUSES = {"queued", "leased", "running"} | set(_FLEET_TERMINAL)


def lint_fleet_manifest_obj(man) -> tuple[list, list]:
    """(errors, warnings) for a parsed fleet_manifest.json
    (shadow_tpu/fleet/manifest.py schema)."""
    errors: list = []
    warnings: list = []
    if not isinstance(man, dict):
        return (["fleet manifest must be a JSON object"], [])
    if man.get("schema") != "shadow-tpu-fleet-manifest":
        errors.append(f'schema must be "shadow-tpu-fleet-manifest", '
                      f'got {man.get("schema")!r}')
    if not isinstance(man.get("schema_version"), int):
        errors.append("schema_version must be an integer")
    if not isinstance(man.get("policy"), dict):
        errors.append('missing the "policy" block')
    for k in ("preempted", "stalled", "complete"):
        if not isinstance(man.get(k), bool):
            errors.append(f"{k} must be a bool, got {man.get(k)!r}")
    jobs = man.get("jobs")
    if not isinstance(jobs, dict) or not jobs:
        errors.append('"jobs" must be a non-empty object')
        return errors, warnings
    counts: dict = {}
    for jid, j in sorted(jobs.items()):
        where = f"jobs[{jid}]"
        if not isinstance(j, dict):
            errors.append(f"{where}: must be an object")
            continue
        st = j.get("status")
        counts[st] = counts.get(st, 0) + 1
        if st not in _FLEET_STATUSES:
            errors.append(f"{where}: unknown status {st!r}")
            continue
        # attempt accounting: monotone non-decreasing 1-based history,
        # attempts == the high-water mark, one history entry per
        # execution (a requeued continuation repeats the attempt
        # number, it never rewinds it)
        hist = j.get("attempt_history")
        if not isinstance(hist, list) or not all(
                isinstance(a, int) and a >= 1 for a in hist):
            errors.append(f"{where}: attempt_history must be a list "
                          f"of attempt numbers >= 1")
            hist = []
        if any(b < a for a, b in zip(hist, hist[1:])):
            errors.append(f"{where}: attempt_history must be "
                          f"monotone non-decreasing, got {hist}")
        att = j.get("attempts")
        if not isinstance(att, int) or att < 0:
            errors.append(f"{where}: attempts must be a non-negative "
                          f"integer")
        elif hist and att != max(hist):
            errors.append(f"{where}: attempts={att} disagrees with "
                          f"attempt_history high-water {max(hist)}")
        ex = j.get("executions")
        if isinstance(ex, int) and hist and ex != len(hist):
            errors.append(f"{where}: executions={ex} but "
                          f"{len(hist)} attempt_history entries")
        bh = j.get("backoff_history", [])
        if not isinstance(bh, list) or not all(
                isinstance(b, (int, float)) and b >= 0 for b in bh):
            errors.append(f"{where}: backoff_history must hold "
                          f"non-negative delays")
        # terminal jobs carry a verdict; the verdict matches status
        verdict = j.get("verdict")
        want = _FLEET_TERMINAL.get(st)
        if want is not None and verdict != want:
            errors.append(f"{where}: terminal status {st!r} must "
                          f"carry verdict {want!r}, got {verdict!r}")
        if want is None and verdict is not None:
            errors.append(f"{where}: non-terminal job carries a "
                          f"verdict ({verdict!r})")
        if st == "done" and not isinstance(j.get("result"), dict):
            errors.append(f"{where}: done job must carry its result")
        # SLO verdict (optional, tenant jobs): the verdict must be
        # arithmetic over the flow percentiles it rides with
        res = j.get("result")
        if isinstance(res, dict) and res.get("slo") is not None:
            errors += _lint_slo_verdict(res["slo"], j.get("flows"),
                                        f"{where}.result.slo")
        if st == "failed" and not isinstance(j.get("failure"), dict):
            errors.append(f"{where}: failed job must carry its "
                          f"failure report")
        if st == "quarantined":
            if not j.get("quarantine_reason"):
                errors.append(f"{where}: quarantined job must state "
                              f"its reason")
            sal = j.get("salvage")
            if not isinstance(sal, dict) or not sal.get("dir"):
                errors.append(f"{where}: quarantined job must carry "
                              f"salvage pointers (at least the job "
                              f"dir)")
            elif not any(sal.get(k) for k in
                         ("checkpoint", "run_manifest", "result")):
                warnings.append(f"{where}: quarantined with no "
                                f"checkpoint/manifest/result salvaged "
                                f"(died before its first checkpoint?)")
        # packed jobs (replicas > 1) surface per-lane verdicts at the
        # entry level; every quarantined lane's requeue child must be
        # a replicas=1 standalone spec back-linked via lane_of, and
        # the runner backfills it into this same queue
        rep = j.get("replicas")
        if rep is not None and (not isinstance(rep, int)
                                or isinstance(rep, bool) or rep < 2):
            errors.append(f"{where}: replicas must be an integer >= 2 "
                          f"when present, got {rep!r}")
        lanes = j.get("lanes")
        if lanes is not None:
            if not isinstance(lanes, dict):
                errors.append(f"{where}: lanes must be an object")
                lanes = {}
            if rep is None:
                errors.append(f"{where}: lane verdicts on a job that "
                              f"does not declare replicas")
            ql = lanes.get("quarantined")
            if not isinstance(ql, list) or not ql:
                errors.append(f"{where}: lanes block without "
                              f"quarantined lanes (omit the block for "
                              f"all-healthy packed jobs)")
                ql = []
            for ci, child in enumerate(lanes.get("requeues") or []):
                cw = f"{where}.lanes.requeues[{ci}]"
                if not isinstance(child, dict):
                    errors.append(f"{cw}: must be an object")
                    continue
                if child.get("lane_of") != jid:
                    errors.append(f"{cw}: lane_of="
                                  f"{child.get('lane_of')!r} must "
                                  f"back-link the packed parent "
                                  f"{jid!r}")
                if child.get("replicas", 1) != 1:
                    errors.append(f"{cw}: a lane requeue must be a "
                                  f"replicas=1 standalone spec")
                cid = child.get("id")
                if isinstance(cid, str) and cid not in jobs:
                    warnings.append(f"{cw}: child {cid!r} not (yet) "
                                    f"backfilled into the queue — "
                                    f"fleet killed between fold and "
                                    f"backfill?")
        lof = j.get("lane_of")
        if lof is not None:
            parent = jobs.get(lof)
            if not isinstance(parent, dict):
                errors.append(f"{where}: lane_of names unknown job "
                              f"{lof!r}")
            elif not parent.get("replicas"):
                errors.append(f"{where}: lane_of parent {lof!r} is "
                              f"not a packed job")
        # bucket-affinity fields (fleet/affinity.py): the scheduling
        # key is spec-derived and always present on new manifests; the
        # program key appears once the job's run reported one
        ak = j.get("affinity_key")
        if ak is not None and (not isinstance(ak, str)
                               or not _AFFINITY_KEY.match(ak)):
            errors.append(f'{where}: affinity_key must match "ak" + '
                          f"16 hex chars, got {ak!r}")
        pk = j.get("program_key")
        if pk is not None and (not isinstance(pk, str)
                               or not _PROGRAM_KEY.match(pk)):
            errors.append(f'{where}: program_key must match "pk" + '
                          f"16 hex chars, got {pk!r}")
    # affinity consistency: two jobs the scheduler binned together
    # (equal affinity_keys) must have realized the same compiled
    # program — a divergence means the spec-derived key is lying
    # about program identity
    prog_of_aff: dict = {}
    for jid, j in sorted(jobs.items()):
        if not isinstance(j, dict):
            continue
        ak, pk = j.get("affinity_key"), j.get("program_key")
        if not (isinstance(ak, str) and isinstance(pk, str)):
            continue
        seen = prog_of_aff.setdefault(ak, (jid, pk))
        if seen[1] != pk:
            errors.append(
                f"jobs[{jid}] and jobs[{seen[0]}] share affinity_key "
                f"{ak} but realized different program_keys "
                f"({pk} vs {seen[1]}) — the affinity key must be a "
                f"program-identity invariant")
    # flows roll-up (optional): the fleet-level totals must equal the
    # sums over the per-job flow summaries — the roll-up is derived,
    # so a divergence means the manifest writer and the job results
    # went out of sync
    ft = man.get("flows")
    job_fl = {jid: j["flows"] for jid, j in sorted(jobs.items())
              if isinstance(j, dict) and isinstance(j.get("flows"),
                                                    dict)}
    for jid, fl in job_fl.items():
        where = f"jobs[{jid}].flows"
        cnt = {}
        for k in ("sampled", "recorded", "harvested", "lost_ring",
                  "lost_window_clamp"):
            v = fl.get(k)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                errors.append(f"{where}.{k} must be a non-negative "
                              f"integer, got {v!r}")
            else:
                cnt[k] = v
        if len(cnt) == 5 and cnt["recorded"] + cnt["lost_window_clamp"] \
                != cnt["sampled"]:
            errors.append(
                f"{where}: recorded={cnt['recorded']} + "
                f"lost_window_clamp={cnt['lost_window_clamp']} != "
                f"sampled={cnt['sampled']}")
    if ft is not None:
        if not isinstance(ft, dict):
            errors.append('"flows" must be an object')
        elif not job_fl:
            errors.append('fleet "flows" roll-up with no flow-traced '
                          'job entries')
        else:
            if ft.get("jobs") != len(job_fl):
                errors.append(f"flows.jobs={ft.get('jobs')!r} but "
                              f"{len(job_fl)} job(s) carry a flows "
                              f"summary")
            for k in ("sampled", "recorded", "harvested", "lost_ring",
                      "lost_window_clamp"):
                want = sum(int(fl.get(k, 0) or 0)
                           for fl in job_fl.values())
                if ft.get(k) != want:
                    errors.append(f"flows.{k}={ft.get(k)!r} but the "
                                  f"job summaries sum to {want}")
            want_lanes: dict = {}
            for fl in job_fl.values():
                for lane, summ in (fl.get("per_lane") or {}).items():
                    if isinstance(summ, dict):
                        want_lanes[lane] = (want_lanes.get(lane, 0)
                                            + int(summ.get("count", 0)
                                                  or 0))
            if ft.get("lane_samples") != want_lanes:
                errors.append(f"flows.lane_samples="
                              f"{ft.get('lane_samples')!r} but the "
                              f"job per-lane counts sum to "
                              f"{want_lanes}")
    elif job_fl:
        errors.append(f'{len(job_fl)} job(s) carry flow summaries but '
                      f'the fleet manifest has no "flows" roll-up')
    # causality roll-up (optional): same derived-totals rule — the
    # fleet block must be the exact fold of the per-job causality
    # summaries, including the binding-cause histogram
    ct = man.get("causality")
    job_cz = {jid: j["causality"] for jid, j in sorted(jobs.items())
              if isinstance(j, dict)
              and isinstance(j.get("causality"), dict)}
    for jid, cz in job_cz.items():
        where = f"jobs[{jid}].causality"
        for k in ("sampled", "harvested", "lost_ring",
                  "windows_attributed", "windows_lost"):
            v = cz.get(k)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                errors.append(f"{where}.{k} must be a non-negative "
                              f"integer, got {v!r}")
        if isinstance(cz.get("harvested"), int) \
                and isinstance(cz.get("lost_ring"), int) \
                and isinstance(cz.get("sampled"), int) \
                and cz["harvested"] + cz["lost_ring"] > cz["sampled"]:
            errors.append(
                f"{where}: harvested={cz['harvested']} + lost_ring="
                f"{cz['lost_ring']} exceeds sampled={cz['sampled']}")
        for name in (cz.get("causes") or {}):
            if name not in _CAUSE_NAMES:
                errors.append(f"{where}.causes[{name!r}]: unknown "
                              f"binding cause")
    if ct is not None:
        if not isinstance(ct, dict):
            errors.append('"causality" must be an object')
        elif not job_cz:
            errors.append('fleet "causality" roll-up with no '
                          'causality-traced job entries')
        else:
            if ct.get("jobs") != len(job_cz):
                errors.append(f"causality.jobs={ct.get('jobs')!r} but "
                              f"{len(job_cz)} job(s) carry a "
                              f"causality summary")
            for k in ("sampled", "harvested", "lost_ring",
                      "windows_attributed", "windows_lost"):
                want = sum(int(cz.get(k, 0) or 0)
                           for cz in job_cz.values())
                if ct.get(k) != want:
                    errors.append(f"causality.{k}={ct.get(k)!r} but "
                                  f"the job summaries sum to {want}")
            want_causes: dict = {}
            for cz in job_cz.values():
                for name, n in (cz.get("causes") or {}).items():
                    want_causes[name] = (want_causes.get(name, 0)
                                         + int(n or 0))
            if ct.get("causes") != want_causes:
                errors.append(f"causality.causes="
                              f"{ct.get('causes')!r} but the job "
                              f"histograms fold to {want_causes}")
    elif job_cz:
        errors.append(f'{len(job_cz)} job(s) carry causality '
                      f'summaries but the fleet manifest has no '
                      f'"causality" roll-up')
    # elastic roll-up (optional): same derived-totals rule — the
    # fleet block must be the exact fold of the per-job elastic
    # records and device-loss requeue counts
    et = man.get("elastic")
    job_el = {jid: j for jid, j in sorted(jobs.items())
              if isinstance(j, dict)
              and (isinstance(j.get("elastic"), dict)
                   or int(j.get("device_losses", 0) or 0) > 0)}
    for jid, j in sorted(jobs.items()):
        if not isinstance(j, dict):
            continue
        dl = j.get("device_losses", 0)
        if not isinstance(dl, int) or isinstance(dl, bool) or dl < 0:
            errors.append(f"jobs[{jid}].device_losses must be a "
                          f"non-negative integer, got {dl!r}")
        so = j.get("shards_override")
        if so is not None and not _is_pow2(so):
            errors.append(f"jobs[{jid}].shards_override must be a "
                          f"positive power of two, got {so!r}")
        jel = j.get("elastic")
        if jel is not None:
            # per-job structural checks; health lives in the job's
            # run_manifest, not here, so sentinel cross-checks are
            # skipped (health=None)
            e2, w2 = _lint_elastic(jel, None)
            errors += [f"jobs[{jid}].{m}" for m in e2]
            warnings += [f"jobs[{jid}].{m}" for m in w2]
    if et is not None:
        if not isinstance(et, dict):
            errors.append('"elastic" must be an object')
        elif not job_el:
            errors.append('fleet "elastic" roll-up with no elastic '
                          'job entries')
        else:
            if et.get("jobs") != len(job_el):
                errors.append(f"elastic.jobs={et.get('jobs')!r} but "
                              f"{len(job_el)} job(s) carry an elastic "
                              f"record or device losses")
            want = {"device_lost": 0, "shard_divergence": 0,
                    "mesh_shrinks": 0, "ladder_steps": 0,
                    "fleet_requeues": 0}
            for j in job_el.values():
                want["fleet_requeues"] += int(
                    j.get("device_losses", 0) or 0)
                jel = j.get("elastic")
                if isinstance(jel, dict):
                    want["device_lost"] += len(jel.get("losses") or ())
                    want["shard_divergence"] += len(
                        jel.get("divergences") or ())
                    want["mesh_shrinks"] += len(
                        jel.get("mesh_transitions") or ())
                    want["ladder_steps"] += len(
                        jel.get("ladder_steps") or ())
            for k, v in want.items():
                if et.get(k) != v:
                    errors.append(f"elastic.{k}={et.get(k)!r} but the "
                                  f"job records fold to {v}")
    elif job_el:
        errors.append(f'{len(job_el)} job(s) carry elastic records '
                      f'but the fleet manifest has no "elastic" '
                      f'roll-up')
    # admission block (optional): a resident program's lease-table
    # roll-up (fleet/admission.py manifest_block)
    adm = man.get("admission")
    if adm is not None:
        e2, w2 = _lint_admission(adm)
        errors += e2
        warnings += w2
    # sweep block (optional): this fleet is one sweep's execution
    # substrate (sweep/driver.py sweep_block) — lattice conservation,
    # ranking re-derivation, census vs prewarm log
    sw = man.get("sweep")
    if sw is not None:
        e2, w2 = _lint_sweep(sw, jobs)
        errors += e2
        warnings += w2
    mc = man.get("counts")
    if isinstance(mc, dict) and mc != counts:
        errors.append(f"counts block {mc} disagrees with the jobs "
                      f"({counts})")
    if man.get("complete"):
        stuck = sorted(jid for jid, j in jobs.items()
                       if isinstance(j, dict)
                       and j.get("status") not in _FLEET_TERMINAL)
        if stuck:
            errors.append(f"manifest claims complete but jobs are "
                          f"non-terminal: {stuck}")
    q = counts.get("quarantined", 0)
    if q:
        warnings.append(f"{q} job(s) quarantined (parked with "
                        f"salvage; see jobs[*].salvage)")
    return errors, warnings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="validate telemetry exports (Chrome-trace JSON "
                    "and/or run manifest)")
    ap.add_argument("--trace", default=None, help="trace JSON path")
    ap.add_argument("--manifest", default=None,
                    help="run_manifest.json path")
    ap.add_argument("--fleet-manifest", default=None,
                    help="fleet_manifest.json path (shadow_tpu.fleet)")
    ap.add_argument("--salvage", default=None,
                    help="lane-salvage .npz path (lease eviction / "
                         "quarantine artifact)")
    ap.add_argument("--checkpoint", default=None,
                    help="snapshot .npz path — validate the "
                         "verified-state ledger stamp (elastic meta)")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="suppress warnings, print errors only")
    args = ap.parse_args(argv)
    if not (args.trace or args.manifest or args.fleet_manifest
            or args.salvage or args.checkpoint):
        ap.error("give --trace, --manifest, --fleet-manifest, "
                 "--salvage and/or --checkpoint")

    errors: list = []
    warnings: list = []
    for path, lint in ((args.trace, lint_trace_obj),
                       (args.manifest, lint_manifest_obj),
                       (args.fleet_manifest, lint_fleet_manifest_obj)):
        if not path:
            continue
        try:
            with open(path) as f:
                obj = json.load(f)
        except (OSError, ValueError) as e:
            errors.append(f"{path}: {e}")
            continue
        e2, w2 = lint(obj)
        errors += [f"{path}: {m}" for m in e2]
        warnings += [f"{path}: {m}" for m in w2]
    if args.salvage:
        errors += lint_salvage(args.salvage)
    if args.checkpoint:
        e2, w2 = lint_checkpoint_elastic(args.checkpoint)
        errors += e2
        warnings += w2

    for e in errors:
        print(f"ERROR: {e}", file=sys.stderr)
    if not args.quiet:
        for w in warnings:
            print(f"WARNING: {w}", file=sys.stderr)
    if errors:
        print(f"{len(errors)} error(s), {len(warnings)} warning(s)",
              file=sys.stderr)
        return 1
    print(f"OK ({len(warnings)} warning(s))", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
