"""Window-boundary checkpoint / resume (SURVEY.md §5.4 — the
reference has no checkpointing; the survey calls device-state
snapshots out as cheap and worth adding. The device state is a pytree
of fixed-shape arrays, so a snapshot is jax.device_get + np.savez and
resume is exact: the window-advance rule restarts from the recorded
next window start and the counter-based RNG (core/rng.py) needs no
stream state beyond what the arrays already hold).

Determinism contract: run(0 -> T) == run(0 -> C) + save + load +
run(C -> T), bit for bit — proven by tests/test_checkpoint.py. The
contract holds with a fault plan installed too: fault effects are a
pure function of (plan, window end), never of saved state
(faults/apply.py).

Torn-snapshot safety (the supervisor in faults/supervisor.py resumes
from these after trips, possibly after the process itself died
mid-save): save() writes to a temp file in the target directory,
fsyncs it, os.replace()s it into place, then fsyncs the PARENT
DIRECTORY — readers see the old snapshot or the new one, never a
partial write, and the rename itself survives power loss rather than
just process death (an unfsynced directory entry can vanish with the
page cache; the fleet journal in shadow_tpu/fleet/journal.py follows
the same discipline for its frames). Every leaf carries a CRC32 that
load() verifies before resuming.
"""

from __future__ import annotations

import json
import os
import tempfile
import zlib
from functools import partial

import jax
import numpy as np

# Bumped whenever the on-device byte layout changes meaning without
# changing shape/dtype (e.g. the packetfmt word reindex): shape checks
# alone cannot catch a reinterpretation, so load() refuses snapshots
# from a different layout generation instead of resuming into garbage.
LAYOUT_VERSION = 3  # v2: protocol-independent packet words 0..5,
                    # TCP header words 6..16 (packetfmt.py)
                    # v3: Outbox grew the route_elided counter leaf —
                    # the pytree structure changed, so v2 snapshots
                    # cannot be resumed (load()'s per-leaf key check
                    # would also catch it, but with a config-mismatch
                    # message; the layout gate names the real cause)
                    # (The Sim.inject staging buffer did NOT bump the
                    # version: like Sim.telem it defaults to None, so
                    # pytrees built without injection are leaf-for-
                    # leaf identical to v3 snapshots, and injection
                    # snapshots simply carry extra .inject leaves that
                    # resume only into injection-enabled builds — the
                    # per-leaf key check names the mismatch.)


def _leaf_dict(sim) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(sim)[0]
    out = {}
    for path, leaf in flat:
        out[jax.tree_util.keystr(path)] = np.asarray(jax.device_get(leaf))
    return out


def _crc(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF


def capacities_of_sim(sim) -> dict:
    """The static-shape knobs a snapshot depends on, read from the
    arrays themselves (the Sim does not carry its NetConfig). These
    ride __meta__ so a resume into a differently-sized build is
    diagnosed by *name* — and so the escalation transplanter
    (faults/escalate.py) knows which axis grew."""
    return {
        "num_hosts": int(sim.events.num_hosts),
        "event_capacity": int(sim.events.capacity),
        "outbox_capacity": int(sim.outbox.dst.shape[1]),
        "router_ring": int(sim.net.rq_src.shape[1]),
    }


def elastic_meta(sim, shards: int = 1) -> dict:
    """The verified-state ledger stamp a snapshot carries for elastic
    resume (parallel/elastic.py): per-shard sha256 digests over the
    leaves as sim_specs shards them (replicated leaves fold into every
    shard's digest, so digest s survives re-partitioning onto any mesh
    that still owns those rows), plus the sentinel's
    `last_verified_window` — the last window barrier proven
    divergence-free (None when no sentinel is attached: the snapshot
    is then trusted as-saved, verified == time_ns)."""
    from shadow_tpu.parallel.elastic import sentinel_report, shard_digests

    rep = sentinel_report(sim)
    return {
        "shard_digests": shard_digests(sim, shards),
        "last_verified_window": (None if rep is None
                                 else rep["verified_through_ns"]),
        "sentinel": rep,
    }


def save(path: str, sim, *, time_ns: int, extra: dict | None = None,
         shards: int = 1, config_digest: str | None = None,
         elastic: dict | None = None):
    """Snapshot a Sim pytree at a window boundary. `time_ns` is the
    next window start (resume point). Atomic: the snapshot appears at
    `path` complete or not at all. `shards` records the mesh width the
    run used and `config_digest` the config hash — both are diagnostic
    metadata only (state arrays are always saved in global layout, so
    a snapshot resumes under ANY shard count; a digest mismatch is a
    warning, not a refusal). `elastic` (elastic_meta) stamps the
    verified-state ledger block: per-shard digests +
    last_verified_window."""
    leaves = _leaf_dict(sim)
    meta = {"time_ns": int(time_ns), "extra": extra or {},
            "layout": LAYOUT_VERSION, "keys": sorted(leaves),
            "crc32": {k: _crc(v) for k, v in leaves.items()},
            "capacities": capacities_of_sim(sim),
            "shards": int(shards),
            "config_digest": config_digest,
            "jax_version": jax.__version__}
    if elastic is not None:
        meta["elastic"] = elastic
    # np.savez appends ".npz" to *paths* but not to file objects, and
    # the atomic write goes through a file object — normalize here so
    # both spellings land at the same place.
    if not path.endswith(".npz"):
        path = path + ".npz"
    d = os.path.dirname(os.path.abspath(path))
    fd, tmp = tempfile.mkstemp(prefix=".ckpt.", suffix=".tmp", dir=d)
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez_compressed(f, __meta__=json.dumps(meta),
                                **{k: v for k, v in leaves.items()})
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)  # same directory -> atomic rename
        # durable rename: without the directory fsync the new entry
        # (and on some filesystems the whole snapshot) can be lost to
        # power failure even though the data blocks were fsynced
        _fsync_dir(d)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


def _fsync_dir(path: str) -> None:
    """Best-effort directory fsync (filesystems that refuse O_RDONLY
    dir fsync keep the old process-death-only guarantee)."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _check_layout(meta: dict):
    layout = meta.get("layout", 1)
    if layout != LAYOUT_VERSION:
        raise ValueError(
            f"snapshot uses packet-word layout v{layout}, this "
            f"build reads v{LAYOUT_VERSION} — resuming would "
            f"reinterpret header words; re-run from config")


def peek_meta(path: str) -> dict:
    """Read a snapshot's __meta__ without touching the state arrays —
    cheap enough for the CLI's --resume to pick capacity overrides and
    for faultplan_lint's cross-check. Raises on a layout-generation
    mismatch (shape metadata from another layout is meaningless)."""
    if not path.endswith(".npz"):
        path = path + ".npz"
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"]))
    _check_layout(meta)
    return meta


def latest_checkpoint(prefix: str) -> str | None:
    """Newest snapshot (by recorded resume time) among files written
    as f"{prefix}.{time_ns}.npz" — the spelling both run_windows and
    the supervisor use. Returns None when no snapshot matches; skips
    files whose time suffix does not parse (never another run's)."""
    import glob

    best, best_t = None, -1
    for p in glob.glob(f"{prefix}.*.npz"):
        stem = p[len(prefix) + 1:-len(".npz")]
        try:
            t = int(stem)
        except ValueError:
            continue
        if t > best_t:
            best, best_t = p, t
    return best


def load_leaves(path: str) -> tuple[dict, dict]:
    """CRC- and layout-verified raw leaves: {keystr: np.ndarray} plus
    the __meta__ dict. load() builds a same-shape Sim from these; the
    escalation transplanter (faults/escalate.py) pads them into a
    grown template instead. A CRC failure names the exact leaf."""
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"]))
        _check_layout(meta)
        crcs = meta.get("crc32", {})  # absent in older snapshots
        leaves = {}
        for key in z.files:
            if key == "__meta__":
                continue
            arr = z[key]
            if key in crcs and _crc(arr) != crcs[key]:
                raise ValueError(
                    f"snapshot leaf {key} fails its CRC32 — snapshot "
                    f"is corrupt, refuse to resume")
            leaves[key] = arr
    return leaves, meta


def save_salvage(path: str, leaves: dict, meta: dict) -> str:
    """Write a raw-leaves artifact (the lane-surgery output of
    faults/escalate.py extract_lane) with the same atomic tmp + rename
    + dir-fsync discipline and per-leaf CRC32 as save(). The artifact
    reads back through load_leaves(); meta rides verbatim plus the
    layout stamp and a kind marker so tooling can tell a salvage slice
    from a resumable snapshot."""
    meta = dict(meta)
    meta.setdefault("layout", LAYOUT_VERSION)
    meta["kind"] = "lane_salvage"
    leaves = {k: np.asarray(v) for k, v in leaves.items()}
    meta["keys"] = sorted(leaves)
    meta["crc32"] = {k: _crc(v) for k, v in leaves.items()}
    if not path.endswith(".npz"):
        path = path + ".npz"
    d = os.path.dirname(os.path.abspath(path))
    fd, tmp = tempfile.mkstemp(prefix=".salvage.", suffix=".tmp", dir=d)
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez_compressed(f, __meta__=json.dumps(meta), **leaves)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        _fsync_dir(d)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


# leaf-key prefixes -> the capacity knob that sizes them, for shape
# mismatch diagnostics (the knob names match NetConfig fields and the
# loader's override keys, so the message is directly actionable)
_KNOB_OF_CAPACITY = {
    "event_capacity": "event_capacity",
    "outbox_capacity": "outbox_capacity",
    "router_ring": "router_ring",
    "num_hosts": "host count",
}


def _shape_mismatch_msg(key, arr, t, meta) -> str:
    msg = (f"snapshot leaf {key} is {arr.shape}/{arr.dtype}, "
           f"template expects {t.shape}/{t.dtype} (config mismatch)")
    caps = meta.get("capacities")
    if caps:
        # name the knob(s) whose recorded value explains the leaf —
        # "config mismatch" alone sends the operator diffing configs;
        # "snapshot was taken at event_capacity=512, this build has
        # 128" sends them straight to the flag
        diffs = [f"snapshot {k}={v}" for k, v in sorted(caps.items())
                 if isinstance(v, int) and (v in arr.shape)
                 and (v not in t.shape)]
        if diffs:
            msg += ("; " + ", ".join(diffs)
                    + " — rebuild with matching capacities or resume "
                      "with --auto-grow")
    return msg


def load(path: str, template_sim):
    """Rebuild a Sim from a snapshot. `template_sim` supplies the
    pytree structure (build the bundle with the SAME config first);
    every array is checked against the template's shape and dtype,
    and against the stored CRC32 when the snapshot carries one. Every
    refusal names the exact leaf (and, for shape mismatches, the
    capacity knob recorded at save time) instead of a generic
    config-mismatch shrug."""
    if not path.endswith(".npz"):
        path = path + ".npz"
    stored, meta = load_leaves(path)
    flat, _ = jax.tree_util.tree_flatten_with_path(template_sim)
    leaves = []
    for pth, tleaf in flat:
        key = jax.tree_util.keystr(pth)
        if key not in stored:
            raise ValueError(f"snapshot missing leaf {key} "
                             f"(config mismatch?)")
        arr = stored[key]
        t = np.asarray(tleaf)
        if arr.shape != t.shape or arr.dtype != t.dtype:
            raise ValueError(_shape_mismatch_msg(key, arr, t, meta))
        leaves.append(jax.numpy.asarray(arr))
    treedef = jax.tree_util.tree_structure(template_sim)
    sim = jax.tree_util.tree_unflatten(treedef, leaves)
    return sim, meta["time_ns"], meta["extra"]


def replan_shards(path: str, new_shards: int, *,
                  template_sim=None, out_path: str | None = None) -> str:
    """Re-partition a snapshot onto a `new_shards`-wide mesh. State
    arrays are saved in GLOBAL layout, so the re-partition is a
    verified metadata restamp, not a data shuffle — exactly why device
    loss costs a resume, not a run (parallel/elastic.py module doc):

    1. validate: new_shards is a power of two >= 1 that divides the
       snapshot's host count;
    2. verify: every leaf's CRC32 (load_leaves), and — when the
       snapshot carries a verified-state ledger AND the caller
       supplies the template to rebuild the pytree — the per-shard
       digests recomputed at the OLD width must match the stamped
       ones (a corrupt snapshot must not silently become the resume
       point of a degraded run);
    3. restamp: meta.shards = new_shards, with the replan recorded in
       meta.elastic.replans (old -> new), and per-shard digests
       recomputed at the NEW width when the template is given.

    Returns the written path (out_path, default: in place)."""
    if not path.endswith(".npz"):
        path = path + ".npz"
    new_shards = int(new_shards)
    if new_shards < 1 or (new_shards & (new_shards - 1)):
        raise ValueError(
            f"replan_shards: new_shards={new_shards} must be a power "
            f"of two >= 1 (the bucket lattice and AOT program keys "
            f"are pow2)")
    leaves, meta = load_leaves(path)
    hosts = int(meta.get("capacities", {}).get("num_hosts", 0))
    if hosts and hosts % new_shards:
        raise ValueError(
            f"replan_shards: num_hosts={hosts} not divisible by "
            f"{new_shards} shards")
    old_shards = int(meta.get("shards", 1))
    el = dict(meta.get("elastic") or {})
    if template_sim is not None:
        from shadow_tpu.parallel.elastic import shard_digests

        sim, _, _ = load(path, template_sim)
        stamped = el.get("shard_digests")
        if stamped:
            fresh = shard_digests(sim, old_shards)
            if fresh != list(stamped):
                bad = [s for s, (a, b) in
                       enumerate(zip(fresh, stamped)) if a != b]
                raise ValueError(
                    f"replan_shards: per-shard digest mismatch at "
                    f"shard(s) {bad} — snapshot state disagrees with "
                    f"its verified-state ledger, refuse to replan")
        el["shard_digests"] = shard_digests(sim, new_shards)
    el.setdefault("replans", []).append(
        {"from": old_shards, "to": new_shards})
    meta["shards"] = new_shards
    meta["elastic"] = el
    out = out_path or path
    if not out.endswith(".npz"):
        out = out + ".npz"
    d = os.path.dirname(os.path.abspath(out))
    fd, tmp = tempfile.mkstemp(prefix=".replan.", suffix=".tmp", dir=d)
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez_compressed(f, __meta__=json.dumps(meta), **leaves)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, out)
        _fsync_dir(d)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return out


class _LoopPlan:
    """Resolved loop parameters shared by run_windows and
    prewarm_dispatch — one resolution rule so the program a prewarm
    persists is bit-for-bit the program a later run_windows loads."""

    __slots__ = ("cfg", "step", "end", "min_jump", "fault_fn",
                 "caller_fault_fn", "bulk_fn", "wpd", "adaptive",
                 "chunked", "shards", "caps")


def _resolve_loop(bundle, app_handlers, *, end_time, fault_fn, mesh,
                  mesh_axis, windows_per_dispatch, adaptive_jump,
                  sim=None):
    from shadow_tpu.net.build import (_resolve_bulk_fn, _resolve_caps,
                                      _resolve_fault_fn)
    from shadow_tpu.net.step import make_step_fn

    p = _LoopPlan()
    cfg = p.cfg = bundle.cfg
    # capability-trimmed variant (compile/specialize.py): same rule as
    # the whole-run factories — an opaque caller fault_fn disables it
    p.caps = _resolve_caps(bundle, fault_fn)
    p.step = make_step_fn(cfg, app_handlers, caps=p.caps)
    p.end = int(end_time if end_time is not None else cfg.end_time)
    p.min_jump = max(int(bundle.min_jump), 1)
    p.caller_fault_fn = fault_fn
    p.fault_fn = (fault_fn if fault_fn is not None
                  else _resolve_fault_fn(bundle, None))
    # honor the bundle's config-installed bulk pass (bundle.app_bulk,
    # net/bulk.py) exactly like the whole-run factories: bulk consumes
    # eligible hosts' windows in one vectorized pass, bit-identical
    # final state, far fewer fixpoint iterations — without it the
    # host-driven loop could never close the throughput gap to
    # engine.run no matter how many windows a dispatch amortizes
    p.bulk_fn = _resolve_bulk_fn(bundle, getattr(bundle, "app_bulk", None),
                                 None, caps=p.caps)
    wpd = (int(windows_per_dispatch) if windows_per_dispatch is not None
           else max(1, int(getattr(cfg, "windows_per_dispatch", 1) or 1)))
    if wpd < 1:
        raise ValueError(f"windows_per_dispatch must be >= 1, got {wpd}")
    p.wpd = wpd
    p.adaptive = (bool(adaptive_jump) if adaptive_jump is not None
                  else bool(getattr(cfg, "adaptive_jump", False)))
    # Causality tracing rides the chunked body even at K=1: the
    # advance-attribution latch lives in the wend_fn.explain path
    # (engine.make_chunk_body), not the host-clamped per-window body —
    # forcing the chunk driver keeps the attribution plane bit-
    # identical across every windows_per_dispatch, which the K1-vs-K64
    # identity contract requires (telemetry/causality.py).
    tracing = (getattr(sim if sim is not None else bundle.sim,
                       "causality", None) is not None)
    p.chunked = wpd > 1 or p.adaptive or tracing
    p.shards = 1 if mesh is None else mesh.shape[mesh_axis]
    return p


def _program_key_for(bundle, plan, sim, app_handlers, *, sharded,
                     exchange_capacity):
    """Canonical program key for this loop's dispatch function
    (compile/buckets.py), or None when the caller passed an opaque
    fault_fn — its closure constants are baked into the trace but
    invisible to the key, so warm serving would risk serving a
    program traced with someone else's constants."""
    if plan.caller_fault_fn is not None:
        return None
    import hashlib

    from shadow_tpu.compile import buckets
    from shadow_tpu.telemetry.export import fault_plan_digest

    fp = getattr(bundle, "fault_plan", None)
    extra = {"path": ("sharded_" if sharded else "")
             + ("chunk" if plan.chunked else "window")}
    if plan.caps is not None and plan.caps.key_extra() is not None:
        # trimmed variants key apart from their full twins (see
        # net.build._whole_run_key_fn); untrimmed builds share keys
        extra["caps"] = plan.caps.key_extra()
    if plan.adaptive:
        # the adaptive wend rule bakes the host->vertex map into the
        # traced pair mask (net.build.adaptive_jump_spec)
        voh = np.asarray(bundle.sim.net.vertex_of_host)
        extra["voh"] = hashlib.sha256(voh.tobytes()).hexdigest()[:16]
    census = buckets.kind_census(
        app_handlers, getattr(bundle, "app_bulk", None),
        fault_plan_digest=fault_plan_digest(fp) if fp is not None else None)
    shapes = buckets.shape_vector_for_sim(bundle.cfg, sim)
    return buckets.program_key(
        shapes, shards=plan.shards,
        chunk_windows=plan.wpd if plan.chunked else 1,
        adaptive=plan.adaptive, census=census, end_time=plan.end,
        min_jump=bundle.min_jump, exchange_capacity=exchange_capacity,
        extra=extra)


def _make_dispatch_fns(bundle, plan, sim, app_handlers, *, mesh,
                       mesh_axis, exchange_capacity, warm,
                       store=None, compile_info=None):
    """Build the loop's dispatch program — the chunked body or the
    per-window body, serial or sharded — and route it through the AOT
    store when warm serving is on. Returns (chunk_fn, one_window,
    key, raw_fn, example_args): exactly one of chunk_fn/one_window is
    non-None; raw_fn/example_args let prewarm_dispatch compile the
    identical program without executing it."""
    import jax.numpy as jnp

    from shadow_tpu.core import simtime
    from shadow_tpu.core.engine import (
        EngineStats,
        make_chunk_body,
        resolve_sparse_lanes,
        step_window,
    )
    from shadow_tpu.compile import serve
    from shadow_tpu.net.build import _caps_meta
    from shadow_tpu.parallel.elastic import make_sentinel_fn
    from shadow_tpu.telemetry.flows import make_flow_fn
    from shadow_tpu.telemetry.ring import make_telem_fn

    cfg = bundle.cfg
    key = None
    if warm or compile_info is not None:
        key = _program_key_for(bundle, plan, sim, app_handlers,
                               sharded=mesh is not None,
                               exchange_capacity=exchange_capacity)
    step, end, wpd = plan.step, plan.end, plan.wpd
    bulk_fn, fault_fn = plan.bulk_fn, plan.fault_fn
    if plan.chunked:
        from shadow_tpu.net.build import resolve_wend_fn

        # the adaptive rule needs the PLAN's record times; an opaque
        # caller fault_fn is only acceptable when the bundle carries
        # the plan it was derived from (resolve_wend_fn enforces)
        wend_fn = resolve_wend_fn(bundle, end, plan.adaptive,
                                  plan.caller_fault_fn)
        if mesh is not None:
            from shadow_tpu.parallel.shard import make_sharded_chunk

            raw = make_sharded_chunk(
                mesh, mesh_axis, bundle.sim, cfg, step,
                end_time=end, wend_fn=wend_fn, chunk_windows=wpd,
                exchange_capacity=exchange_capacity,
                bulk_fn=bulk_fn, fault_fn=fault_fn)
        else:
            telem_fn = make_telem_fn()  # trace-time no-op, telem None
            body = make_chunk_body(
                step, end_time=end, wend_fn=wend_fn, chunk_windows=wpd,
                emit_capacity=cfg.emit_capacity,
                lane_fn=lambda s: s.net.lane_id,
                bulk_fn=bulk_fn, fault_fn=fault_fn, telem_fn=telem_fn,
                sparse_lanes=resolve_sparse_lanes(cfg),
                flow_fn=make_flow_fn(),
                sentinel_fn=make_sentinel_fn())
            raw = jax.jit(body)
        example = (sim, EngineStats.create(),
                   jnp.asarray(0, simtime.DTYPE))
        chunk_fn = serve.maybe_warm(raw, key, enabled=warm, store=store,
                                    meta=_caps_meta(plan.caps),
                                    info=compile_info)
        return chunk_fn, None, key, raw, example
    if mesh is not None:
        from shadow_tpu.parallel.shard import make_sharded_window

        raw = make_sharded_window(
            mesh, mesh_axis, bundle.sim, cfg, step,
            exchange_capacity=exchange_capacity,
            bulk_fn=bulk_fn, fault_fn=fault_fn,
            donate=True)
    else:
        telem_fn = make_telem_fn()  # trace-time no-op, telem is None
        flow_fn = make_flow_fn()    # likewise when flows is None

        @partial(jax.jit, donate_argnums=(0,))
        def raw(sim, wstart, wend):
            stats = EngineStats.create()
            return step_window(sim, stats, step, wend,
                               emit_capacity=cfg.emit_capacity,
                               lane_id=sim.net.lane_id,
                               bulk_fn=bulk_fn, fault_fn=fault_fn,
                               telem_fn=telem_fn, wstart=wstart,
                               sparse_lanes=resolve_sparse_lanes(cfg),
                               flow_fn=flow_fn,
                               sentinel_fn=make_sentinel_fn())
    example = (sim, 0, plan.min_jump)
    one_window = serve.maybe_warm(raw, key, enabled=warm, store=store,
                                  meta=_caps_meta(plan.caps),
                                  info=compile_info)
    return None, one_window, key, raw, example


def prewarm_dispatch(bundle, app_handlers=(), *, end_time=None, sim=None,
                     mesh=None, mesh_axis: str = "hosts",
                     exchange_capacity=None, windows_per_dispatch=None,
                     adaptive_jump=None, store=None) -> dict:
    """Compile (or confirm already stored) the exact dispatch program
    run_windows would use for this bundle, WITHOUT executing a single
    window — the engine behind compile.serve.prewarm and the
    compcache_ctl `prewarm` subcommand. Returns the compile-info
    block ({key, hit, compile_s|load_s})."""
    from shadow_tpu.compile.store import default_store

    sim = sim if sim is not None else bundle.sim
    plan = _resolve_loop(bundle, app_handlers, end_time=end_time,
                         fault_fn=None, mesh=mesh, mesh_axis=mesh_axis,
                         windows_per_dispatch=windows_per_dispatch,
                         adaptive_jump=adaptive_jump, sim=sim)
    _, _, key, raw, example = _make_dispatch_fns(
        bundle, plan, sim, app_handlers, mesh=mesh, mesh_axis=mesh_axis,
        exchange_capacity=exchange_capacity, warm=False, store=store,
        compile_info={})
    st = store if store is not None else default_store()
    from shadow_tpu.net.build import _caps_meta

    _, info = st.get_or_compile(key, raw, example,
                                meta=_caps_meta(plan.caps))
    return info


def run_windows(bundle, app_handlers=(), *, end_time: int | None = None,
                start_time: int = 0, sim=None,
                checkpoint_every_ns: int | None = None,
                checkpoint_path: str | None = None,
                on_window=None, on_round=None, on_chunk=None,
                fault_fn=None, stats0=None, mesh=None,
                mesh_axis: str = "hosts",
                exchange_capacity: int | None = None,
                windows_per_dispatch: int | None = None,
                adaptive_jump: bool | None = None,
                feeder=None, warm_start: bool | None = None,
                compile_info: dict | None = None,
                dispatch_wrap=None):
    """Host-driven window loop with optional periodic snapshots —
    the checkpointing twin of engine.run (same advance rule,
    master.c:450-480). Returns (sim, stats, checkpoints) where
    checkpoints lists the saved (path, time_ns).

    `windows_per_dispatch` (default: cfg.windows_per_dispatch, 1)
    sets how many window rounds run on device per host barrier. At 1
    the loop dispatches one jitted step_window per round, exactly as
    before. At K > 1 it dispatches engine.make_chunk_body fori_loop
    chunks — fault rewrites, telemetry-ring stores, the sparse fast
    path and the sharded all-to-all all run INSIDE the chunk — and
    the host keeps ONE speculative chunk in flight: hooks, ring
    harvest and checkpoint device_gets for chunk N overlap the device
    executing chunk N+1 (a chunk dispatched past the end is a device
    no-op). Chunked dispatch trades hook/checkpoint granularity for
    dispatch amortization: cadences snap to chunk boundaries.

    `adaptive_jump` (default: cfg.adaptive_jump) derives each
    window's span from the LIVE latency/reliability tables instead of
    the boot-time bundle.min_jump (net.build.resolve_wend_fn) —
    fault plans that raise latencies let windows grow. Final state
    keeps all conservation/event counters; per-window counters and
    window counts differ wherever the partition into windows does.

    `on_window(sim, wend)` runs after every dispatch — pcap drains,
    heartbeats, progress hooks. `on_chunk(sim, wstats, wstart, wend,
    next_min)` additionally sees the dispatch's aggregate stats
    delta and times — the supervisor (faults/supervisor.py) hangs
    its health latches and window-counted checkpoint cadence off it;
    it may raise to abort the loop. `on_round` is the same hook's
    historical name (one dispatch == one round at K=1) and is called
    only when on_chunk is not given. `fault_fn` (faults.apply) is
    threaded into step_window. The bundle's config-installed bulk
    pass (bundle.app_bulk) rides every path — bit-identical final
    state, fewer fixpoint iterations, exactly as in the whole-run
    factories.

    `stats0` seeds the running totals (resume chains and escalation
    restarts carry processed-event counts across program rebuilds).
    `mesh` switches the window body to the shard_map harness
    (parallel.shard.make_sharded_window / make_sharded_chunk) over
    `mesh_axis` — same advance rule, same host-side loop, so
    supervision and checkpoints work identically multi-chip; state
    stays in global layout at the host boundary, so snapshots remain
    shard-count portable.

    The per-window path donates the sim argument to each dispatch
    (steady-state device allocation is one sim); the caller's input
    sim is copied once at entry and never consumed. The chunked path
    does NOT donate: the host still reads chunk N's sim while chunk
    N+1 executes — the two live pytrees are the double buffer that
    buys the overlap.

    `feeder` (inject.Feeder) streams an open-system injection trace
    into the sim's staging buffer (docs/9-injection.md). On entry
    feeder.sync(sim) reconciles against the (possibly
    checkpoint-restored) device staging state — a supervised resume
    replays nothing and drops nothing — then every dispatch boundary
    prunes merged entries and stages fresh ones at chunk granularity.
    The staging horizon bounds every window, so streamed runs are
    bit-identical to fully-staged ones; the chunked loop runs
    non-speculatively while events remain (the refill must land
    before the next dispatch) and falls back to the speculative
    double-buffer once the trace is exhausted.

    `warm_start` asks for the dispatch program from the persistent
    AOT store (compile/) instead of jitting inline: a stored program
    for this shape bucket loads in milliseconds where a fresh trace
    costs seconds-to-minutes. SHADOW_WARM_PROGRAMS=1/0 overrides the
    caller's choice; a store miss compiles and persists for the next
    run; any store trouble falls back to the inline jit
    (compile/serve.py). `compile_info`, when given, is filled with
    the manifest `compile` block ({key, warm, hit, load_s|compile_s})
    at the first dispatch — the supervisor threads it into the run
    manifest. An opaque caller `fault_fn` disables warm serving (its
    closure constants cannot be keyed).
    """
    import jax.numpy as jnp

    from shadow_tpu.core import simtime
    from shadow_tpu.core.engine import EngineStats

    plan = _resolve_loop(bundle, app_handlers, end_time=end_time,
                         fault_fn=fault_fn,
                         mesh=mesh, mesh_axis=mesh_axis,
                         windows_per_dispatch=windows_per_dispatch,
                         adaptive_jump=adaptive_jump, sim=sim)
    cfg, end, min_jump = plan.cfg, plan.end, plan.min_jump
    chunked, wpd, adaptive = plan.chunked, plan.wpd, plan.adaptive
    shards = plan.shards
    # host-side twin of the record-time wend clamp (make_wend_fn /
    # engine.run): faults apply exactly at their timestamps, never
    # early because a window happened to cross one. Sorted by
    # np.unique, so searchsorted finds the next record past wstart.
    from shadow_tpu.net.build import plan_times

    _pt = plan_times(bundle)

    def _clamp_record(wstart, wend):
        if _pt is None:
            return wend
        i = int(np.searchsorted(_pt, wstart, side="right"))
        return min(wend, int(_pt[i])) if i < len(_pt) else wend
    sim = sim if sim is not None else bundle.sim
    hook = on_chunk if on_chunk is not None else on_round

    from shadow_tpu.compile import serve as _serve

    warm = _serve.warm_enabled(default=bool(warm_start))
    chunk_fn, one_window, _key, _raw, _ex = _make_dispatch_fns(
        bundle, plan, sim, app_handlers, mesh=mesh, mesh_axis=mesh_axis,
        exchange_capacity=exchange_capacity, warm=warm,
        compile_info=compile_info)
    if dispatch_wrap is not None:
        # device-loss guard / chaos poison (parallel/elastic.py): the
        # wrap sees every dispatch the loop issues — XLA device errors
        # re-raise as typed DeviceLossError for the supervisor's
        # degradation ladder
        if chunk_fn is not None:
            chunk_fn = dispatch_wrap(chunk_fn)
        if one_window is not None:
            one_window = dispatch_wrap(one_window)

    def _elastic_stamp(s):
        # verified-state ledger: stamped only on sentinel-carrying
        # runs (the opt-in that funds the per-checkpoint digest cost)
        if getattr(s, "sentinel", None) is None:
            return None
        return elastic_meta(s, shards)

    total = stats0 if stats0 is not None else EngineStats.create()
    saved = []
    next_ckpt = (start_time + checkpoint_every_ns
                 if checkpoint_every_ns else None)
    wstart = max(int(jnp.min(sim.events.min_time())), start_time)
    if feeder is not None:
        if getattr(sim, "inject", None) is None:
            raise ValueError(
                "run_windows(feeder=...) needs a sim with injection "
                "staging attached (NetConfig.inject_lanes > 0 or "
                "inject.attach)")
        # reconcile against (possibly checkpoint-restored) device
        # staging state, then stage the first batch; staged events
        # join the first-window rule so a trace-only run (empty
        # queue) still starts at the trace's first timestamp
        feeder.sync(sim)
        sim = feeder.refill(sim)
        wstart = max(min(int(jnp.min(sim.events.min_time())),
                         feeder.pending_min()), start_time)

    def _stall_msg(t):
        return (f"injection stalled at t={t}: all {sim.inject.lanes} "
                f"staging lanes hold events at one timestamp and more "
                f"remain in the trace — raise --inject-lanes (or "
                f"NetConfig.inject_lanes) past the largest "
                f"same-timestamp burst")

    if chunked:
        if wstart > end:
            return sim, total, saved
        if feeder is not None:
            # Streaming loop: non-speculative while trace events
            # remain — each refill must land in the staging planes
            # BEFORE the next dispatch reads them. Falls through to
            # the speculative double-buffer for the closed-loop tail
            # once the trace is fully staged and merged.
            prev_state = (None, None)
            while not feeder.done:
                csim, cstats, cnext = chunk_fn(
                    sim, EngineStats.create(),
                    jnp.asarray(wstart, simtime.DTYPE))
                # the device's next_min only sees the queue and the
                # STAGED events; an un-staged trace event below it
                # must pull the next window start back or it would
                # merge late once staged (measured before the refill
                # moves the horizon)
                nm = min(int(cnext), feeder.horizon)
                total = total.add(cstats)
                wend_c = min(nm, end + 1)
                if (next_ckpt is not None and checkpoint_path is not None
                        and nm >= next_ckpt and nm <= end):
                    p = save(f"{checkpoint_path}.{nm}.npz", csim,
                             time_ns=nm, shards=shards,
                             elastic=_elastic_stamp(csim))
                    saved.append((p, nm))
                    while next_ckpt <= nm:
                        next_ckpt += checkpoint_every_ns
                if on_window is not None:
                    on_window(csim, wend_c)
                if hook is not None:
                    hook(csim, cstats, wstart, wend_c, nm)
                sim = feeder.refill(csim, nm)
                if nm >= simtime.INVALID:
                    # quiet queue: jump to the next staged event
                    nm = feeder.pending_min()
                if nm > end or nm >= simtime.INVALID:
                    return sim, total, saved
                if not feeder.done and feeder.horizon <= nm:
                    raise RuntimeError(_stall_msg(nm))
                if (nm, feeder.cursor) == prev_state:
                    raise RuntimeError(_stall_msg(nm))
                prev_state = (nm, feeder.cursor)
                wstart = nm
            if wstart > end:
                return sim, total, saved
        cur = chunk_fn(sim, EngineStats.create(),
                       jnp.asarray(wstart, simtime.DTYPE))
        cur_start = wstart
        while True:
            csim, cstats, cnext = cur
            # Speculative one-ahead dispatch on chunk N's as-yet-
            # unresolved outputs: the int(cnext) below blocks on chunk
            # N while chunk N+1 is already executing, so every host-
            # side read (stats, harvest, checkpoint device_get,
            # manifest writes in hooks) overlaps device compute. Past
            # the end the chunk no-ops, so the last speculation is
            # harmless and discarded.
            nxt = chunk_fn(csim, EngineStats.create(), cnext)
            nm = int(cnext)
            total = total.add(cstats)
            wend_c = min(nm, end + 1)
            if (next_ckpt is not None and checkpoint_path is not None
                    and nm >= next_ckpt and nm <= end):
                p = save(f"{checkpoint_path}.{nm}.npz", csim,
                         time_ns=nm, shards=shards,
                         elastic=_elastic_stamp(csim))
                saved.append((p, nm))
                while next_ckpt <= nm:
                    next_ckpt += checkpoint_every_ns
            if on_window is not None:
                on_window(csim, wend_c)
            if hook is not None:
                hook(csim, cstats, cur_start, wend_c, nm)
            if nm >= simtime.INVALID or nm > end:
                return csim, total, saved
            cur, cur_start = nxt, nm

    # Per-window path: one dispatch per round. one_window donates its
    # sim argument, so the caller's pytree must not be consumed — copy
    # once at entry (supervisor retries re-enter with bundle.sim).
    sim = jax.tree_util.tree_map(jnp.copy, sim)
    while wstart <= end:
        if (next_ckpt is not None and wstart >= next_ckpt
                and checkpoint_path is not None):
            p = save(f"{checkpoint_path}.{wstart}.npz", sim,
                     time_ns=wstart, shards=shards,
                     elastic=_elastic_stamp(sim))
            saved.append((p, wstart))
            next_ckpt += checkpoint_every_ns
        wend = _clamp_record(wstart, min(wstart + min_jump, end + 1))
        if feeder is not None:
            # prune merged (everything < this window's start), stage
            # fresh events, and keep the window inside the horizon
            sim = feeder.refill(sim, wstart)
            wend = min(wend, feeder.horizon)
            if wend <= wstart:
                raise RuntimeError(_stall_msg(wstart))
        sim, stats, next_min = one_window(sim, wstart, wend)
        total = total.replace(
            events_processed=total.events_processed + stats.events_processed,
            micro_steps=total.micro_steps + stats.micro_steps,
            windows=total.windows + 1,
            fastpath_hit=total.fastpath_hit + stats.fastpath_hit,
            fastpath_miss=total.fastpath_miss + stats.fastpath_miss,
            bulk_events=total.bulk_events + stats.bulk_events,
        )
        nm = int(next_min)
        if feeder is not None:
            # same horizon rule as the chunked streaming loop: the
            # first un-staged trace event bounds the next window start
            nm = min(nm, feeder.horizon)
        if on_window is not None:
            on_window(sim, wend)
        if hook is not None:
            hook(sim, stats, wstart, wend, nm)
        if nm >= simtime.INVALID:
            if feeder is not None and not feeder.done:
                # queue and staging both drained, but the trace still
                # holds events: stage the next batch and jump there
                sim = feeder.refill(sim, nm)
                nm = feeder.pending_min()
                if nm < simtime.INVALID:
                    wstart = nm
                    continue
            break
        wstart = nm
    return sim, total, saved
