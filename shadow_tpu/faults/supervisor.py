"""Run supervisor: window loop + health latches + checkpoint-backed
recovery + capacity escalation + preemption-safe resume chains.

The CLI's `--supervise` mode runs the simulation through here instead
of the one-shot jitted runner. At every dispatch barrier — one window,
or one K-window chunk when cfg.windows_per_dispatch > 1 (the chunked
loop in checkpoint.run_windows) — the supervisor inspects the sticky
latches (faults/health.py) plus its own stall / time-regression
telemetry; every N *windows* it snapshots the sim
(utils/checkpoint.py — atomic + checksummed, so a trip mid-save can
never leave a poisoned resume point). Recovery has three distinct
paths, accounted separately:

- **escalation** (`escalation=EscalationPolicy(...)`): a fatal
  *capacity* latch (event queue / outbox / router ring overflow) is
  healed, not retried — the tripped knob doubles, the bundle rebuilds
  at the grown shapes (bundle.rebuild, installed by config/loader),
  and the last clean pre-trip snapshot transplants into the padded
  arrays (faults/escalate.py). Escalation restarts do NOT consume the
  retry budget and do not back off: the restart is a fix, not a
  gamble.
- **retry**: everything else (stall, regression, exhausted grow
  budget, no rebuild hook) restores the last good snapshot, backs off
  exponentially, and retries up to max_retries before giving up with
  a structured failure report. Retrying a *deterministic* trip
  reproduces it — the budget exists for host-process crashes and
  transient device loss.
- **preemption** (`stop=callable`): when the flag reads true at a
  round barrier the supervisor takes one final atomic checkpoint and
  raises out with `preempted=True` — the CLI maps it to its own exit
  code and a manifest carrying the `resume_of` chain id, and
  `--resume` continues the chain later, under any shard count
  (snapshots hold global-layout arrays).

Checkpoint cadence is counted in windows, not sim-ns: window length
tracks min_jump, so N windows is a stable amount of device work
regardless of the topology's latency floor. Engine-stat totals ride
every snapshot's `extra` (escalation-aware carryover: the pre-trip
counters live in a different compiled program than the post-heal
ones), so a resumed chain reports cumulative work, not the last
attempt's slice.
"""

from __future__ import annotations

import dataclasses
import time as _time
import uuid
from typing import Optional

import numpy as np

from shadow_tpu.core import simtime
from shadow_tpu.core.engine import EngineStats
from shadow_tpu.faults import escalate as escalate_mod
from shadow_tpu.faults import health as health_mod
from shadow_tpu.parallel import elastic as elastic_mod
from shadow_tpu.utils import checkpoint as ckpt


class LatchTrip(RuntimeError):
    """A fatal health latch fired mid-run. Carries the sim state at the
    trip so the failure path can still dump diagnostics (object counts,
    final counters for the run manifest)."""

    def __init__(self, health: health_mod.RunHealth, sim=None):
        self.health = health
        self.sim = sim
        msgs = "; ".join(m for s, m in health.diagnostics() if s == "fatal")
        super().__init__(msgs or "health latch tripped")


class Preempted(RuntimeError):
    """The stop flag was set at a round barrier; a final checkpoint
    was taken before raising."""

    def __init__(self, path: str, time_ns: int, sim=None):
        self.path = path
        self.time_ns = time_ns
        self.sim = sim
        super().__init__(f"preempted at t={time_ns}, checkpoint {path}")


class DeadlineExceeded(Preempted):
    """The per-run wallclock deadline (max_run_wallclock) passed at a
    round barrier: same final-snapshot discipline as preemption, but
    latched as a `deadline` health fault — the run did not hang, it
    ran out of budget. The fleet watchdog (shadow_tpu/fleet) is the
    out-of-process counterpart for runs wedged *inside* a device call,
    where no round barrier ever comes back to the host."""

    def __init__(self, path: str, time_ns: int, sim=None,
                 elapsed_s: float = 0.0):
        super().__init__(path, time_ns, sim)
        self.elapsed_s = elapsed_s


@dataclasses.dataclass(frozen=True)
class LaneIncident:
    """One quarantined lane, detected at a chunk barrier of a packed
    (lane-isolated) run. Carries the blast-radius evidence plus the
    requeue context the fleet consumes (fleet/scenario.py packed
    jobs): which capacity knobs the trip bits say to regrow, and
    where the lane's salvage slice landed."""

    lane: int
    time_ns: int          # window barrier the device quarantined at
    detected_ns: int      # chunk barrier the host noticed it at
    trip_bits: int
    trip: tuple           # TRIP_* names (core.lanes.trip_names)
    flushed: int          # pending events flushed when frozen
    salvage: Optional[str] = None       # lane-surgery artifact path
    salvaged_from: Optional[str] = None  # snapshot the slice came from
    regrow: dict = dataclasses.field(default_factory=dict)

    def as_dict(self) -> dict:
        return {"lane": self.lane, "time_ns": self.time_ns,
                "detected_ns": self.detected_ns,
                "trip_bits": self.trip_bits, "trip": list(self.trip),
                "flushed": self.flushed, "salvage": self.salvage,
                "salvaged_from": self.salvaged_from,
                "regrow": dict(self.regrow)}


@dataclasses.dataclass
class SupervisorResult:
    ok: bool
    sim: object
    stats: object                      # EngineStats, cumulative chain
    health: health_mod.RunHealth       # final latch snapshot
    attempts: int = 1
    resumed_from: Optional[str] = None  # snapshot path of the last resume
    checkpoints: tuple = ()            # (path, time_ns) saved, all attempts
    # accounting split (the --retries budget must not be consumed by
    # successful self-healing):
    retries_used: int = 0              # failure retries, <= max_retries
    escalation_restarts: int = 0       # heals; unbounded by max_retries
    escalations: tuple = ()            # Escalation records, chain-wide
    preempted: bool = False
    deadline_exceeded: bool = False    # max_run_wallclock fired
    final_checkpoint: Optional[str] = None  # preemption's last snapshot
    run_id: Optional[str] = None
    resume_of: Optional[str] = None    # run_id of the chain predecessor
    # Dispatch accounting for the FINAL attempt (chunked window loop):
    # how many device dispatches the loop issued and how many windows
    # each executed. sum(dispatch_windows) == stats.windows for a
    # clean single-attempt, non-resumed run — the invariant
    # tools/telemetry_lint.py checks when a manifest embeds the list.
    dispatches: int = 0
    dispatch_windows: tuple = ()
    # Lane-isolated runs: every lane quarantined across the chain,
    # with salvage pointers — the fleet's requeue feed.
    lane_incidents: tuple = ()
    # Manifest `compile` block for the FINAL attempt's dispatch
    # program (compile/serve.py): {key, warm, hit, load_s|compile_s}.
    # None when the loop never dispatched or warm accounting was off.
    compile_info: Optional[dict] = None
    # Elastic degraded-mesh recovery (parallel/elastic.py): losses,
    # divergences, the ladder steps taken and the mesh transitions —
    # the manifest's `elastic` block. None when no ElasticPolicy was
    # installed and nothing tripped.
    elastic: Optional[dict] = None

    def failure_report(self) -> dict:
        rep = self.health.failure_report() if self.health is not None \
            else {"verdict": "preempted", "fatal": []}
        rep["attempts"] = self.attempts
        rep["resumed_from"] = self.resumed_from
        rep["retries_used"] = self.retries_used
        rep["escalation_restarts"] = self.escalation_restarts
        if self.escalations:
            rep["escalations"] = [e.as_dict() for e in self.escalations]
        if self.lane_incidents:
            rep["lane_incidents"] = [i.as_dict()
                                     for i in self.lane_incidents]
        if self.preempted:
            rep["verdict"] = "preempted"
            rep["final_checkpoint"] = self.final_checkpoint
        if self.deadline_exceeded:
            rep["verdict"] = "deadline"
            rep["final_checkpoint"] = self.final_checkpoint
        if self.elastic is not None:
            rep["elastic"] = dict(self.elastic)
        return rep


_STAT_KEYS = tuple(f.name for f in dataclasses.fields(EngineStats))


def _stats_get(wstats) -> dict:
    """Per-round EngineStats as host ints (one device_get)."""
    import jax

    return jax.device_get(wstats).as_dict()


def run_supervised(bundle, app_handlers=(), *, fault_fn=None,
                   end_time=None, checkpoint_path,
                   checkpoint_every_windows: int = 64,
                   max_retries: int = 2, backoff_s: float = 0.25,
                   stall_windows: int = 512,
                   log=None, on_window=None, on_round=None,
                   harvester=None, sleep=_time.sleep,
                   escalation: escalate_mod.EscalationPolicy | None = None,
                   rebuild=None, stop=None, resume_from=None,
                   max_run_wallclock: float | None = None,
                   run_id: str | None = None,
                   mesh=None, mesh_axis: str = "hosts",
                   exchange_capacity: int | None = None,
                   config_digest: str | None = None,
                   windows_per_dispatch: int | None = None,
                   adaptive_jump: bool | None = None,
                   feeder=None,
                   on_lane_quarantine=None,
                   warm_start: bool | None = None,
                   elastic: elastic_mod.ElasticPolicy | None = None,
                   dispatch_wrap=None,
                   on_mesh_change=None,
                   ) -> SupervisorResult:
    """Run bundle to end_time under supervision (host-driven window
    loop; serial by default, shard_map'd over `mesh` when given — the
    host regains control at every window barrier either way).

    `escalation` turns capacity trips into heals (see module doc);
    `rebuild(overrides) -> SimBundle` defaults to bundle.rebuild (set
    by config/loader.load). When escalation rebuilds, an explicitly
    passed `fault_fn` is dropped and re-resolved from the rebuilt
    bundle's installed plan — a closure over the old shapes would
    poison the new program. `stop()` is polled at every round barrier
    (preemption flag, set from a signal handler); `resume_from` is a
    snapshot path to continue a previous run's chain (grown-capacity
    snapshots transplant automatically). `max_run_wallclock` is a
    per-run wallclock budget in seconds, chain-wide (attempts and
    heals share it): when a round barrier finds it spent, the
    supervisor takes the preemption-style final snapshot and returns
    with `deadline_exceeded=True` plus a latched `deadline` health
    fault instead of running forever — a wedge *inside* a device call
    never reaches a barrier, which is what the fleet watchdog's
    out-of-process SIGKILL path is for. `on_round(sim, wstats,
    wstart, wend, next_min)` runs after the health check at each
    round barrier — the chaos harness samples its conservation ledger
    there. `log` is a callable taking one message string; `sleep` is
    injectable for tests.

    Lane-isolated runs (core/lanes.py attached): a CONTAINED lane
    quarantine is not fatal (faults/health.py), so the run keeps going
    while the supervisor performs checkpoint lane surgery at the
    detecting barrier — the sick lane's slice is cut out of the last
    clean snapshot (faults/escalate.py extract_lane) and written as a
    salvage artifact next to the checkpoints; `on_lane_quarantine`
    (callable taking one LaneIncident) fires once per lane, chain-wide
    — the fleet's requeue hook.

    `windows_per_dispatch` / `adaptive_jump` (default: the bundle
    cfg's knobs) select the chunked window loop
    (checkpoint.run_windows): at K windows per dispatch the
    supervisor's barrier — health latches, harvest, checkpoint
    cadence, stop/deadline polls, on_round — runs once per CHUNK on
    per-chunk aggregate stats plus the ring records. Streak and
    checkpoint cadences are counted in executed windows either way,
    so `checkpoint_every_windows` and `stall_windows` keep their
    meaning, quantized up to a chunk boundary; a chunk whose windows
    all processed zero events extends the stall streak by the whole
    chunk, but a mixed chunk resets it — pick stall_windows >= a few
    chunks.

    `elastic` (parallel/elastic.ElasticPolicy) arms degraded-mesh
    recovery: every dispatch is wrapped in guard_dispatch, so a dead
    chip (XLA device/transfer error, or a dispatch overrunning
    `dispatch_deadline_s`) surfaces as a typed DeviceLossError and
    steps the degradation ladder — retry the same mesh
    (`same_mesh_retries`), then shrink to the next-pow2-down survivor
    mesh (checkpoints hold global layout, so the snapshot replans with
    a digest-verified restamp), then fall back to serial — always
    resuming from the last VERIFIED checkpoint (saved with sentinel
    trips == 0, or pre-sentinel and therefore health-clean). A
    SHARD_DIVERGENCE latch (the cross-shard integrity sentinel,
    attach_sentinel) steps the SAME ladder: a shard whose replica of
    the replicated state diverged is treated like a failing chip.
    Ladder steps consume no failure retries (like escalation heals —
    the sim did nothing wrong) and are bounded by `max_losses`.
    `dispatch_wrap` composes INSIDE the guard (chaos poison injection
    sees the dispatch first, the classifier sees its error);
    `on_mesh_change(old_shards, new_shards, cause)` fires on every
    shrink/serial transition — the fleet's degraded-requeue hook."""

    def say(msg):
        if log is not None:
            log(msg)

    rebuild_fn = rebuild if rebuild is not None \
        else getattr(bundle, "rebuild", None)
    run_id = run_id or uuid.uuid4().hex[:12]
    t_chain0 = _time.monotonic()   # max_run_wallclock origin
    # Elastic recovery makes the mesh MUTABLE chain state: a ladder
    # step may shrink it (or drop to serial) between attempts.
    cur_mesh = mesh
    cur_shards = mesh.shape[mesh_axis] if mesh is not None else 1
    shards0 = cur_shards
    losses: list = []              # DeviceLossError records, chain-wide
    divergences: list = []         # sentinel trips, chain-wide
    ladder_steps: list = []        # one per loss/divergence handled
    same_mesh_used: dict = {}      # mesh width -> same-mesh retries spent

    total_saved = []
    attempt = 0
    retries_used = 0
    escalation_restarts = 0
    escalations: list = []
    grows_used = 0
    resume_sim = None
    resume_time = 0
    resumed_from = None
    resume_of = None
    base_stats = {}                    # chain totals at the resume point
    lane_incidents: list = []          # chain-wide, one per lane
    lanes_seen: set = set()            # lanes already surgeried

    if resume_from is not None:
        leaves, meta = ckpt.load_leaves(resume_from)
        resume_sim, resume_time, extra = escalate_mod.transplant(
            leaves, meta, bundle.sim)
        base_stats = dict(extra.get("stats", {}))
        resume_of = extra.get("run_id")
        escalations = [escalate_mod.Escalation.from_dict(d)
                       for d in extra.get("escalations", [])]
        grows_used = len(escalations)
        resumed_from = resume_from
        say(f"supervisor: resuming chain {resume_of or '?'} from "
            f"{resume_from} (t={resume_time})")

    def _ckpt_extra(acc: dict) -> dict:
        stats = {k: base_stats.get(k, 0) + acc.get(k, 0)
                 for k in _STAT_KEYS}
        return {"stats": stats, "run_id": run_id,
                "escalations": [e.as_dict() for e in escalations]}

    def _lane_surgery(h, detected_ns):
        """Record newly quarantined lanes (once per lane, chain-wide)
        and cut each lane's slice out of the last clean snapshot —
        every snapshot predates the trip (health precedes every save),
        so the salvage is the lane's best pre-corruption evidence."""
        if not h.lanes_total:
            return
        caps = ckpt.capacities_of_sim(bundle.sim)
        # resident programs (core/lanes.LaneAdmission): a lane with no
        # live lease holds no tenant — there is nothing to salvage or
        # requeue, and the lease table (fleet/admission.py) owns the
        # lane's lifecycle; raising an incident for it would fabricate
        # a tenant failure out of an empty vessel
        inactive = {d["lane"] for d in getattr(h, "admission", ())
                    if not d.get("active")}
        for d in h.lanes:
            if not d.get("quarantined") or d["lane"] in lanes_seen:
                continue
            if d["lane"] in inactive:
                lanes_seen.add(d["lane"])
                continue
            lanes_seen.add(d["lane"])
            bits = int(d.get("trip_bits", 0))
            salvage, src = None, None
            if total_saved:
                src = total_saved[-1][0]
                try:
                    leaves, meta = ckpt.load_leaves(src)
                    ll, lm = escalate_mod.extract_lane(
                        leaves, meta, d["lane"], h.lanes_total)
                    lm["trip_bits"] = bits
                    lm["trip"] = list(d.get("trip", []))
                    lm["quarantined_at_ns"] = d.get("quarantined_at_ns")
                    salvage = ckpt.save_salvage(
                        f"{checkpoint_path}.lane{d['lane']}.salvage",
                        ll, lm)
                except (OSError, ValueError, KeyError) as e:
                    say(f"supervisor: lane {d['lane']} salvage "
                        f"failed: {e}")
            inc = LaneIncident(
                lane=int(d["lane"]),
                time_ns=int(d.get("quarantined_at_ns") or 0),
                detected_ns=int(detected_ns), trip_bits=bits,
                trip=tuple(d.get("trip", ())),
                flushed=int(d.get("flushed", 0)),
                salvage=salvage, salvaged_from=src,
                regrow=escalate_mod.plan_lane_regrow(bits, caps))
            lane_incidents.append(inc)
            say(f"supervisor: lane {inc.lane} quarantined at "
                f"t={inc.time_ns} (trip={list(inc.trip)}), "
                f"{inc.flushed} event(s) flushed"
                + (f"; salvage {salvage}" if salvage
                   else "; no snapshot to salvage"))
            if on_lane_quarantine is not None:
                on_lane_quarantine(inc)

    def _verified_snapshot(limit_ns: int | None = None):
        """Newest checkpoint the elastic ladder may resume from:
        its elastic stamp (utils/checkpoint.elastic_meta) shows zero
        sentinel trips — or predates the sentinel entirely, in which
        case the health check that preceded the save is the verifier.
        `limit_ns` (a divergence's verified_through) additionally caps
        the resume time. Returns (path, time_ns, meta) or None."""
        for path, t in reversed(total_saved):
            if limit_ns is not None and t > limit_ns:
                continue
            try:
                _, meta = ckpt.load_leaves(path)
            except (OSError, ValueError, KeyError) as e:
                say(f"supervisor: skipping unreadable snapshot "
                    f"{path}: {e}")
                continue
            el = meta.get("elastic")
            rep = el.get("sentinel") if isinstance(el, dict) else None
            if rep and rep.get("trips"):
                continue
            return path, t, meta
        return None

    def _elastic_block():
        if elastic is None and not losses and not divergences:
            return None
        return {
            "policy": elastic.as_dict() if elastic is not None else None,
            "initial_shards": shards0,
            "final_shards": cur_shards,
            "losses": [dict(d) for d in losses],
            "divergences": [dict(d) for d in divergences],
            "ladder_steps": [dict(s) for s in ladder_steps],
            "mesh_transitions": [dict(s) for s in ladder_steps
                                 if s["from"] != s["to"]],
        }

    def _elastic_step(cause: str, shard: int, limit_ns=None):
        """One rung of the degradation ladder. Decides retry / shrink /
        serial, finds the verified resume point (replanning its shard
        stamp when the width changes), and mutates the chain's mesh
        state. Returns True when the chain should continue, False when
        the ladder is exhausted."""
        nonlocal cur_mesh, cur_shards, resume_sim, resume_time
        nonlocal resumed_from, base_stats
        if len(losses) + len(divergences) > elastic.max_losses:
            say(f"supervisor: elastic budget exhausted "
                f"({elastic.max_losses} losses)")
            return False
        # --- decide the rung ---------------------------------------
        if same_mesh_used.get(cur_shards, 0) < elastic.same_mesh_retries:
            same_mesh_used[cur_shards] = \
                same_mesh_used.get(cur_shards, 0) + 1
            action, new_mesh, new_shards = "retry", cur_mesh, cur_shards
        elif (elastic.allow_shrink and cur_mesh is not None
                and cur_shards > max(elastic.min_shards, 1)):
            new_mesh, new_shards = elastic_mod.survivor_mesh(
                cur_mesh, mesh_axis, shard)
            if new_mesh is None or new_shards < elastic.min_shards:
                if not elastic.allow_serial:
                    say("supervisor: survivors cannot carry a mesh and "
                        "serial fallback is disabled")
                    return False
                action, new_mesh, new_shards = "serial", None, 1
            else:
                action = "shrink"
        elif elastic.allow_serial and cur_mesh is not None:
            action, new_mesh, new_shards = "serial", None, 1
        else:
            say(f"supervisor: ladder exhausted at {cur_shards} "
                f"shard(s) ({cause})")
            return False
        # --- verified resume point ---------------------------------
        found = _verified_snapshot(limit_ns)
        if found is not None:
            path, t, _meta = found
            if new_shards != cur_shards:
                try:
                    # digest-verified restamp: recomputes the per-shard
                    # sha256 ledger at the OLD width against the stamp,
                    # then restamps at the NEW width
                    path = ckpt.replan_shards(path, new_shards,
                                              template_sim=bundle.sim)
                except (ValueError, OSError, KeyError) as e:
                    say(f"supervisor: replan of {path} failed ({e}); "
                        f"rebooting at {new_shards} shard(s)")
                    path = None
            if path is not None:
                resume_sim, resume_time, extra = ckpt.load(path,
                                                           bundle.sim)
                base_stats = dict(extra.get("stats", {}))
                resumed_from = path
            else:
                resume_sim, resume_time, base_stats = None, 0, {}
                t = 0
        else:
            say("supervisor: no verified snapshot, rebooting from t=0")
            resume_sim, resume_time, base_stats = None, 0, {}
            t = 0
        ladder_steps.append({
            "action": action, "cause": cause, "shard": int(shard),
            "from": cur_shards, "to": new_shards,
            "resume_time_ns": int(t), "attempt": attempt,
        })
        say(f"supervisor: elastic {action} ({cause}, shard {shard}): "
            f"{cur_shards} -> {new_shards} shard(s), resuming at "
            f"t={int(t)}")
        if new_shards != cur_shards and on_mesh_change is not None:
            on_mesh_change(cur_shards, new_shards, cause)
        cur_mesh, cur_shards = new_mesh, new_shards
        return True

    def _wrap_dispatch(fn):
        """Compose the caller's dispatch_wrap (chaos poison — it must
        see the dispatch first so its injected error reaches the
        classifier) inside the device-loss guard."""
        if dispatch_wrap is not None:
            fn = dispatch_wrap(fn)
        if elastic is not None:
            fn = elastic_mod.guard_dispatch(
                fn, shards=cur_shards,
                deadline_s=elastic.dispatch_deadline_s)
        return fn

    while True:
        attempt += 1
        # Per-attempt telemetry the chunk closure mutates.
        tele = {"zero_streak": 0, "worst_streak": 0, "regressed": False,
                "wstart": None, "since_ckpt": 0, "acc": {},
                "dispatch_windows": []}
        # Filled by run_windows' warm wrapper at the first dispatch of
        # this attempt; the FINAL attempt's block lands in the result
        # (an escalation restart compiles a new program — that is the
        # one the manifest should report).
        cinfo: dict = {}

        def _on_chunk(sim, wstats, wstart, wend, next_min):
            tele["wstart"] = wstart
            ws = _stats_get(wstats)
            for k, v in ws.items():
                tele["acc"][k] = tele["acc"].get(k, 0) + v
            tele["dispatch_windows"].append(ws["windows"])
            # Streaks count executed WINDOWS (not dispatches), so the
            # stall limit keeps its meaning at any chunk size — a
            # whole-chunk zero extends the streak by the chunk's
            # window count.
            if ws["events_processed"] == 0:
                tele["zero_streak"] += ws["windows"]
                tele["worst_streak"] = max(tele["worst_streak"],
                                           tele["zero_streak"])
            else:
                tele["zero_streak"] = 0
            # Runahead may legally schedule inside the current window
            # (next_min < wend); only a start-regression is corrupt.
            if next_min < wstart:
                tele["regressed"] = True
            if harvester is not None:
                harvester.drain(sim)
            h = _gather(sim)
            # Lane surgery BEFORE the fatal check: even the
            # all-lanes-quarantined abort should leave salvage behind.
            _lane_surgery(h, wend)
            if h.fatal:
                # before the user hooks on purpose: a tripped round's
                # state is corrupt and will be replayed after the heal
                # — observers should never see it as a completed round
                raise LatchTrip(h, sim)
            # Health precedes every save: snapshots are always clean,
            # which is what makes escalation transplants exact.
            tele["since_ckpt"] += ws["windows"]
            if (tele["since_ckpt"] >= checkpoint_every_windows
                    and next_min < simtime.INVALID):
                # Healthy at this barrier: snapshot resumes at next_min.
                p = ckpt.save(f"{checkpoint_path}.{next_min}", sim,
                              time_ns=next_min, shards=cur_shards,
                              config_digest=config_digest,
                              extra=_ckpt_extra(tele["acc"]))
                total_saved.append((p, next_min))
                tele["since_ckpt"] = 0
            if on_round is not None:
                on_round(sim, wstats, wstart, wend, next_min)
            if on_window is not None:
                on_window(sim, wend)
            # Preemption polls LAST: the round is complete and every
            # observer has seen it — the final snapshot's resume point
            # starts the next round, so a hook that never saw this one
            # would double- or under-count across the kill boundary.
            if stop is not None and stop() and next_min < simtime.INVALID:
                p = ckpt.save(f"{checkpoint_path}.{next_min}", sim,
                              time_ns=next_min, shards=cur_shards,
                              config_digest=config_digest,
                              extra=_ckpt_extra(tele["acc"]))
                total_saved.append((p, next_min))
                raise Preempted(p, next_min, sim)
            # The wallclock deadline uses the same final-snapshot
            # discipline as preemption (round complete, observers
            # saw it, state healthy) but latches as a health fault:
            # the caller learns the budget was the problem, and
            # --resume continues the chain.
            if max_run_wallclock is not None \
                    and next_min < simtime.INVALID:
                el = _time.monotonic() - t_chain0
                if el >= max_run_wallclock:
                    p = ckpt.save(f"{checkpoint_path}.{next_min}", sim,
                                  time_ns=next_min, shards=cur_shards,
                                  config_digest=config_digest,
                                  extra=_ckpt_extra(tele["acc"]))
                    total_saved.append((p, next_min))
                    raise DeadlineExceeded(p, next_min, sim,
                                           elapsed_s=el)

        def _gather(sim):
            return health_mod.gather(
                sim,
                window_start=tele["wstart"],
                stalled_windows=tele["worst_streak"],
                stall_limit=stall_windows,
                time_regression=tele["regressed"],
                # flow-ring overruns ride the same observability-
                # degraded warning: results stay exact, the flight
                # recorder has gaps (telemetry/flows.py)
                telemetry_lost=(harvester.records_lost
                                + getattr(harvester, "flow_lost", 0)
                                if harvester is not None else 0),
                trace_warnings=tuple(
                    getattr(feeder, "warnings", ()) or ()),
            )

        def _result(ok, sim, h, **kw):
            return SupervisorResult(
                ok=ok, sim=sim, health=h, attempts=attempt,
                resumed_from=resumed_from,
                checkpoints=tuple(total_saved),
                retries_used=retries_used,
                escalation_restarts=escalation_restarts,
                escalations=tuple(escalations),
                run_id=run_id, resume_of=resume_of,
                dispatches=len(tele["dispatch_windows"]),
                dispatch_windows=tuple(tele["dispatch_windows"]),
                lane_incidents=tuple(lane_incidents),
                compile_info=(dict(cinfo) if cinfo else None),
                elastic=_elastic_block(), **kw)

        try:
            sim, stats, _ = ckpt.run_windows(
                bundle, app_handlers,
                end_time=end_time,
                start_time=resume_time,
                sim=resume_sim,
                fault_fn=fault_fn,
                on_chunk=_on_chunk,
                stats0=(EngineStats.from_dict(base_stats)
                        if base_stats else None),
                mesh=cur_mesh, mesh_axis=mesh_axis,
                exchange_capacity=exchange_capacity,
                windows_per_dispatch=windows_per_dispatch,
                adaptive_jump=adaptive_jump,
                feeder=feeder,
                warm_start=warm_start,
                compile_info=cinfo,
                dispatch_wrap=(_wrap_dispatch
                               if (dispatch_wrap is not None
                                   or elastic is not None) else None),
            )
            if harvester is not None:
                harvester.drain(sim)
            h = _gather(sim)
            _lane_surgery(h, tele["wstart"] or 0)
            if h.fatal:
                raise LatchTrip(h, sim)
            return _result(True, sim, h, stats=stats)
        except DeadlineExceeded as d:
            say(f"supervisor: wallclock deadline after "
                f"{d.elapsed_s:.1f}s: {d}")
            h = dataclasses.replace(_gather(d.sim),
                                    deadline_exceeded=True)
            return _result(
                False, d.sim, h,
                stats=EngineStats.from_dict(
                    _ckpt_extra(tele["acc"])["stats"]),
                deadline_exceeded=True, final_checkpoint=d.path)
        except Preempted as p:
            say(f"supervisor: {p}")
            # the preempting round passed its health check before the
            # final save — report that healthy snapshot, not a guess
            return _result(
                False, p.sim, _gather(p.sim),
                stats=EngineStats.from_dict(
                    _ckpt_extra(tele["acc"])["stats"]),
                preempted=True, final_checkpoint=p.path)
        except elastic_mod.DeviceLossError as loss:
            say(f"supervisor: device loss on attempt {attempt}: {loss}")
            if elastic is None:
                raise
            losses.append(dict(loss.as_dict(), attempt=attempt,
                               mesh=cur_shards))
            if _elastic_step("device_lost", loss.shard):
                continue  # a ladder step consumes no retry, no backoff
            h = health_mod.RunHealth(
                device_lost=len(losses),
                lost_shard=loss.shard,
                device_lost_cause=loss.cause)
            return _result(False, None, h, stats=None)
        except LatchTrip as trip:
            say(f"supervisor: latch trip on attempt {attempt}: {trip}")
            if elastic is not None and trip.health.shard_divergence:
                # the sentinel's SDC screen: a shard whose replica of
                # the replicated state diverged is a failing chip —
                # step the SAME ladder, but the resume point must also
                # predate the trip's verified_through (nothing after it
                # is trusted)
                divergences.append({
                    "fault": "SHARD_DIVERGENCE",
                    "shard": int(trip.health.divergent_shard),
                    "tripped_at_ns": int(trip.health.sentinel_tripped_at),
                    "verified_through_ns":
                        int(trip.health.sentinel_verified_through),
                    "attempt": attempt, "mesh": cur_shards,
                })
                if _elastic_step(
                        "shard_divergence", trip.health.divergent_shard,
                        limit_ns=trip.health.sentinel_verified_through):
                    continue
                return _result(False, trip.sim, trip.health, stats=None)
            healed = False
            if escalation is not None and rebuild_fn is not None:
                try:
                    caps = ckpt.capacities_of_sim(bundle.sim)
                    t0 = total_saved[-1][1] if total_saved else 0
                    grow, events = escalate_mod.plan_growth(
                        trip.health, caps, escalation, grows_used,
                        time_ns=t0)
                    healed = True
                except (ValueError, escalate_mod.GrowBudgetExceeded) as e:
                    say(f"supervisor: escalation unavailable: {e}")
            if healed:
                for ev in events:
                    say(f"supervisor: escalating {ev.knob} "
                        f"{ev.old} -> {ev.new} ({ev.latch})")
                    if harvester is not None:
                        harvester.mark_escalation(ev)
                old_telem = getattr(bundle.sim, "telem", None)
                old_inject = getattr(bundle.sim, "inject", None)
                old_lanes = getattr(bundle.sim, "lanes", None)
                old_caps = getattr(bundle, "caps", None)
                bundle = rebuild_fn(grow)
                if old_lanes is not None:
                    # re-attach lane isolation at the grown shapes
                    # FIRST (the telemetry ring sizes its per-lane
                    # planes off sim.lanes) so the transplant finds
                    # matching .lanes / overflow-plane leaves and
                    # containment survives the heal
                    from shadow_tpu.core import lanes as lanes_mod

                    bundle.sim = lanes_mod.attach(
                        bundle.sim, old_lanes.replicas,
                        stall_limit=old_lanes.stall_limit)
                if old_telem is not None:
                    from shadow_tpu.telemetry.ring import attach

                    bundle.sim = attach(bundle.sim,
                                        capacity=old_telem.capacity)
                if old_inject is not None:
                    # keep the staging buffer across the heal (same
                    # lane count) so the snapshot transplant below
                    # finds matching .inject leaves and the feeder's
                    # sync() resumes the trace without replay
                    from shadow_tpu.inject.staging import attach as \
                        inject_attach

                    bundle.sim = inject_attach(bundle.sim,
                                               old_inject.lanes)
                if old_caps is not None:
                    # re-derive the capability vector at the grown
                    # shapes (capacity growth cannot change it — the
                    # reliability table and handler set are capacity-
                    # independent) so the transplant below finds the
                    # snapshot's guard leaves in the template and the
                    # healed program stays trimmed under the same key
                    # discipline (compile/specialize.py)
                    from shadow_tpu.compile import specialize as \
                        specialize_mod

                    bundle = specialize_mod.apply(
                        bundle, app_handlers,
                        app_bulk=getattr(bundle, "app_bulk", None))
                # a caller-supplied fault_fn closes over the OLD
                # shapes; drop it — run_windows re-resolves from the
                # rebuilt bundle's installed plan
                fault_fn = None
                escalations.extend(events)
                grows_used += len(events)
                escalation_restarts += 1
                if total_saved:
                    path, t = total_saved[-1]
                    say(f"supervisor: transplanting {path} (t={t}) "
                        f"into grown shapes")
                    leaves, meta = ckpt.load_leaves(path)
                    resume_sim, resume_time, extra = \
                        escalate_mod.transplant(leaves, meta, bundle.sim)
                    base_stats = dict(extra.get("stats", {}))
                    resumed_from = path
                else:
                    say("supervisor: no snapshot yet, rebooting at "
                        "grown capacity")
                    resume_sim, resume_time = None, 0
                    base_stats = {}
                continue  # a heal consumes no retry and sleeps never
            if retries_used >= max_retries:
                # carry the tripped sim so the caller can still report
                # (object counts, manifest counters) from it
                return _result(False, trip.sim, trip.health, stats=None)
            retries_used += 1
            if total_saved:
                path, t = total_saved[-1]
                say(f"supervisor: resuming from {path} (t={t}) after "
                    f"backoff")
                resume_sim, resume_time, extra = ckpt.load(path, bundle.sim)
                base_stats = dict(extra.get("stats", {}))
                resumed_from = path
            else:
                say("supervisor: no snapshot yet, restarting from boot")
                resume_sim, resume_time = None, 0
                resumed_from = None
                base_stats = {}
            sleep(backoff_s * (2 ** (retries_used - 1)))
