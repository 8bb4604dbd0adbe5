"""Fleet data layers: journal durability, job state machine, backoff
determinism, manifest schema + lint. No engine, no worker processes —
the process-level recovery paths live in test_fleet_recovery.py.
"""

import json
import os

import pytest

from shadow_tpu.fleet import journal, manifest as manifest_mod, spec, state
from tests.conftest import load_tool


def _policy(**kw):
    kw.setdefault("max_attempts", 2)
    kw.setdefault("backoff_base_s", 0.0)
    kw.setdefault("backoff_cap_s", 0.0)
    return spec.FleetPolicy(**kw)


# ---------------------------------------------------------------- journal

def test_journal_roundtrip(tmp_path):
    p = str(tmp_path / "j.log")
    with journal.Journal(p, fsync=False) as J:
        for i in range(7):
            J.append({"ev": "x", "i": i, "payload": "y" * i})
    recs, good = journal.replay(p)
    assert [r["i"] for r in recs] == list(range(7))
    assert good == os.path.getsize(p)


def test_journal_torn_tail_truncated_on_replay_and_reopen(tmp_path):
    """Satellite: a torn final frame (power loss mid-write) must not
    poison the journal — replay stops cleanly at the last whole frame
    and reopening truncates the torn bytes before appending."""
    p = str(tmp_path / "j.log")
    with journal.Journal(p, fsync=False) as J:
        for i in range(5):
            J.append({"ev": "x", "i": i})
    whole = os.path.getsize(p)
    with open(p, "r+b") as f:          # tear the last frame mid-payload
        f.truncate(whole - 9)
    recs, good = journal.replay(p)
    assert [r["i"] for r in recs] == [0, 1, 2, 3]
    assert good < whole - 9
    with journal.Journal(p, fsync=False) as J:   # truncates the tail
        J.append({"ev": "x", "i": 99})
    recs, good = journal.replay(p)
    assert [r["i"] for r in recs] == [0, 1, 2, 3, 99]
    assert good == os.path.getsize(p)


def test_journal_corrupt_frame_stops_replay(tmp_path):
    """A flipped byte mid-journal fails the frame CRC; replay keeps
    the clean prefix (a fleet resumed from it loses the suffix but
    never reads garbage)."""
    p = str(tmp_path / "j.log")
    with journal.Journal(p, fsync=False) as J:
        for i in range(5):
            J.append({"ev": "x", "i": i})
    data = bytearray(open(p, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(p, "wb").write(bytes(data))
    recs, _ = journal.replay(p)
    assert 0 < len(recs) < 5
    assert [r["i"] for r in recs] == list(range(len(recs)))


def test_journal_rejects_concurrent_garbage_header(tmp_path):
    p = str(tmp_path / "j.log")
    open(p, "wb").write(b"not a journal at all")
    recs, good = journal.replay(p)
    assert recs == [] and good == 0


# ---------------------------------------------------------------- backoff

def test_backoff_deterministic_exponential_jitter():
    pol = spec.FleetPolicy(backoff_base_s=0.25, backoff_cap_s=30.0,
                           backoff_seed=7)
    d1 = state.backoff_delay(pol, "job-a", 1)
    assert d1 == state.backoff_delay(pol, "job-a", 1)  # reproducible
    assert d1 != state.backoff_delay(pol, "job-b", 1)  # de-phased
    for attempt in range(1, 12):
        d = state.backoff_delay(pol, "job-a", attempt)
        base = min(30.0, 0.25 * 2 ** (attempt - 1))
        assert base <= d <= base * 1.25  # bounded jitter
    assert state.backoff_delay(pol, "job-a", 40) <= 30.0 * 1.25


# ------------------------------------------------------------------ spec

def test_jobs_file_validation(tmp_path):
    with pytest.raises(ValueError, match="duplicate job id"):
        spec.parse_jobs_obj({"jobs": [{"id": "a"}, {"id": "a"}]})
    with pytest.raises(ValueError, match="zero jobs"):
        spec.parse_jobs_obj({"jobs": []})
    with pytest.raises(ValueError, match="unknown key"):
        spec.parse_jobs_obj({"jobs": [{"id": "a", "bogus": 1}]})
    with pytest.raises(ValueError, match="unknown fleet policy"):
        spec.parse_jobs_obj({"fleet": {"nope": 1},
                             "jobs": [{"id": "a"}]})
    with pytest.raises(ValueError, match="must match"):
        spec.JobSpec(id="../escape")
    with pytest.raises(ValueError, match="unknown kind"):
        spec.JobSpec(id="a", kind="mystery")
    pol, jobs = spec.parse_jobs_obj(
        {"fleet": {"max_attempts": 5},
         "jobs": [{"id": "a", "seed": 3,
                   "faults": [{"time_s": 0.1, "kind": "loss",
                               "a": 0, "b": 0, "value": 1}]}]})
    assert pol.max_attempts == 5
    assert jobs[0].faults[0]["kind"] == "loss"
    # the digest is stable across dict round-trips (spec.json reload)
    assert jobs[0].digest() == spec.JobSpec.from_dict(
        jobs[0].as_dict()).digest()


# ----------------------------------------------------------------- queue

def _mkqueue(tmp_path, jobs=("a", "b"), **pol_kw):
    t = {"v": 100.0}
    q = state.FleetQueue(
        str(tmp_path), _policy(**pol_kw),
        [spec.JobSpec(id=j, seed=i) for i, j in enumerate(jobs)],
        fsync=False, now=lambda: t["v"])
    return q, t


def test_queue_failure_retry_then_quarantine(tmp_path):
    q, t = _mkqueue(tmp_path)
    q.lease("a", "w0")
    q.mark_running("a", "w0")
    assert q.fail("a", {"error": "boom"}) == state.QUEUED
    j = q.jobs["a"]
    assert j.attempts == 1 and j.resume_from is None
    assert not j.continuation          # a retry restarts clean
    rec = q.lease("a", "w0")
    assert rec["attempt"] == 2
    assert q.fail("a", {"error": "boom"}) == state.QUARANTINED
    assert j.quarantine_reason.startswith("attempts exhausted")
    assert j.terminal
    # quarantined jobs never come back
    assert [x.spec.id for x in q.ready(t["v"] + 1e6)] == ["b"]


def test_queue_fatal_failure_skips_retries(tmp_path):
    q, _ = _mkqueue(tmp_path)
    q.lease("a", "w0")
    assert q.fail("a", {"error": "ValueError: bad spec"},
                  fatal=True) == state.FAILED
    assert q.jobs["a"].status == state.FAILED


def test_queue_worker_loss_requeues_same_attempt(tmp_path):
    q, t = _mkqueue(tmp_path)
    q.lease("a", "w0")
    q.mark_running("a", "w0")
    q.heartbeat("a", checkpoint="/ck/400.npz")
    assert q.worker_lost("w0", "a", "SIGKILL") == state.QUEUED
    j = q.jobs["a"]
    assert j.worker_losses == 1 and j.continuation
    assert j.resume_from == "/ck/400.npz"
    rec = q.lease("a", "w1")
    assert rec["attempt"] == 1          # continuation, not a retry
    assert rec["resume_from"] == "/ck/400.npz"
    assert j.attempt_history == [1, 1]


def test_queue_worker_loss_budget_quarantines(tmp_path):
    q, _ = _mkqueue(tmp_path, requeue_budget=1)
    for i in range(3):
        q.lease("a", f"w{i}")
        st = q.worker_lost(f"w{i}", "a", "crash loop")
        if st == state.QUARANTINED:
            break
    j = q.jobs["a"]
    assert j.status == state.QUARANTINED
    assert "requeue budget exhausted" in j.quarantine_reason


def test_queue_worker_loss_after_result_keeps_result(tmp_path):
    q, _ = _mkqueue(tmp_path)
    q.lease("a", "w0")
    q.complete("a", {"ok": True})
    assert q.worker_lost("w0", "a", "died after report") == state.DONE
    assert q.jobs["a"].status == state.DONE


def test_queue_backoff_gates_ready(tmp_path):
    q, t = _mkqueue(tmp_path, jobs=("a",), backoff_base_s=5.0,
                    backoff_cap_s=5.0)
    q.lease("a", "w0")
    q.fail("a", {"error": "boom"})
    assert "a" not in [j.spec.id for j in q.ready(t["v"])]
    assert 0 < q.next_wakeup(t["v"]) <= 5.0 * 1.25
    t["v"] += 10.0
    assert "a" in [j.spec.id for j in q.ready(t["v"])]


def test_queue_resume_replays_journal(tmp_path):
    q, t = _mkqueue(tmp_path)
    q.lease("a", "w0")
    q.mark_running("a", "w0")
    q.heartbeat("a", checkpoint="/ck/800.npz")
    q.lease("b", "w1")
    q.complete("b", {"ok": True, "digest": "d"})
    q.close()
    # the fleet dies; --resume folds the journal back up
    q2 = state.FleetQueue(str(tmp_path), _policy(), resume=True,
                          fsync=False, now=lambda: t["v"])
    a, b = q2.jobs["a"], q2.jobs["b"]
    assert b.status == state.DONE and b.result["digest"] == "d"
    assert a.status == state.QUEUED        # in-flight -> requeued
    assert a.continuation and a.resume_from == "/ck/800.npz"
    # specs reloaded from jobs/<id>/spec.json, not the jobs file
    assert a.spec.seed == 0 and b.spec.seed == 1
    q2.close()


def test_queue_refuses_nonempty_dir_without_resume(tmp_path):
    q, _ = _mkqueue(tmp_path)
    q.close()
    with pytest.raises(FileExistsError, match="--resume"):
        state.FleetQueue(str(tmp_path), _policy(),
                         [spec.JobSpec(id="c")], fsync=False)


def test_queue_resume_survives_torn_final_frame(tmp_path):
    """Satellite: kill -9 mid-append leaves a torn frame; --resume
    must replay the clean prefix and keep going."""
    q, t = _mkqueue(tmp_path)
    q.lease("a", "w0")
    q.complete("a", {"ok": True})
    q.close()
    jp = str(tmp_path / "journal.log")
    with open(jp, "r+b") as f:
        f.truncate(os.path.getsize(jp) - 5)
    q2 = state.FleetQueue(str(tmp_path), _policy(), resume=True,
                          fsync=False, now=lambda: t["v"])
    # the torn "done" frame is gone; the leased job comes back queued
    a = q2.jobs["a"]
    assert a.status == state.QUEUED and a.continuation
    q2.complete("a", {"ok": True})
    q2.close()
    assert state.FleetQueue(str(tmp_path), _policy(), resume=True,
                            fsync=False).jobs["a"].status == state.DONE


# -------------------------------------------------------------- manifest

def _terminal_queue(tmp_path):
    q, _ = _mkqueue(tmp_path, jobs=("ok-0", "bad-0", "park-0"))
    q.lease("ok-0", "w0")
    q.complete("ok-0", {"ok": True, "digest": "abc"})
    q.lease("bad-0", "w0")
    q.fail("bad-0", {"error": "ValueError: x"}, fatal=True)
    q.lease("park-0", "w0")
    q.fail("park-0", {"error": "boom"})
    q.lease("park-0", "w0")
    q.fail("park-0", {"error": "boom"})
    return q


def test_fleet_manifest_schema_and_lint(tmp_path):
    q = _terminal_queue(tmp_path)
    man = manifest_mod.fleet_manifest(q, complete=True)
    p = manifest_mod.write_fleet_manifest(
        str(tmp_path / "fleet_manifest.json"), man)
    loaded = json.load(open(p))
    assert loaded["counts"] == {"done": 1, "failed": 1,
                                "quarantined": 1}
    assert loaded["jobs"]["ok-0"]["verdict"] == "ok"
    assert loaded["jobs"]["bad-0"]["verdict"] == "failed"
    park = loaded["jobs"]["park-0"]
    assert park["verdict"] == "quarantined"
    assert park["salvage"]["dir"] == os.path.join("jobs", "park-0")
    assert park["attempt_history"] == [1, 2]
    tl = load_tool("telemetry_lint")
    errors, warnings = tl.lint_fleet_manifest_obj(loaded)
    assert errors == []
    assert any("quarantined" in w for w in warnings)
    q.close()


def test_fleet_lint_catches_violations(tmp_path):
    q = _terminal_queue(tmp_path)
    man = manifest_mod.fleet_manifest(q, complete=True)
    q.close()
    tl = load_tool("telemetry_lint")

    bad = json.loads(json.dumps(man))
    bad["jobs"]["ok-0"]["attempt_history"] = [2, 1]  # rewound attempt
    errs, _ = tl.lint_fleet_manifest_obj(bad)
    assert any("monotone" in e for e in errs)

    bad = json.loads(json.dumps(man))
    bad["jobs"]["bad-0"]["verdict"] = None           # verdict dropped
    errs, _ = tl.lint_fleet_manifest_obj(bad)
    assert any("verdict" in e for e in errs)

    bad = json.loads(json.dumps(man))
    del bad["jobs"]["park-0"]["salvage"]             # salvage dropped
    errs, _ = tl.lint_fleet_manifest_obj(bad)
    assert any("salvage" in e for e in errs)

    bad = json.loads(json.dumps(man))
    bad["counts"]["done"] = 7                        # counts lie
    errs, _ = tl.lint_fleet_manifest_obj(bad)
    assert any("disagrees" in e for e in errs)

    bad = json.loads(json.dumps(man))
    bad["jobs"]["ok-0"]["status"] = "running"        # complete lie
    errs, _ = tl.lint_fleet_manifest_obj(bad)
    assert any("non-terminal" in e for e in errs)


# ------------------------------------------------------------ status CLI

def test_fleet_status_readonly(tmp_path, capsys):
    from shadow_tpu.fleet import cli as fleet_cli

    q = _terminal_queue(tmp_path)
    q.close()
    before = open(str(tmp_path / "journal.log"), "rb").read()
    rc = fleet_cli.main(["status", "--fleet-dir", str(tmp_path)])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["counts"] == {"done": 1, "failed": 1, "quarantined": 1}
    assert out["jobs"]["ok-0"] == "done"
    # status never mutates the journal (a live fleet owns it)
    assert open(str(tmp_path / "journal.log"), "rb").read() == before


# ------------------------------------------------------- chip ownership

@pytest.mark.parametrize("workers,platforms,refused", [
    (2, None, True),       # both would start on the host's TPU
    (2, "tpu", True),
    (1, None, False),      # one worker owns the chip
    (2, "cpu", False),     # CPU workers hold no chip
])
def test_fleet_refuses_workers_that_fight_for_the_chip(
        tmp_path, monkeypatch, workers, platforms, refused):
    """On a host with a TPU the pool may hold one chip-holding worker
    (a JAX process takes every chip), refused before anything is
    spawned or written; the check reads the PCI bus, not a backend."""
    from jax._src import hardware_utils

    from shadow_tpu.fleet import runner

    monkeypatch.setattr(hardware_utils,
                        "num_available_tpu_chips_and_device_id",
                        lambda: (1, None))
    monkeypatch.setattr(runner, "worker_platforms", lambda: platforms)
    fd = tmp_path / "fleet"
    if refused:
        with pytest.raises(ValueError, match="1 TPU chip"):
            runner.FleetRunner(str(fd), _policy(), [], workers=workers)
        assert not fd.exists()
    else:
        r = runner.FleetRunner(str(fd), _policy(), [], workers=workers)
        assert r._platforms == platforms
