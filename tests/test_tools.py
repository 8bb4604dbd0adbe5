"""Log-analysis tool parity (ref: src/tools/parse-shadow.py /
plot-shadow.py): heartbeat node lines (with the byte split), [ram]
lines, and completion ticks parse into stats.shadow.json."""

from conftest import load_tool as _load


LOG = """\
00:00:10.000000000 [message] [alpha] [shadow-heartbeat] [node] 10,1000,900,800,700,200,200,0,5,5,0,0
00:00:10.000000000 [message] [alpha] [shadow-heartbeat] [ram] 4096
00:00:20.000000000 [message] [alpha] [shadow-heartbeat] [node] 10,1100,950,900,760,200,190,64,6,6,1,0
00:00:30.000000000 [message] [beta] [shadow-heartbeat] [node] 10,5,6,1,2,4,4,0,1,1,0,0
00:00:20.000000000 [message] [shadow-tpu] simulation complete {"events": 12, "simulated_seconds_per_wall_second": 3.5}
"""

LOG_V1 = """\
00:00:10.000000000 [message] [gamma] [shadow-heartbeat] [node] 10,1000,900,5,5,0,0
"""


def test_parse_shadow_fields():
    ps = _load("parse_shadow")
    stats = ps.parse(LOG.splitlines(True))
    a = stats["nodes"]["alpha"]
    assert a["recv_bytes_by_second"][10] == 1000
    assert a["send_bytes_by_second"][20] == 950
    assert a["ram_bytes_by_second"][10] == 4096
    assert a["retransmit_bytes_by_second"][20] == 64
    assert a["retransmits_by_second"][20] == 1
    assert "beta" in stats["nodes"]
    assert stats["ticks"][0]["events"] == 12


def test_parse_shadow_v1_format_back_compat():
    ps = _load("parse_shadow")
    stats = ps.parse(LOG_V1.splitlines(True))
    g = stats["nodes"]["gamma"]
    assert g["recv_bytes_by_second"][10] == 1000
    assert g["drops_by_second"][10] == 0


def test_strip_log_for_compare():
    """Wall-time fields and address-like tokens are canonicalized;
    sim-time determinism content is preserved (ref:
    strip_log_for_compare.py + determinism1_compare.cmake)."""
    st = _load("strip_log_for_compare")
    a = ('00:00:20.000000000 [message] [shadow-tpu] simulation complete '
         '{"events": 12, "wall_seconds": 53.47, "events_per_second": '
         '157.7, "simulated_seconds_per_wall_second": 1.122, '
         '"overflow": 0}\n')
    b = a.replace("53.47", "99.9").replace("157.7", "3.3").replace(
        "1.122", "0.5")
    assert st.strip_line(a) == st.strip_line(b)
    assert '"events": 12' in st.strip_line(a)
    assert st.strip_line("obj at 0xDEADBEEF ok\n") == "obj at 0xX ok\n"
    # heartbeat counters are NOT stripped (determinism contract)
    hb = "00:00:10.0 [message] [a] [shadow-heartbeat] [node] 10,1,2\n"
    assert st.strip_line(hb) == hb


def test_convert_legacy_config_runs_through_loader():
    """node/application + kill-time configs convert to host/process
    and the result builds (ref: convert_multi_app.py migration)."""
    cv = _load("convert_legacy_config")
    old = """<shadow>
  <kill time="30"/>
  <topology><![CDATA[x]]></topology>
  <plugin id="png" path="pingpong"/>
  <node id="server"><application plugin="png" starttime="1"
    arguments="mode=server port=5000"/></node>
  <node id="client" quantity="2"><application plugin="png" time="2"
    arguments="mode=client server=server port=5000 count=2"/></node>
</shadow>"""
    new = cv.convert(old)
    from shadow_tpu.config.xmlconfig import parse_config

    cfg = parse_config(new)
    assert cfg.stoptime == 30_000_000_000
    names = dict(cfg.expanded_hosts())
    # quantity expansion follows the reference: name, name2, ...
    assert set(names) == {"server", "client", "client2"}
    procs = names["client"].processes
    assert procs[0].plugin == "png"
    assert procs[0].starttime == 2_000_000_000


def test_convert_software_reference_nodes():
    """Oldest-generation nodes referencing a <software> element by id
    get their process synthesized from it (no silent app loss)."""
    cv = _load("convert_legacy_config")
    old = """<shadow>
  <kill time="10"/>
  <topology><![CDATA[x]]></topology>
  <software id="fx" plugin="filetransfer" time="3"
            arguments="mode=client server=s port=80 bytes=100"/>
  <node id="c" software="fx"/>
</shadow>"""
    new = cv.convert(old)
    from shadow_tpu.config.xmlconfig import parse_config

    cfg = parse_config(new)
    host = dict(cfg.expanded_hosts())["c"]
    assert len(host.processes) == 1
    p = host.processes[0]
    assert p.plugin == "fx"
    assert p.starttime == 3_000_000_000
    assert "bytes=100" in p.arguments


def test_generate_example_config_builds(tmp_path):
    gen = _load("generate_example_config")
    gen.main(["-o", str(tmp_path), "--clients", "3", "--kib", "10",
              "--vertices", "2"])
    from shadow_tpu.config.loader import load
    from shadow_tpu.config.xmlconfig import parse_config

    text = (tmp_path / "shadow.config.xml").read_text()
    cfg = parse_config(text)
    # loader takes absolute paths; the CLI resolves a relative
    # <topology path> against the config file's directory (cli.py)
    cfg = cfg.__class__(**{**cfg.__dict__, "topology_path":
                           str(tmp_path / "topology.graphml.xml")})
    loaded = load(cfg)
    assert loaded.bundle.cfg.num_hosts == 4
    # typehints attach clients and server to their own vertices
    import numpy as np

    v = np.asarray(loaded.bundle.sim.net.vertex_of_host)
    names = loaded.bundle.host_names
    sv = v[names.index("server")]
    assert all(v[i] != sv for i, n in enumerate(names) if n != "server")


def test_parse_shadow_progress_ticks():
    """[shadow-progress] records (cli.py progress_hook) land in the
    ticks list alongside the final completion tick."""
    ps = _load("parse_shadow")
    log = (
        '00:00:10.000000000 [message] [shadow-tpu] [shadow-progress] '
        '{"sim_seconds": 10.0, "wall_seconds": 1.5}\n'
        '00:00:20.000000000 [message] [shadow-tpu] [shadow-progress] '
        '{"sim_seconds": 20.0, "wall_seconds": 2.9}\n'
        '00:00:20.000000000 [message] [shadow-tpu] simulation complete '
        '{"events": 7, "sim_seconds": 20.0, "wall_seconds": 3.0, '
        '"simulated_seconds_per_wall_second": 6.7}\n')
    stats = ps.parse(log.splitlines(True))
    assert len(stats["ticks"]) == 3
    assert stats["ticks"][0]["wall_seconds"] == 1.5
    assert stats["ticks"][-1]["events"] == 7


def test_plot_shadow_multi_experiment(tmp_path):
    """Multi-experiment comparison plotting (VERDICT r2 missing #3,
    ref: plot-shadow.py): two parsed runs overlay into one combined
    multi-page PDF — throughput/retransmit/RAM pages, the per-node
    CDF, the progress tick plot, and the rate bars."""
    import json
    import re

    ps = _load("parse_shadow")
    plot = _load("plot_shadow")

    paths = []
    for i, scale in enumerate((1, 3)):
        log = "".join(
            f"00:00:{10 * t:02d}.000000000 [message] [n{n}] "
            f"[shadow-heartbeat] [node] "
            f"10,{scale * 100 * t},{scale * 90 * t},80,70,20,20,0,5,5,"
            f"{t % 2},0\n"
            for t in range(1, 4) for n in range(3)
        ) + "".join(
            f"00:00:{10 * t:02d}.000000000 [message] [n0] "
            f"[shadow-heartbeat] [ram] {scale * 1000 * t}\n"
            for t in range(1, 4)
        ) + (
            f'00:00:30.000000000 [message] [shadow-tpu] [shadow-progress] '
            f'{{"sim_seconds": 30.0, "wall_seconds": {2.0 * scale}}}\n'
            f'00:00:30.000000000 [message] [shadow-tpu] simulation '
            f'complete {{"events": 9, "sim_seconds": 30.0, '
            f'"wall_seconds": {3.0 * scale}, '
            f'"simulated_seconds_per_wall_second": {10.0 / scale}}}\n')
        p = tmp_path / f"stats{i}.json"
        p.write_text(json.dumps(ps.parse(log.splitlines(True))))
        paths.append(str(p))

    out = tmp_path / "cmp"
    rc = plot.main(["-d", paths[0], "fast", "-d", paths[1], "slow",
                    "-o", str(out)])
    assert rc == 0
    pdf = (tmp_path / "cmp.pdf").read_bytes()
    m = re.search(rb"/Count (\d+)", pdf)
    assert m, "no page count in PDF"
    # the reference plotter's page families (r5 parity): per
    # direction {throughput, goodput, fractional goodput, control,
    # fractional control} x 3 views (30) + send retrans x2 families
    # x3 (6) + retransmitted segments x3 + RAM x3 + 3 CDFs +
    # progress + rate bars = 44+
    assert int(m.group(1)) >= 40, int(m.group(1))


# ---- telemetry_lint (tools/telemetry_lint.py) -----------------------

GOOD_TRACE = {
    "traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
         "args": {"name": "sim-time"}},
        {"ph": "X", "pid": 0, "tid": 0, "name": "window 0",
         "ts": 0.0, "dur": 50000.0,
         "args": {"events": 4, "micro_steps": 2, "routed_local": 4,
                  "routed_cross": 0, "drops": 0, "retx": 0,
                  "queue_occupancy": {"min": 0, "max": 2, "sum": 3}}},
        {"ph": "X", "pid": 0, "tid": 0, "name": "window 1",
         "ts": 50000.0, "dur": 50000.0,
         "args": {"events": 2, "micro_steps": 1, "routed_local": 2,
                  "routed_cross": 0, "drops": 0, "retx": 0,
                  "queue_occupancy": {"min": 0, "max": 1, "sum": 1}}},
    ],
    "displayTimeUnit": "ms",
}

GOOD_MANIFEST = {
    "config_hash": "ab" * 32, "seed": 1, "shards": 1,
    "counters": {"windows": 2, "events_processed": 6},
    "telemetry": {"windows_recorded": 2, "records_lost": 0},
    "health": {"verdict": "clean", "diagnostics": [],
               "telemetry_lost": 0},
}


def _copy(obj):
    import copy

    return copy.deepcopy(obj)


def test_telemetry_lint_accepts_good_outputs():
    tl = _load("telemetry_lint")
    assert tl.lint_trace_obj(GOOD_TRACE) == ([], [])
    assert tl.lint_manifest_obj(GOOD_MANIFEST) == ([], [])


def test_telemetry_lint_rejects_schema_violations():
    tl = _load("telemetry_lint")
    # bare array: Perfetto needs the object format to be emitted here
    errs, _ = tl.lint_trace_obj([])
    assert errs
    # every event needs a phase
    t = _copy(GOOD_TRACE)
    del t["traceEvents"][1]["ph"]
    errs, _ = tl.lint_trace_obj(t)
    assert any('"ph"' in e for e in errs)
    # zero-duration complete events render invisibly
    t = _copy(GOOD_TRACE)
    t["traceEvents"][1]["dur"] = 0
    errs, _ = tl.lint_trace_obj(t)
    assert any("dur" in e for e in errs)
    # negative counters can't come out of a correct exporter
    t = _copy(GOOD_TRACE)
    t["traceEvents"][1]["args"]["events"] = -1
    errs, _ = tl.lint_trace_obj(t)
    assert any("args.events" in e for e in errs)
    # impossible occupancy bounds
    t = _copy(GOOD_TRACE)
    t["traceEvents"][1]["args"]["queue_occupancy"] = {"min": 5, "max": 1}
    errs, _ = tl.lint_trace_obj(t)
    assert any("min > max" in e for e in errs)


def test_telemetry_lint_overlap_is_warning_not_error():
    tl = _load("telemetry_lint")
    t = _copy(GOOD_TRACE)
    t["traceEvents"][2]["ts"] = 10000.0   # starts inside window 0
    errs, warns = tl.lint_trace_obj(t)
    assert errs == []
    assert any("before the previous window ended" in w for w in warns)


def test_telemetry_lint_unsurfaced_ring_loss_is_error():
    tl = _load("telemetry_lint")
    m = _copy(GOOD_MANIFEST)
    m["telemetry"]["records_lost"] = 3
    m["counters"]["windows"] = 5      # 2 recorded + 3 lost
    errs, _ = tl.lint_manifest_obj(m)
    assert any("does not surface" in e for e in errs)
    # latched in health -> warning, not error
    m["health"]["telemetry_lost"] = 3
    errs, warns = tl.lint_manifest_obj(m)
    assert errs == []
    assert any("ring overrun" in w for w in warns)
    # more windows accounted for than the engine ran
    m2 = _copy(GOOD_MANIFEST)
    m2["telemetry"]["windows_recorded"] = 9
    errs, _ = tl.lint_manifest_obj(m2)
    assert any("engine ran only" in e for e in errs)


def test_telemetry_lint_cli_exit_codes(tmp_path):
    import json

    tl = _load("telemetry_lint")
    good = tmp_path / "good.json"
    good.write_text(json.dumps(GOOD_TRACE))
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"pid": 0}]}))
    assert tl.main(["--trace", str(good), "-q"]) == 0
    assert tl.main(["--trace", str(bad), "-q"]) == 1
    assert tl.main(["--trace", str(tmp_path / "missing.json"), "-q"]) == 1


# ---- dual-mode conformance (tools/dualmode_diff.py) -----------------

def _trace_doc(procs):
    return {"meta": {}, "procs": procs}


def test_dualmode_diff_compare_exit_codes(tmp_path):
    import json

    dd = _load("dualmode_diff")
    agree = _trace_doc({"h0:p1": [["getpid", [], 1], ["_exit", [], None]]})
    diverge = _trace_doc({"h0:p1": [["getpid", [], 2], ["_exit", [], None]]})
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    c = tmp_path / "c.json"
    a.write_text(json.dumps(agree))
    b.write_text(json.dumps(agree))
    c.write_text(json.dumps(diverge))
    assert dd.main(["--sim", str(a), "--host", str(b)]) == dd.EXIT_OK
    # divergence MUST exit non-zero (the CI contract)
    assert dd.main(["--sim", str(a), "--host", str(c)]) == dd.EXIT_DIVERGED
    # usage errors are distinguishable from divergence
    assert dd.main(["--sim", str(a)]) == dd.EXIT_USAGE
    assert dd.main(["--sim", str(a),
                    "--host", str(tmp_path / "nope.json")]) == dd.EXIT_USAGE
    rpt = tmp_path / "report.json"
    assert dd.main(["--sim", str(a), "--host", str(c),
                    "--json", str(rpt)]) == dd.EXIT_DIVERGED
    doc = json.loads(rpt.read_text())
    assert doc["agree"] is False and doc["mode"] == "compare"


def test_dualmode_diff_catalog_surface():
    dd = _load("dualmode_diff")
    assert dd.main(["--list"]) == dd.EXIT_OK
    assert dd.main(["--workload", "not-a-workload"]) == dd.EXIT_USAGE


def test_telemetry_lint_conformance_block():
    tl = _load("telemetry_lint")
    m = _copy(GOOD_MANIFEST)
    m["conformance"] = {"workloads": {"bind": "agree", "epoll": "agree"},
                        "agree": 2, "diverge": 0, "total": 2}
    assert tl.lint_manifest_obj(m) == ([], [])
    # a divergence is surfaced as a warning, never silent
    m["conformance"]["workloads"]["epoll"] = "diverge"
    m["conformance"] = dict(m["conformance"], agree=1, diverge=1)
    errs, warns = tl.lint_manifest_obj(m)
    assert errs == []
    assert any("diverged" in w and "epoll" in w for w in warns)
    # incoherent counts and missing keys are errors
    m["conformance"]["total"] = 5
    errs, _ = tl.lint_manifest_obj(m)
    assert any("incoherent" in e for e in errs)
    m2 = _copy(GOOD_MANIFEST)
    m2["conformance"] = {"workloads": {}, "agree": -1, "diverge": 0,
                         "total": 0}
    errs, _ = tl.lint_manifest_obj(m2)
    assert any("non-negative" in e for e in errs)
    m3 = _copy(GOOD_MANIFEST)
    m3["conformance"] = {"agree": 0}
    errs, _ = tl.lint_manifest_obj(m3)
    assert any('missing "workloads"' in e for e in errs)


def test_telemetry_lint_escalation_and_resume_blocks():
    """The supervisor-v2 manifest fields (ISSUE PR 5 satellite):
    run_id/resume_of chain identity, escalations[] records, and the
    preempted flag all validate — and incoherent ones are errors."""
    tl = _load("telemetry_lint")
    m = _copy(GOOD_MANIFEST)
    m["run_id"] = "abc123def456"
    m["resume_of"] = "000111222333"
    m["preempted"] = False
    m["escalations"] = [
        {"time_ns": 0, "latch": "events_overflow",
         "knob": "event_capacity", "from": 32, "to": 64},
        {"time_ns": 5, "latch": "events_overflow",
         "knob": "event_capacity", "from": 64, "to": 128},
    ]
    errs, warns = tl.lint_manifest_obj(m)
    assert errs == []
    assert any("escalation(s) healed" in w for w in warns)

    # a chained run must identify itself
    m2 = _copy(GOOD_MANIFEST)
    m2["resume_of"] = "000111222333"
    errs, _ = tl.lint_manifest_obj(m2)
    assert any("resume_of" in e and "run_id" in e for e in errs)
    m2["run_id"] = ""          # empty id is as bad as a missing one
    errs, _ = tl.lint_manifest_obj(m2)
    assert any("non-empty string" in e for e in errs)

    # unknown knobs and non-growing records are exporter bugs
    m3 = _copy(m)
    m3["escalations"][0]["knob"] = "emit_capacity"
    errs, _ = tl.lint_manifest_obj(m3)
    assert any("unknown grow knob" in e for e in errs)
    m4 = _copy(m)
    m4["escalations"][1]["to"] = 64
    errs, _ = tl.lint_manifest_obj(m4)
    assert any("capacities only grow" in e for e in errs)

    # a "healed" run whose latch counter is still nonzero lied
    m5 = _copy(m)
    m5["counters"]["events_overflow"] = 3
    m5["health"]["verdict"] = "clean"
    errs, _ = tl.lint_manifest_obj(m5)
    assert any("latch at zero" in e for e in errs)

    # empty escalations array: omit the key instead
    m6 = _copy(GOOD_MANIFEST)
    m6["escalations"] = []
    errs, _ = tl.lint_manifest_obj(m6)
    assert any("non-empty array" in e for e in errs)

    m7 = _copy(GOOD_MANIFEST)
    m7["preempted"] = "yes"
    errs, _ = tl.lint_manifest_obj(m7)
    assert any("preempted must be a bool" in e for e in errs)


# ---- faultplan_lint --checkpoint cross-check ------------------------

def _snapshot_meta(**caps):
    base = {"num_hosts": 8, "event_capacity": 64,
            "outbox_capacity": 32, "router_ring": 32}
    base.update(caps)
    return {"time_ns": 100, "extra": {}, "layout": None,
            "capacities": base, "shards": 4}


def test_faultplan_lint_against_checkpoint_meta():
    fl = _load("faultplan_lint")
    meta = _snapshot_meta()
    # shrinking any capacity below the snapshot's is a lint error
    errs, warns, hosts = fl.lint_against_checkpoint(
        meta, event_capacity=32)
    assert any("capacities only grow" in e for e in errs)
    # growing is allowed, flagged as a transplant
    errs, warns, hosts = fl.lint_against_checkpoint(
        meta, event_capacity=128)
    assert errs == []
    assert any("transplant" in w for w in warns)
    # the snapshot's host count feeds the plan's range checks
    assert hosts == 8
    # changing the host axis can never transplant
    errs, _, _ = fl.lint_against_checkpoint(meta, hosts=16)
    assert any("host axis" in e for e in errs)
    # matching intent is clean (shard note is informational only)
    errs, warns, _ = fl.lint_against_checkpoint(
        meta, hosts=8, event_capacity=64)
    assert errs == []
    assert any("any --workers count" in w for w in warns)


def test_faultplan_lint_checkpoint_cli(tmp_path):
    """End to end through main(): a resume into a shrunken config
    fails at lint time; the same plan with a grown target passes."""
    import json

    import numpy as np

    from shadow_tpu.utils.checkpoint import LAYOUT_VERSION

    fl = _load("faultplan_lint")
    plan = tmp_path / "plan.json"
    plan.write_text(json.dumps({"faults": [
        {"time_s": 1.0, "kind": "loss", "a": 0, "b": 0, "value": 0.05},
    ]}))
    meta = _snapshot_meta()
    meta["layout"] = LAYOUT_VERSION
    snap = tmp_path / "snap.npz"
    np.savez(snap, __meta__=json.dumps(meta))

    assert fl.main([str(plan), "--checkpoint", str(snap),
                    "--event-capacity", "32", "-q"]) == 1
    assert fl.main([str(plan), "--checkpoint", str(snap),
                    "--event-capacity", "128", "-q"]) == 0
    # an unreadable snapshot is an error, not a crash
    assert fl.main([str(plan), "--checkpoint",
                    str(tmp_path / "missing.npz"), "-q"]) == 1


def test_compcache_machine_claim_and_redirect(tmp_path):
    """The persistent compile cache is claimed by the first host's
    CPU-feature fingerprint; a host with different features is
    redirected to a per-fingerprint subdirectory with a warning
    (XLA:CPU AOT entries embed the compile machine's features —
    loading foreign ones would mis-execute), and a corrupt sidecar is
    re-claimed instead of crashing."""
    import json
    import pathlib

    from shadow_tpu.utils import compcache

    fp = compcache.machine_fingerprint()
    assert fp == compcache.machine_fingerprint()     # stable
    cache = pathlib.Path(tmp_path) / ".jax_cache"
    msgs = []
    # first claim: recorded and kept
    assert compcache._claim_or_redirect(cache, fp, msgs.append) == cache
    assert json.loads((cache / "machine.json").read_text())[
        "fingerprint"] == fp
    # same host again: no warning, same dir
    assert compcache._claim_or_redirect(cache, fp, msgs.append) == cache
    assert msgs == []
    # a different host: redirected to a fresh-compile namespace
    other = compcache._claim_or_redirect(cache, "feedfacedeadbeef",
                                         msgs.append)
    assert other == cache / "hosts" / "feedfacedeadbeef"
    assert len(msgs) == 1 and "different CPU features" in msgs[0]
    # corrupt sidecar: re-claimed, not fatal
    (cache / "machine.json").write_text("{not json")
    assert compcache._claim_or_redirect(cache, fp, msgs.append) == cache
    assert json.loads((cache / "machine.json").read_text())[
        "fingerprint"] == fp


def test_compcache_honours_jax_compilation_cache_dir(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache directory as
    given: no claim sidecar, no redirect, the AOT store under it, and
    compiled entries written there."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from shadow_tpu.compile import store
    from shadow_tpu.utils import compcache

    d = tmp_path / "cc"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(d))
    monkeypatch.delenv("SHADOW_AOT_DIR", raising=False)
    monkeypatch.delenv("SHADOW_NO_COMPILE_CACHE", raising=False)
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        compcache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(d)
        assert store.default_root() == d / "aot"
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        compilation_cache.reset_cache()
        jax.jit(lambda x: x * 3 + 1).lower(jnp.arange(7)).compile()
        assert [p for p in d.iterdir() if p.is_file()]
        assert not (d / "machine.json").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev[1])
        compilation_cache.reset_cache()
