"""Config-system tests: the reference's own phold XML parses and runs
(format compatibility with configuration.c), the builtin example
works, CLI flags parse, and the logger sorts by sim time."""

import io
import json

from shadow_tpu.cli import make_parser
from shadow_tpu.config.examples import example_config
from shadow_tpu.config.loader import load
from shadow_tpu.config.xmlconfig import kv_arguments, parse_config
from shadow_tpu.utils.shadowlog import LogLevel, SimLogger

REFERENCE_PHOLD_XML = """<shadow>
  <topology><![CDATA[<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
  <key attr.name="packetloss" attr.type="double" for="edge" id="d4" />
  <key attr.name="latency" attr.type="double" for="edge" id="d3" />
  <key attr.name="bandwidthup" attr.type="int" for="node" id="d2" />
  <key attr.name="bandwidthdown" attr.type="int" for="node" id="d1" />
  <key attr.name="countrycode" attr.type="string" for="node" id="d0" />
  <graph edgedefault="undirected">
    <node id="poi-1">
      <data key="d0">US</data>
      <data key="d1">10240</data>
      <data key="d2">10240</data>
    </node>
    <edge source="poi-1" target="poi-1">
      <data key="d3">50.0</data>
      <data key="d4">0.0</data>
    </edge>
  </graph>
</graphml>
]]></topology>
  <kill time="3"/>
  <plugin id="testphold" path="shadow-plugin-test-phold"/>
  <node id="peer" quantity="10">
    <application plugin="testphold" starttime="1"
      arguments="loglevel=info basename=peer quantity=10 load=25 weightsfilepath=weights.txt"/>
  </node>
</shadow>"""


def test_parse_reference_phold_config():
    cfg = parse_config(REFERENCE_PHOLD_XML)
    assert cfg.stoptime == 3_000_000_000
    assert "testphold" in cfg.plugins
    assert cfg.plugins["testphold"].path == "shadow-plugin-test-phold"
    names = [n for n, _ in cfg.expanded_hosts()]
    assert len(names) == 10
    assert names[0] == "peer" and names[1] == "peer2"
    (name, he) = next(iter(cfg.expanded_hosts()))
    assert he.processes[0].starttime == 1_000_000_000
    kv = kv_arguments(he.processes[0].arguments)
    assert kv["load"] == "25"


def test_load_and_run_reference_phold():
    cfg = parse_config(REFERENCE_PHOLD_XML)
    loaded = load(cfg, seed=3)
    from shadow_tpu.net.build import run

    sim, stats = run(loaded.bundle, app_handlers=loaded.handlers)
    # 10 peers x load 25 all injected, messages circulating
    assert int(sim.app.remaining.sum()) == 0
    assert int(sim.app.rcvd.sum()) > 0
    assert int(sim.events.overflow) == 0


def test_example_config_parses():
    cfg = parse_config(example_config(clients=5))
    assert len(list(cfg.expanded_hosts())) == 6
    loaded = load(cfg)
    assert loaded.bundle.cfg.num_hosts == 6
    assert len(loaded.handlers) == 1


def test_cli_flag_parity():
    p = make_parser()
    a = p.parse_args([
        "conf.xml", "-w", "4", "--seed", "7", "--scheduler-policy", "steal",
        "--runahead", "10", "--interface-qdisc", "rr",
        "--socket-recv-buffer", "100000", "--tcp-congestion-control",
        "reno", "-l", "info", "--heartbeat-frequency", "30",
    ])
    assert a.workers == 4 and a.seed == 7
    assert a.scheduler_policy == "steal"
    assert a.runahead == 10 and a.interface_qdisc == "rr"


def test_logger_sorts_by_simtime():
    out = io.StringIO()
    lg = SimLogger(level=LogLevel.INFO, stream=out)
    lg.info(2_000_000_000, "b", "later")
    lg.info(1_000_000_000, "a", "earlier")
    lg.message(1_000_000_000, "a", "earlier-second")  # same time: emit order
    lg.flush()
    lines = out.getvalue().splitlines()
    assert lines[0].startswith("00:00:01.000000000 [info] [a] earlier")
    assert lines[1].endswith("earlier-second")
    assert lines[2].startswith("00:00:02.000000000")


def test_cli_reference_compat_flags():
    """Reference invocations using mechanism-less flags (--preload,
    --gdb, --valgrind, --data-template, --interface-batch/-buffer;
    options.c:89-132) must parse, and the sim-meaningful knobs
    (--tcp-ssthresh/-windows, --cpu-threshold/-precision,
    --heartbeat-log-info) must carry their reference units."""
    p = make_parser()
    a = p.parse_args([
        "conf.xml", "--preload", "/usr/lib/libfoo.so", "--gdb",
        "--valgrind", "--data-template", "shadow.data.template",
        "--interface-batch", "5000", "--interface-buffer", "1024000",
        "--tcp-ssthresh", "64", "--tcp-windows", "10",
        "--cpu-threshold", "1000", "--cpu-precision", "200",
        "-i", "node,ram",
    ])
    assert a.tcp_ssthresh == 64 and a.tcp_windows == 10
    assert a.cpu_threshold == 1000 and a.cpu_precision == 200
    assert a.heartbeat_log_info == "node,ram"


def test_tcp_window_knobs_reach_state():
    """--tcp-ssthresh / --tcp-windows initialize TcpState (ref:
    options.c:137-138 -> tcp_new initial windows)."""
    import numpy as np

    from shadow_tpu.net.state import NetConfig, make_sim, make_net_state

    cfg = NetConfig(num_hosts=1, tcp_ssthresh=64, tcp_windows=10)
    net = make_net_state(
        cfg, host_ips=np.array([0x0B000001], np.int64),
        bw_up_kibps=np.array([1024]), bw_down_kibps=np.array([1024]),
        vertex_of_host=np.array([0], np.int32),
        latency_ns=np.array([[10**6]], np.int64),
        reliability=np.array([[1.0]], np.float32),
    )
    sim = make_sim(cfg, net)
    assert int(sim.tcp.cwnd[0, 0]) == 10
    assert int(sim.tcp.ssthresh[0, 0]) == 64


def test_tracker_sections_filter():
    """--heartbeat-log-info gates which sections print (ref:
    options.c:92, default 'node')."""
    import io as _io

    import numpy as np

    from shadow_tpu.net.state import NetConfig, make_sim, make_net_state
    from shadow_tpu.utils.shadowlog import SimLogger
    from shadow_tpu.utils.tracker import Tracker

    cfg = NetConfig(num_hosts=1, tcp=False)
    net = make_net_state(
        cfg, host_ips=np.array([0x0B000001], np.int64),
        bw_up_kibps=np.array([1024]), bw_down_kibps=np.array([1024]),
        vertex_of_host=np.array([0], np.int32),
        latency_ns=np.array([[10**6]], np.int64),
        reliability=np.array([[1.0]], np.float32),
    )
    sim = make_sim(cfg, net)
    out = _io.StringIO()
    lg = SimLogger(stream=out, buffered=False)
    tr = Tracker(lg, ["h0"], interval_s=1, sections=("node",))
    tr.heartbeat(sim, 10**9)
    text = out.getvalue()
    assert "[node-header]" in text
    assert "[socket-header]" not in text and "[ram-header]" not in text


def test_cli_knobs_reach_loader_overrides():
    """The parsed flags must actually flow into the loader overrides
    (units converted: CPU knobs are microseconds on the CLI,
    nanoseconds in NetConfig)."""
    from shadow_tpu.cli import overrides_from_args

    p = make_parser()
    a = p.parse_args(["conf.xml", "--tcp-ssthresh", "64",
                      "--tcp-windows", "10", "--cpu-threshold", "1000"])
    ov = overrides_from_args(a)
    assert ov["tcp_ssthresh"] == 64 and ov["tcp_windows"] == 10
    assert ov["cpu_threshold_ns"] == 1_000_000
    assert ov["cpu_precision_ns"] == 200_000
    # defaults stay out (loader keeps config/NetConfig values)
    a2 = p.parse_args(["conf.xml"])
    ov2 = overrides_from_args(a2)
    assert "tcp_ssthresh" not in ov2 and "tcp_windows" not in ov2
    assert "cpu_threshold_ns" not in ov2


def test_loader_installs_phold_bulk_and_matches_serial():
    """The loader installs phold's bulk pass on the bundle
    (bundle.app_bulk), and running WITH it is bit-identical to the
    serial engine — the golden contract of net/bulk.py through the
    config path."""
    import numpy as np

    cfg = parse_config(REFERENCE_PHOLD_XML)
    loaded = load(cfg, seed=3)
    assert loaded.bundle.app_bulk is not None
    from shadow_tpu.net.build import run

    sim_a, _ = run(loaded.bundle, app_handlers=loaded.handlers)
    loaded_b = load(cfg, seed=3)
    sim_b, stats_b = run(loaded_b.bundle, app_handlers=loaded_b.handlers,
                         app_bulk=loaded_b.bundle.app_bulk)
    assert int(sim_b.events.overflow) == 0
    np.testing.assert_array_equal(np.asarray(sim_a.app.rcvd),
                                  np.asarray(sim_b.app.rcvd))
    np.testing.assert_array_equal(np.asarray(sim_a.events.time),
                                  np.asarray(sim_b.events.time))


def test_cli_main_sharded_end_to_end(tmp_path):
    """The CLI's --workers N branch end to end: a reference-format
    config runs under an N-device mesh through cli.main (the
    run_sharded path), bit-identical to the serial CLI run — the
    user-facing form of the shard-count-independence contract."""
    import json

    from shadow_tpu.cli import main as cli_main

    conf = tmp_path / "phold.xml"
    conf.write_text(REFERENCE_PHOLD_XML)

    outs = []
    # -w 5 divides the config's 10 hosts exactly (a real 5-shard
    # mesh on the conftest's 8 devices); -w 4 does NOT divide 10 and
    # must ADAPT (largest divisor <= 4 is 2) instead of crashing
    for workers in ("1", "5", "4"):
        import contextlib
        import io

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = cli_main([str(conf), "-w", workers, "--seed", "5",
                           "--platform", "cpu",
                           "-d", str(tmp_path / f"data{workers}")])
        assert rc == 0
        report = json.loads(buf.getvalue().strip().splitlines()[-1])
        assert report["overflow"] == 0
        assert report["events"] > 0
        outs.append(report)

    for other in outs[1:]:
        assert outs[0]["events"] == other["events"]
        assert outs[0]["windows"] == other["windows"]
        assert outs[0].get("app_rcvd") == other.get("app_rcvd")


def test_cli_workers_above_device_count_fails(tmp_path, capsys):
    """-w N with N above the device count is refused, and the message
    names the count: one shard per device, never a silent clamp."""
    from shadow_tpu.cli import main as cli_main

    conf = tmp_path / "phold.xml"
    conf.write_text(REFERENCE_PHOLD_XML)
    rc = cli_main([str(conf), "-w", "9", "--platform", "cpu",
                   "-d", str(tmp_path / "data")])
    assert rc == 1
    err = capsys.readouterr().err
    assert "--workers 9" in err and "8 cpu device(s)" in err
