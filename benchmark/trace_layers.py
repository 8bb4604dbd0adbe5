"""Device time per layer of the program, from the names it gives its
ops.

The program runs each layer of its window step under a
`jax.named_scope` (core/engine.py, core/events.py, parallel/shard.py):
shadow_window, shadow_bulk, shadow_serial, shadow_route (with the
insert's steps sort, permute, count, sweep, mailbox and scatter nested
inside it), shadow_barrier and shadow_exchange. The names ride each
op's op_name path into the compiled program, and the profiler writes
that path as the `tf_op` stat of the op's event metadata.
jax.profiler.ProfileData does not expose event metadata, so this
module reads the .xplane.pb's protobuf wire format itself (XSpace ->
XPlane -> lines, event_metadata, stat_metadata; no dependency beyond
the standard library).

Each op's self time inside the window (trace_reduce.self_times, on
the same events and times as trace_reduce) is charged to the
innermost layer scope on its path, and route time to its step as
`shadow_route/<step>` (`shadow_route` alone for the route's own glue).
Ops whose path names no layer are charged to `other`; ops with no
path at all (copies XLA inserts) to `unscoped`. The result,
`layer_s`, is seconds per device and partitions trace_reduce's busy_s.
"""

from __future__ import annotations

import gzip
import json
import pathlib
import sys

from benchmark import trace_reduce

LAYERS = ("shadow_window", "shadow_bulk", "shadow_serial", "shadow_route",
          "shadow_barrier", "shadow_exchange")
ROUTE = "shadow_route"
ROUTE_STEPS = ("sort", "permute", "count", "sweep", "mailbox", "scatter")
OTHER = "other"
UNSCOPED = "unscoped"
TOP = 5

# XPlane proto field numbers (tsl/profiler/protobuf/xplane.proto)
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_MD_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_EMD_ID, _EMD_NAME, _EMD_DISPLAY_NAME, _EMD_STATS = 1, 2, 4, 5
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_SMD_ID, _SMD_NAME = 1, 2
_MAP_VALUE = 2
TF_OP = "tf_op"


def _varint(b, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b, i: int, end: int):
    """(field number, value) of each field of the message in b[i:end]:
    an int for a varint, (start, stop) for a length-delimited field,
    the raw bytes for a fixed-width one."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v = (i, i + n)
            i += n
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _str(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def _events(b, start: int, stop: int, t0_ps: int) -> list:
    """[(metadata id, start_ns, end_ns)] of one line, in file order,
    at the whole nanoseconds ProfileData gives."""
    out = []
    for num, span in _fields(b, start, stop):
        if num != _LINE_EVENTS:
            continue
        mid = off = dur = 0
        for f, v in _fields(b, span[0], span[1]):
            if f == _EVENT_MD_ID:
                mid = v
            elif f == _EVENT_OFFSET_PS:
                off = v
            elif f == _EVENT_DURATION_PS:
                dur = v
        a = (t0_ps + off) // 1000
        out.append((mid, float(a), float(a + dur // 1000)))
    return out


def _plane(b, start: int, stop: int) -> dict:
    name, lines, event_md, stat_md = "", [], {}, {}
    for num, v in _fields(b, start, stop):
        if num == _PLANE_NAME:
            name = _str(b, v)
        elif num == _PLANE_LINES:
            lines.append(v)
        elif num in (_PLANE_EVENT_MD, _PLANE_STAT_MD):
            value = next((s for f, s in _fields(b, *v) if f == _MAP_VALUE),
                         None)
            if value is not None:
                (event_md if num == _PLANE_EVENT_MD else stat_md)[v] = value
    stat_names = {}
    for span in stat_md.values():
        sid, sname = 0, ""
        for f, v in _fields(b, *span):
            if f == _SMD_ID:
                sid = v
            elif f == _SMD_NAME:
                sname = _str(b, v)
        stat_names[sid] = sname
    tf_op_ids = {k for k, v in stat_names.items() if v == TF_OP}
    names, paths = {}, {}
    for span in event_md.values():
        mid, ename, display, path = 0, "", "", ""
        for f, v in _fields(b, *span):
            if f == _EMD_ID:
                mid = v
            elif f == _EMD_NAME:
                ename = _str(b, v)
            elif f == _EMD_DISPLAY_NAME:
                display = _str(b, v)
            elif f == _EMD_STATS:
                path = _tf_op(b, v, tf_op_ids, stat_names) or path
        names[mid] = ename or display
        paths[mid] = path
    out_lines = []
    for span in lines:
        lname, t0 = "", 0
        for f, v in _fields(b, *span):
            if f == _LINE_NAME:
                lname = _str(b, v)
            elif f == _LINE_TIMESTAMP_NS:
                t0 = v
        out_lines.append({"name": lname,
                          "events": _events(b, span[0], span[1], t0 * 1000)})
    return {"name": name, "lines": out_lines, "names": names,
            "paths": paths}


def _tf_op(b, span, tf_op_ids, stat_names) -> str | None:
    sid, value = None, None
    for f, v in _fields(b, *span):
        if f == _STAT_MD_ID:
            sid = v
        elif f == _STAT_STR:
            value = _str(b, v)
        elif f == _STAT_REF:
            value = stat_names.get(v)
    return value if sid in tf_op_ids else None


def load_planes(path) -> list[dict]:
    """Each plane of an .xplane.pb (or a gzipped one, .gz) as
    {name, lines: [{name, events: [(metadata id, start_ns, end_ns)]}],
    names: {metadata id: event name}, paths: {metadata id: tf_op}}."""
    path = pathlib.Path(path)
    raw = path.read_bytes()
    if path.suffix == ".gz":
        raw = gzip.decompress(raw)
    b = memoryview(raw)
    return [_plane(b, *span) for num, span in _fields(b, 0, len(b))
            if num == _SPACE_PLANES]


def layer_of(path: str) -> str:
    """The layer an op's op_name path charges: its innermost layer
    scope (and for the route, the step inside it), `other` for a path
    that names none, `unscoped` for no path. The path's last name is
    the op's own primitive, never a scope."""
    if not path:
        return UNSCOPED
    names = path.split("/")[:-1]
    for i in range(len(names) - 1, -1, -1):
        if names[i] in LAYERS:
            if names[i] != ROUTE:
                return names[i]
            step = next((n for n in names[i + 1:] if n in ROUTE_STEPS),
                        None)
            return f"{ROUTE}/{step}" if step else ROUTE
    return OTHER


def reduce_layers(planes: list[dict], *, window_span: str) -> dict:
    """layer_s (seconds per device by layer) inside the window span,
    its sum (busy_s), and the largest ops of `other` and `unscoped`
    by instruction name."""
    spans = [(a, b_) for p in planes if p["name"].startswith("/host:")
             for ln in p["lines"] for mid, a, b_ in ln["events"]
             if p["names"].get(mid) == window_span]
    if not spans:
        raise ValueError(f"no host span {window_span!r} in the trace")
    w0, w1 = spans[0]
    layer_ns: dict[str, float] = {}
    loose: dict[str, dict[str, float]] = {OTHER: {}, UNSCOPED: {}}
    nd = 0
    for p in planes:
        if not p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            if ln["name"] != trace_reduce.OPS_LINE:
                continue
            nd += 1
            clipped = [(mid, max(a, w0), min(b_, w1))
                       for mid, a, b_ in ln["events"]
                       if min(b_, w1) > max(a, w0)]
            for mid, t in trace_reduce.self_times(clipped):
                layer = layer_of(p["paths"].get(mid, ""))
                layer_ns[layer] = layer_ns.get(layer, 0.0) + t
                if layer in loose:
                    op = trace_reduce.short_name(p["names"].get(mid, ""))
                    loose[layer][op] = loose[layer].get(op, 0.0) + t
    if not nd:
        raise ValueError("no device plane with an ops line in the trace")
    layer_s = {k: v / nd / 1e9 for k, v in sorted(layer_ns.items())}
    top = {k: [[op, t / nd / 1e9] for op, t in
               sorted(v.items(), key=lambda kv: -kv[1])[:TOP]]
           for k, v in loose.items()}
    return {"layer_s": layer_s, "busy_s": sum(layer_s.values()),
            "devices": nd, "top": top}


def layer_total(layer_s: dict, layer: str) -> float:
    """Seconds of one layer, its steps included."""
    return sum(t for k, t in layer_s.items()
               if k == layer or k.startswith(layer + "/"))


def for_record(record: dict) -> dict | None:
    """The layer reduction of the traced run that `record` describes
    (the newest trace under the benchmark's trace directory), computed
    once per record, which every reader of the run is handed, and
    printed to stderr as one JSON line. None when the program names no
    layer, as before it had scopes. Raises when the reduction does not
    partition the record's busy time, which would mean the trace is
    not the record's."""
    if "layers" not in record:
        from benchmark import run

        r = reduce_layers(load_planes(trace_reduce.find_xplane(run.TRACE_DIR)),
                          window_span=run.SPAN_WINDOW)
        busy = record["trace"]["busy_s"]
        if abs(r["busy_s"] - busy) > 1e-6 * busy:
            raise ValueError(
                f"layer_s sums to {r['busy_s']} s, the trace's busy time "
                f"is {busy} s")
        print(json.dumps({"layer_s": r["layer_s"], "top_ops": r["top"]}),
              file=sys.stderr)
        record["layers"] = r
    r = record["layers"]
    if not any(layer_total(r["layer_s"], k) > 0 for k in LAYERS):
        return None
    return r


def ms_per_window(record: dict, layer: str) -> float | None:
    """Device milliseconds of `layer` per window the run closed."""
    r = for_record(record)
    windows = record["totals"]["windows"]
    if r is None or not windows:
        return None
    return 1e3 * layer_total(r["layer_s"], layer) / windows
