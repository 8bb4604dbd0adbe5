"""From a profiler trace (the .xplane.pb that jax.profiler writes) to
the numbers the per-layer metrics read.

- Device ops: the events of each device plane's "XLA Ops" line.
  Busy time is the union of their intervals inside the window (ops
  that overlap count once), averaged over the devices; the idle
  share is 1 - busy / window.
- The window is the host span the benchmark wraps around its timed
  loop (`window_span`).
- Idle gaps: the stretches inside the window where no op ran on the
  first device, each labelled by the benchmark's host span that
  overlaps it most ("none" where no span does).
- Time by op: the ops line nests (a while loop holds its body's
  ops), so each op is charged its self time, summed by instruction
  name over its events clipped to the window, averaged over the
  devices. Kernel time is that of the names
  a reader asks for; collective time that of all-to-all, all-reduce,
  all-gather, reduce-scatter and collective-permute ops.
"""

from __future__ import annotations

import pathlib

OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute")
TOP = 10


def find_xplane(trace_dir: pathlib.Path) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_planes(path: pathlib.Path) -> list[dict]:
    """[{name, lines: [{name, events: [(name, start_ns, end_ns)]}]}]
    from an .xplane.pb, or a gzipped one (.gz)."""
    import gzip

    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.suffix == ".gz":
        pd = ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    else:
        pd = ProfileData.from_file(str(path))
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [(e.name, float(e.start_ns),
                                    float(e.start_ns + e.duration_ns))
                                   for e in ln.events]}
                       for ln in p.lines]}
            for p in pd.planes]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted intervals covering the same time."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def short_name(text: str) -> str:
    """An ops-line event's name is its HLO instruction's text
    ("%fusion.12 = f32[...] fusion(...), ..."); the instruction's own
    name ("fusion.12") is what the text starts with."""
    head = text.split(" = ", 1)[0] if " = " in text else text
    return head.lstrip("%").strip()


def self_times(events) -> list[tuple[str, float]]:
    """(name, self time) of nested events on one line: an op's time
    less that of the ops inside it (a while loop's body ops, say)."""
    out = []
    stack: list[list] = []   # [name, end, self]
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            n, _, t = stack.pop()
            out.append((n, t))
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    out.extend((n, t) for n, _, t in stack)
    return out


def _clip(events, w0: float, w1: float):
    for name, a, b in events:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            yield name, a, b


def device_ops(planes: list[dict]) -> list[list[tuple[str, float, float]]]:
    """Per device plane, the op events of its ops line."""
    out = []
    for p in planes:
        if not p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            if ln["name"] == OPS_LINE:
                out.append(ln["events"])
    return out


def host_events(planes: list[dict], names) -> list[tuple[str, float, float]]:
    names = set(names)
    return [e for p in planes if p["name"].startswith("/host:")
            for ln in p["lines"] for e in ln["events"] if e[0] in names]


def reduce_planes(planes: list[dict], *, window_span: str,
                  host_spans=()) -> dict:
    spans = host_events(planes, (window_span,))
    if not spans:
        raise ValueError(f"no host span {window_span!r} in the trace")
    w0, w1 = spans[0][1], spans[0][2]
    devices = device_ops(planes)
    if not devices:
        raise ValueError("no device plane with an ops line in the trace")
    busy, by_name, op_text = [], {}, {}
    for evs in devices:
        clipped = list(_clip(evs, w0, w1))
        busy.append(sum(b - a for a, b in union([(a, b)
                                                 for _, a, b in clipped])))
        for text, t_self in self_times(clipped):
            name = short_name(text)
            op_text.setdefault(name, text)
            t, c = by_name.get(name, (0.0, 0))
            by_name[name] = (t + t_self, c + 1)
    nd = len(devices)
    # self seconds and calls per device, by instruction name
    op_s = {k: (t / nd / 1e9, c / nd) for k, (t, c) in by_name.items()}

    merged = union([(a, b) for _, a, b in _clip(devices[0], w0, w1)])
    edges = [w0] + [x for ab in merged for x in ab] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    labels = host_events(planes, host_spans)
    idle = []
    for a, b in gaps:
        best, label = 0.0, "none"
        for name, s, e in labels:
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, label = ov, name
        idle.append([label, (b - a) / 1e9])
    idle.sort(key=lambda x: -x[1])
    window_s = (w1 - w0) / 1e9
    busy_s = sum(busy) / nd / 1e9
    top = sorted(op_s.items(), key=lambda kv: -kv[1][0])[:TOP]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "devices": nd,
        "op_s": op_s,
        "op_text": op_text,
        "top_ops": [[k, v[0]] for k, v in top],
        "idle_gaps": idle[:TOP],
        "collective_s": sum(t for k, (t, _) in op_s.items()
                            if any(c in k for c in COLLECTIVES)),
    }


def kernel_time(reduced: dict, prefix: str) -> tuple[float, float, list]:
    """(seconds, calls, instruction texts) per device of the ops whose
    instruction name starts with `prefix`."""
    t = c = 0.0
    texts = []
    for k, (s, n) in reduced["op_s"].items():
        if k.startswith(prefix):
            t, c = t + s, c + n
            texts.append(reduced["op_text"][k])
    return t, c, texts


def reduce(trace_dir, *, window_span: str, host_spans=()) -> dict:
    return reduce_planes(load_planes(find_xplane(trace_dir)),
                         window_span=window_span, host_spans=host_spans)
