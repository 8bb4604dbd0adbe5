"""Op-level device profile of the window loop via jax.profiler (the
trace.json.gz carries real per-fusion device durations). Prints the top device ops by total time with their
HLO-metadata source locations when resolvable.

Usage:  python tools/profile_trace.py [--hosts 10240] [--load 8]
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from tools.perfutil import build_warm_phold


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hosts", type=int, default=10240)
    ap.add_argument("--load", type=int, default=8)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()
    print(f"backend: {jax.default_backend()}")

    w = build_warm_phold(args.hosts, args.load)
    sim, wstart, one_window = w["sim"], w["wstart"], w["one_window"]

    tracedir = tempfile.mkdtemp(prefix="shadowtpu_trace_")
    with jax.profiler.trace(tracedir):
        out = None
        for _ in range(args.calls):
            out = one_window(sim, wstart)
        jax.block_until_ready(out)

    files = glob.glob(os.path.join(tracedir, "**", "*.trace.json.gz"),
                      recursive=True)
    if not files:
        print(f"no trace produced under {tracedir}")
        return
    with gzip.open(files[0]) as f:
        tr = json.load(f)
    ev = tr["traceEvents"] if isinstance(tr, dict) else tr
    pids = {}
    for e in ev:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pids[e["pid"]] = e["args"].get("name", "")
    dur = collections.Counter()
    cnt = collections.Counter()
    for e in ev:
        if e.get("ph") == "X" and "dur" in e:
            pname = pids.get(e["pid"], "")
            if "TPU" in pname or "/device" in pname.lower():
                dur[e["name"]] += e["dur"]
                cnt[e["name"]] += 1
    tot = sum(dur.values())
    print(f"total device op time: {tot / 1e3:.1f} ms over {args.calls} "
          f"calls ({tot / 1e3 / args.calls:.1f} ms/call)")
    for name, d in dur.most_common(args.top):
        print(f"{d / 1e3 / args.calls:9.2f} ms/call  x{cnt[name] // args.calls:4d}  {name[:90]}")
    print(f"trace dir kept at {tracedir}")


if __name__ == "__main__":
    main()
