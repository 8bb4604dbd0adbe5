"""Device milliseconds per window in the bulk window pass: the ops
under the program's `shadow_bulk` scope (core/engine.py step_window
around bulk_fn: net/bulk.py, net/tcp_bulk.py), self time in the
traced window (trace_layers), over EngineStats.windows. Nothing to
read when the program names no layer."""

from benchmark import trace_layers


def read(record):
    return trace_layers.ms_per_window(record, "shadow_bulk")
