"""Device milliseconds per window in route/insert: the ops under the
program's `shadow_route` scope (core/engine.py step_window; its steps
sort, permute, count, sweep, mailbox and scatter in core/events.py),
self time in the traced window (trace_layers), over EngineStats.windows.
Nothing to read when the program names no layer."""

from benchmark import trace_layers


def read(record):
    return trace_layers.ms_per_window(record, trace_layers.ROUTE)
