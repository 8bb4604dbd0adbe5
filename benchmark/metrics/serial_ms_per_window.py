"""Device milliseconds per window in the serial fixpoint: the ops
under the program's `shadow_serial` scope (core/engine.py step_window
around window_fixpoint, full-width or compacted: pop, the handlers of
net/step.py, apply_emissions), self time in the traced window
(trace_layers), over EngineStats.windows. Nothing to read when the
program names no layer."""

from benchmark import trace_layers


def read(record):
    return trace_layers.ms_per_window(record, "shadow_serial")
