"""Conservative windows the run driver (core/engine.py) closed per
second of the traced window: EngineStats.windows over its wall time."""


def read(record):
    return record["totals"]["windows"] / record["wall_s"]
