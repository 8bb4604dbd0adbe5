"""Serial micro-steps per window (EngineStats.micro_steps / windows):
falls when a bulk pass commits more of a window at once."""


def read(record):
    w = record["totals"]["windows"]
    return record["totals"]["micro_steps"] / w if w else None
