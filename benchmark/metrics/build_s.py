"""Seconds of the host build (net/build.py and the app's setup), from
the benchmark's own span around it."""


def read(record):
    return record["spans"]["build_s"]
