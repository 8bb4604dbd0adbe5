"""The Pallas mailbox gather's (core/insert_pallas.py) share of its
HBM roofline: the bytes its work requires (kernel_bytes.mailbox_bytes,
from the kernel's result shape in the trace and the queue's row width)
at the chip's peak bandwidth, over its mean device time per call in
the trace. Nothing to read, and no value, when the trace shows no
call of the kernel."""

from benchmark import kernel_bytes, trace_reduce


def read(record):
    secs, calls, texts = trace_reduce.kernel_time(record["trace"],
                                                  "mailbox_gather")
    wins = {kernel_bytes.mailbox_window(t) for t in texts}
    if calls == 0 or secs <= 0 or len(wins) != 1 or None in wins:
        return None
    hosts, window = wins.pop()
    need = kernel_bytes.mailbox_bytes(hosts, window,
                                      record["shapes"]["event_words"])
    peak = kernel_bytes.peaks(record["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / (secs / calls)
