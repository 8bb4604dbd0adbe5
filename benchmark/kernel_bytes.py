"""Bytes a kernel's work requires, from the cell's shapes, and the
chip's peaks (peaks.json, keyed by device_kind).

These count what the algorithm must move, not what one implementation
happens to move: a kernel that pads or re-reads does not raise its own
count, so its roofline share shows the waste.
"""

from __future__ import annotations

import json
import pathlib
import re

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
INT32 = 4
# The queue's packed row (core/events._queue_packed): time as two
# words, kind, source, sequence number, then the event's words.
PACKED_FIXED = 5


def peaks(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def mailbox_window(op_text: str) -> tuple[int, int] | None:
    """(hosts, window rows) of a mailbox gather from its instruction's
    text: its result is one [hosts, window, lanes] block of rows per
    destination host ("%mailbox_gather.1 = s32[10240,32,128]...")."""
    m = re.match(r"\s*%?\S+ = s32\[(\d+),(\d+),(\d+)\]", op_text)
    return (int(m.group(1)), int(m.group(2))) if m else None


def mailbox_bytes(hosts: int, window: int, event_words: int) -> int:
    """One mailbox gather: each destination host's window of `window`
    packed rows read from the row-sorted stream and written out, at the
    packed row's real width (not the 128 lanes a DMA pads it to)."""
    return 2 * hosts * window * (PACKED_FIXED + event_words) * INT32
