"""Native (C++) runtime components, loaded via ctypes.

The reference keeps its hot irregular bookkeeping native (the C++
retransmit tally, tcp_retransmit_tally.cc; glib C for everything
else). This package mirrors that split: JAX/XLA owns the device
compute path, and host-side runtime pieces with irregular data
structures live in libshadow_native.so:

- retransmit tally: interval-set SACK/loss scoreboard (tally.py)
- payload pool: refcounted byte store behind device payloadRef ids
  (pool.py)
- logsort: stable (time, seq) argsort for the log writer

Every process runs `make` before loading the library (a no-op when
it is up to date), so the library loaded always matches src/ — never
a stale build left beside the checkout. g++ is part of the
toolchain; everything has a pure-Python fallback so the package works
where the build fails.
"""

from __future__ import annotations

import ctypes
import pathlib
import subprocess

_DIR = pathlib.Path(__file__).resolve().parent
_LIB_PATH = _DIR / "libshadow_native.so"

_lib = None
_tried = False


def _build() -> bool:
    try:
        subprocess.run(["make", "-s", "-C", str(_DIR)], check=True,
                       capture_output=True, timeout=120)
        return _LIB_PATH.exists()
    except Exception:
        return False


def load() -> ctypes.CDLL | None:
    """Load (building if needed) the native library; None if
    unavailable — callers fall back to Python implementations."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if not _build():
        return None
    try:
        lib = ctypes.CDLL(str(_LIB_PATH))
    except OSError:
        return None
    # signatures
    i64, i32, vp = ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p
    p_i64 = ctypes.POINTER(ctypes.c_int64)
    p_u8 = ctypes.POINTER(ctypes.c_uint8)
    lib.retransmit_tally_new.restype = vp
    lib.retransmit_tally_new.argtypes = [i64]
    lib.retransmit_tally_free.argtypes = [vp]
    for f in ("sacked", "retransmitted", "mark_lost"):
        fn = getattr(lib, f"retransmit_tally_{f}")
        fn.argtypes = [vp, i64, i64]
    lib.retransmit_tally_dupl_ack.argtypes = [vp]
    lib.retransmit_tally_set_recovery_point.argtypes = [vp, i64]
    lib.retransmit_tally_advance.argtypes = [vp, i64]
    lib.retransmit_tally_is_sacked.restype = i32
    lib.retransmit_tally_is_sacked.argtypes = [vp, i64, i64]
    lib.retransmit_tally_lost_ranges.restype = i32
    lib.retransmit_tally_lost_ranges.argtypes = [vp, p_i64, p_i64, i32]
    lib.retransmit_tally_sacked_bytes.restype = i64
    lib.retransmit_tally_sacked_bytes.argtypes = [vp]

    lib.payload_pool_new.restype = vp
    lib.payload_pool_free.argtypes = [vp]
    lib.payload_pool_put.restype = i32
    lib.payload_pool_put.argtypes = [vp, p_u8, i64]
    lib.payload_pool_ref.restype = i32
    lib.payload_pool_ref.argtypes = [vp, i32]
    lib.payload_pool_unref.restype = i32
    lib.payload_pool_unref.argtypes = [vp, i32]
    lib.payload_pool_len.restype = i64
    lib.payload_pool_len.argtypes = [vp, i32]
    lib.payload_pool_get.restype = i64
    lib.payload_pool_get.argtypes = [vp, i32, p_u8, i64]
    lib.payload_pool_live_bytes.restype = i64
    lib.payload_pool_live_bytes.argtypes = [vp]
    lib.payload_pool_total_allocs.restype = i64
    lib.payload_pool_total_allocs.argtypes = [vp]
    lib.payload_pool_live_count.restype = i64
    lib.payload_pool_live_count.argtypes = [vp]
    lib.payload_pool_live_ids.restype = i64
    lib.payload_pool_live_ids.argtypes = [vp, ctypes.POINTER(i32), i64]

    lib.logsort_argsort.argtypes = [p_i64, p_i64, i64, p_i64]
    _lib = lib
    return _lib
