"""Elastic degraded-mesh recovery: device-loss detection, the
cross-shard integrity sentinel, and shrink-to-survivors planning.

A sharded run is pinned to its launch mesh today: one lost chip (or
one shard silently corrupting its replica of the replicated state)
kills the whole run. Shard-count invariance — results are bit-
identical across {1,8} shards × {K=1,64} chunking, and checkpoints
store the GLOBAL layout — means device loss should cost a resume, not
a run. This module owns the three mechanisms:

1. **Device-loss classification** (`DeviceLossError`, `classify`,
   `guard_dispatch`): XLA surfaces a dead chip as a RuntimeError from
   the next dispatch (or as a dispatch that never completes). The
   guard wraps the chunk/window dispatch callables
   (checkpoint.run_windows `dispatch_wrap`) and converts matching
   errors into a typed `DEVICE_LOST` health fault carrying the failed
   shard/device identity — distinct from sim faults (faults/), which
   are *simulated*; this one is about the machine underneath.

2. **Cross-shard integrity sentinel** (`SentinelState`,
   `attach_sentinel`, `make_sentinel_fn`): inside the jitted window
   body, right after the route barrier restored the replication
   invariant, every shard folds the replicated leaves it carries into
   one u32 digest and compares pmax-vs-pmin across the mesh. Any
   disagreement is silent divergence (an SDC, a miscompiled
   collective, a flipped bit in a replicated table) and latches a
   sticky FATAL `SHARD_DIVERGENCE` trip with the offending shard id.
   None-default opt-in like telemetry: `Sim.sentinel is None` compiles
   to zero ops, so sentinel-off programs are byte-identical to
   pre-sentinel builds.

   What the digest covers — the replicated CONTROL state: exactly the
   leaves that are invariantly replicated at EVERY window barrier (not
   just at chunk exit, where `_replicate_scalars` additionally psums
   the per-shard scalar partials) AND that feed back into simulation
   state: the NetState replicated lookup tables minus the
   per-shard-delta path matrix, plus the replicated injection/
   causality cursors. Per-shard partials (scalar counters inside a
   chunk, lineage rows, `ctr_path_packets`) are legitimately different
   across shards mid-chunk and are excluded by construction. The bulk
   telemetry/flow ring PLANES are also excluded, deliberately: they
   are write-only accumulation buffers drained host-side — a diverged
   ring record corrupts observability output, never the simulation —
   and folding their DUS-updated planes into a per-window reduce sends
   the XLA CPU backend into a pathological multi-hour compile (the
   digest must stay a few fused reduces over lookup tables).

3. **Shrink planning** (`survivor_mesh`, `next_pow2_down`,
   `shard_digests`): given a mesh and a lost shard, build the
   next-pow2-down mesh over the surviving devices. The AOT program
   key includes the shard count and the bucket lattice is pow2, so
   the shrunk program is often already warm. `shard_digests` computes
   the per-shard sha256 the verified-state checkpoint ledger stamps
   (utils/checkpoint.py `save(..., elastic=...)`).

The degradation ladder itself — retry same mesh → shrink to
survivors → serial fallback, resuming from the last *verified*
checkpoint — lives in faults/supervisor.py (`ElasticPolicy` here is
its knob block); the fleet's device-set leases and no-attempt-burn
requeue live in fleet/.
"""

from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax import lax
from jax.tree_util import tree_map_with_path

from shadow_tpu.core import collectives, simtime

I32 = jnp.int32
I64 = jnp.int64
U32 = jnp.uint32

# ---------------------------------------------------------------------
# device-loss classification
# ---------------------------------------------------------------------

# Substrings XLA/PJRT runtimes use when a device (or the transfer path
# to it) died underneath a dispatch. Deliberately broad: a false
# DEVICE_LOST costs one ladder step from a verified checkpoint; a
# missed one costs the run.
_LOSS_MARKERS = (
    "device_lost",
    "device lost",
    "device is lost",
    "device halted",
    "device unavailable",
    "failed to transfer",
    "transfer to device",
    "transfer from device",
    "data transfer failed",
    "device to host copy",
    "unable to enqueue",
    "failed to enqueue",
    "device failure",
    "chip unreachable",
    "ici link",
    "slice has been terminated",
    "core halted",
)


class DeviceLossError(RuntimeError):
    """A dispatch failed (or overran its deadline) because the machine
    underneath lost a device — NOT a simulation fault. Carries the
    failed shard index (-1 = unknown) and device repr for the health
    report and the fleet's elastic block."""

    def __init__(self, message: str, *, shard: int = -1,
                 device: str | None = None, cause: str = "xla_error"):
        super().__init__(message)
        self.shard = int(shard)
        self.device = device
        self.cause = cause

    def as_dict(self) -> dict:
        return {"fault": "DEVICE_LOST", "shard": self.shard,
                "device": self.device, "cause": self.cause,
                "message": str(self)}


def classify(exc: BaseException, *, shards: int = 1,
             elapsed_s: float | None = None,
             deadline_s: float | None = None) -> DeviceLossError | None:
    """Map an exception raised by (or a deadline measured around) a
    device dispatch to a DeviceLossError, or None when it is an
    ordinary error that should propagate as-is. The failed shard is
    parsed from the message when the runtime names a device ordinal;
    -1 (unknown) still drives the ladder — shrink decisions only need
    *that* a shard died, identity is for the report."""
    if isinstance(exc, DeviceLossError):
        return exc
    msg = str(exc).lower()
    hit = any(m in msg for m in _LOSS_MARKERS)
    if not hit and deadline_s is not None and elapsed_s is not None \
            and elapsed_s > deadline_s:
        return DeviceLossError(
            f"dispatch exceeded deadline ({elapsed_s:.1f}s > "
            f"{deadline_s:.1f}s): {exc}", cause="dispatch_deadline")
    if not hit:
        return None
    shard = -1
    for tok in ("device ordinal ", "device id ", "tpu_", "device "):
        i = msg.find(tok)
        if i >= 0:
            tail = msg[i + len(tok):]
            digits = ""
            for ch in tail:
                if ch.isdigit():
                    digits += ch
                else:
                    break
            if digits and int(digits) < max(shards, 1):
                shard = int(digits)
                break
    return DeviceLossError(str(exc), shard=shard, cause="xla_error")


def guard_dispatch(fn, *, shards: int = 1,
                   deadline_s: float | None = None):
    """Wrap a dispatch callable (the chunk/window fn run_windows
    drives): XLA errors matching the loss markers re-raise as
    DeviceLossError, and a *blocking* call that overran `deadline_s`
    raises one too (the dispatch itself is async; the overrun is
    measured when the runtime forces a sync inside the call — a hung
    device stalls exactly there). Ordinary errors propagate
    untouched."""
    def guarded(*args, **kwargs):
        t0 = time.monotonic()
        try:
            return fn(*args, **kwargs)
        except DeviceLossError:
            raise
        except Exception as e:           # noqa: BLE001 — classify-all
            loss = classify(e, shards=shards,
                            elapsed_s=time.monotonic() - t0,
                            deadline_s=deadline_s)
            if loss is not None:
                raise loss from e
            raise
    return guarded


def make_poisoned_dispatch(at_call, *, shard: int = 0,
                           message: str | None = None):
    """A dispatch_wrap that injects device losses: the global dispatch
    counter (shared across supervisor attempts — the wrap is re-applied
    per attempt but the counter persists) raises a DEVICE_LOST-shaped
    RuntimeError at each call index in `at_call` (int or collection),
    so the full classify path is exercised. Consecutive indices take
    the ladder past same-mesh retry into shrink territory. The chaos
    harness (tools/chaos_soak.py --device-loss) and the elastic tests
    use this as the software stand-in for pulling a chip."""
    kills = {int(at_call)} if isinstance(at_call, int) \
        else {int(c) for c in at_call}
    state = {"n": 0}

    def wrap(fn):
        def poisoned(*args, **kwargs):
            n = state["n"]
            state["n"] = n + 1
            if n in kills:
                raise RuntimeError(
                    message or f"INTERNAL: DEVICE_LOST: device ordinal "
                    f"{shard} halted mid-dispatch (injected)")
            return fn(*args, **kwargs)
        return poisoned
    return wrap


# ---------------------------------------------------------------------
# cross-shard integrity sentinel
# ---------------------------------------------------------------------

@struct.dataclass
class SentinelState:
    """Sticky divergence latch — every leaf is a REPLICATED scalar
    (all updates below are pure functions of collectives), so the
    whole subtree pins through _replicate_scalars like the telemetry
    ring (a delta-psum would multiply the counts by the shard
    count)."""

    checks: jax.Array            # [] i64 barrier comparisons performed
    trip: jax.Array              # [] i32 sticky mismatch count
    shard: jax.Array             # [] i32 offending shard of FIRST trip
    tripped_at: jax.Array        # [] i64 wend of first trip (0 before)
    verified_through: jax.Array  # [] i64 last wend verified divergence-free
    digest: jax.Array            # [] u32 last barrier digest (pmax'd)

    @staticmethod
    def create() -> "SentinelState":
        return SentinelState(
            checks=jnp.zeros((), I64),
            trip=jnp.zeros((), I32),
            shard=jnp.full((), -1, I32),
            tripped_at=jnp.zeros((), I64),
            verified_through=jnp.zeros((), I64),
            digest=jnp.zeros((), U32),
        )


def attach_sentinel(sim):
    """Return `sim` with the integrity sentinel attached (no-op if one
    already is). Same opt-in contract as telemetry.attach: Sim.sentinel
    defaults to None and contributes no pytree leaves, so sentinel-off
    checkpoints and compiled programs are byte-identical."""
    if getattr(sim, "sentinel", None) is not None:
        return sim
    return sim.replace(sentinel=SentinelState.create())


_GOLDEN = np.uint32(2654435761)      # Knuth multiplicative hash
_PRIME = np.uint32(16777619)         # FNV prime


def _fold_u32(acc, x):
    """Fold a u32 array into the running u32 digest: a position-
    weighted wraparound sum (so permutations change the digest), mixed
    multiplicatively. Pure vector ops — one fused reduce per leaf."""
    n = x.size
    w = (jnp.arange(n, dtype=U32) * _GOLDEN + U32(1)).reshape(x.shape)
    s = jnp.sum(x * w, dtype=U32)
    return (acc * _PRIME) ^ (s + acc)


def _leaf_u32(leaf):
    """View any leaf's bits as u32 words (i64 splits into lo/hi)."""
    x = jnp.asarray(leaf)
    if x.dtype == jnp.bool_:
        return [x.astype(U32)]
    if jnp.issubdtype(x.dtype, jnp.floating):
        return [lax.bitcast_convert_type(x.astype(jnp.float32), U32)]
    if x.dtype.itemsize == 8:
        return [(x & 0xFFFFFFFF).astype(U32),
                ((x >> 32) & 0xFFFFFFFF).astype(U32)]
    return [x.astype(U32)]


def _replicated_digest_leaves(sim):
    """The leaves the per-barrier digest covers (module docstring §2):
    invariantly replicated at every window barrier. Returns a flat
    list of arrays."""
    from shadow_tpu.net.state import REPLICATED_FIELDS

    out = []
    net = getattr(sim, "net", None)
    if net is not None:
        for name in sorted(REPLICATED_FIELDS):
            if name == "ctr_path_packets":
                continue  # per-shard scatter-add deltas mid-chunk
            out.append(getattr(net, name))
    # telemetry/flow rings are deliberately NOT covered: their planes
    # are write-only observability buffers (drained host-side, never
    # read back by the simulation), and reducing the DUS-updated
    # planes every window drives the XLA CPU backend into a
    # pathological compile (module docstring §2)
    inject = getattr(sim, "inject", None)
    if inject is not None:
        # only the replicated cursors — the cumulative counters are
        # per-shard partials inside a chunk (parallel/shard.py)
        out.extend([inject.seq_floor, inject.horizon])
    caus = getattr(sim, "causality", None)
    if caus is not None:
        out.append(caus.adv_count)
    return out


def digest_replicated(sim, wend) -> jax.Array:
    """One u32 digest over the replicated-at-barrier leaves + wend.

    All leaf words concatenate into ONE flat u32 vector folded by a
    single position-weighted reduce — the weight vector is a folded
    compile-time constant, so the whole digest lowers to the per-leaf
    word converts plus one concat and one fused multiply-reduce. The
    op count per window stays ~flat in the leaf count; a per-leaf
    fold chain (~5 ops x ~40 words) costs measurable dispatch
    overhead per window on small-host CPU shapes."""
    words = []
    for word in _leaf_u32(jnp.asarray(wend, simtime.DTYPE)):
        words.append(word.reshape(-1))
    for leaf in _replicated_digest_leaves(sim):
        for word in _leaf_u32(leaf):
            words.append(word.reshape(-1))
    flat = jnp.concatenate(words) if len(words) > 1 else words[0]
    acc = jnp.asarray(0x811C9DC5, U32)   # FNV offset basis
    return _fold_u32(acc, flat)


def make_sentinel_fn(axis: str | None = None):
    """Build the engine's sentinel_fn(sim, wend) -> sim barrier hook
    (core/engine.step_window runs it after route_fn + the lane
    barrier). `axis` names the shard_map mesh axis; None compiles the
    single-shard identity reductions — the digest is still computed
    and `verified_through` still advances (serial runs get the same
    verified-state ledger), but pmax == pmin by construction so a
    serial run can never trip.

    Replication: every SentinelState update below is a pure function
    of collectives (pmax/pmin/psum) and the replicated wend, so the
    new state is identical on every shard — _replicate_scalars pins
    the subtree rather than delta-psumming it.

    When sim.sentinel is None the hook is a trace-time no-op: zero ops
    in the compiled program (the byte-identity contract)."""

    def sentinel_fn(sim, wend):
        st = getattr(sim, "sentinel", None)
        if st is None:
            return sim
        d = digest_replicated(sim, wend)
        wend64 = jnp.asarray(wend, simtime.DTYPE)
        if axis is None:
            dmax = dmin = d
            offender = jnp.full((), -1, I32)
        else:
            dmax = collectives.pmax(d, axis)
            dmin = collectives.pmin(d, axis)
            n = lax.psum(jnp.ones((), I32), axis)
            n_max = lax.psum((d == dmax).astype(I32), axis)
            # suspects = the minority digest's holders (ties blame the
            # dmax holders, deterministically); offender = the lowest
            # suspect shard index — replicated via the pmin
            minority_is_max = n_max * 2 <= n
            suspect = jnp.where(minority_is_max, d == dmax, d != dmax)
            idx = lax.axis_index(axis).astype(I32)
            offender = collectives.pmin(jnp.where(suspect, idx, n), axis)
        mismatch = dmax != dmin
        first = mismatch & (st.trip == 0)
        trip = st.trip + mismatch.astype(I32)
        st = st.replace(
            checks=st.checks + 1,
            trip=trip,
            shard=jnp.where(first, offender, st.shard),
            tripped_at=jnp.where(first, wend64, st.tripped_at),
            # a barrier only extends the verified prefix while the
            # latch is clean — everything after a trip is suspect
            verified_through=jnp.where(
                trip == 0, wend64, st.verified_through),
            digest=dmax,
        )
        return sim.replace(sentinel=st)

    return sentinel_fn


def make_divergence_fault_fn(axis: str, *, shard: int, at_ns: int,
                             inner=None):
    """TEST/CHAOS helper: a fault_fn that corrupts ONE shard's replica
    of a replicated table (latency_ns[0, 0] += 1) from `at_ns` on —
    the software stand-in for a replicated-memory bit flip. Composes
    over an existing fault_fn via `inner`."""
    def fault_fn(sim, wend):
        if inner is not None:
            sim = inner(sim, wend)
        idx = lax.axis_index(axis).astype(I32)
        hit = (idx == shard) & (jnp.asarray(wend, simtime.DTYPE)
                                >= at_ns)
        lat = sim.net.latency_ns
        bumped = lat.at[0, 0].add(1)
        return sim.replace(net=sim.net.replace(
            latency_ns=jnp.where(hit, bumped, lat)))
    return fault_fn


# ---------------------------------------------------------------------
# shrink planning
# ---------------------------------------------------------------------

def next_pow2_down(n: int) -> int:
    """Largest power of two <= n (>= 1)."""
    if n < 1:
        raise ValueError(f"no pow2 <= {n}")
    return 1 << (int(n).bit_length() - 1)


def survivor_mesh(mesh, axis: str, lost_shard: int):
    """Build the next-pow2-down mesh over the devices that survive
    losing `lost_shard` (-1 = unknown: drop the LAST shard — any
    pow2-down subset works, the layout is global). Returns
    (new_mesh, new_shards) or (None, 1) when the survivors can only
    carry a serial run."""
    from jax.sharding import Mesh

    devices = list(np.asarray(mesh.devices).reshape(-1))
    n = len(devices)
    drop = lost_shard if 0 <= lost_shard < n else n - 1
    survivors = [d for i, d in enumerate(devices) if i != drop]
    new_n = next_pow2_down(max(len(survivors), 1))
    if new_n < 2:
        return None, 1
    return Mesh(np.array(survivors[:new_n]), (axis,)), new_n


def shard_digests(sim, shards: int, axis: str = "hosts") -> list[str]:
    """Host-side per-shard sha256 over the checkpoint's leaves, split
    the way sim_specs shards them: leading-H leaves contribute shard
    s's row block to digest s; replicated leaves contribute whole to
    every shard's digest. Shard s's digest is therefore invariant
    under re-partitioning onto any mesh that still assigns it those
    rows — the verified-state ledger's integrity stamp
    (utils/checkpoint.py)."""
    from jax.sharding import PartitionSpec as P

    from shadow_tpu.parallel.shard import sim_specs

    shards = max(int(shards), 1)
    hashes = [hashlib.sha256() for _ in range(shards)]
    specs = sim_specs(sim, axis)
    flat_vals = jax.tree_util.tree_flatten_with_path(sim)[0]
    flat_specs = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(flat_vals, flat_specs):
        arr = np.asarray(leaf)
        name = jax.tree_util.keystr(path).encode()
        sharded = (isinstance(spec, P) and len(spec) > 0
                   and spec[0] is not None and arr.ndim > 0
                   and arr.shape[0] % shards == 0)
        if sharded:
            per = arr.shape[0] // shards
            for s in range(shards):
                hashes[s].update(name)
                hashes[s].update(
                    np.ascontiguousarray(arr[s * per:(s + 1) * per])
                    .tobytes())
        else:
            blob = np.ascontiguousarray(arr).tobytes()
            for h in hashes:
                h.update(name)
                h.update(blob)
    return [h.hexdigest() for h in hashes]


# ---------------------------------------------------------------------
# the supervisor's ladder knobs
# ---------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ElasticPolicy:
    """Knobs for the device-loss degradation ladder
    (faults/supervisor.py): retry same mesh → shrink to survivors →
    serial fallback, resuming from the last VERIFIED checkpoint.
    Ladder steps do NOT burn the failure retry budget (like
    escalation heals: the sim did nothing wrong)."""

    same_mesh_retries: int = 1       # re-dispatch on the full mesh first
    allow_shrink: bool = True        # next-pow2-down onto survivors
    allow_serial: bool = True        # final rung: mesh=None
    min_shards: int = 1              # stop shrinking below this
    max_losses: int = 8              # total DEVICE_LOST budget per run
    dispatch_deadline_s: float | None = None  # hung-dispatch overrun

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def sentinel_report(sim) -> dict | None:
    """Host-side summary of the sentinel latch for manifests/health.
    None when the sentinel is not attached."""
    st = getattr(sim, "sentinel", None)
    if st is None:
        return None
    return {
        "checks": int(np.asarray(st.checks)),
        "trips": int(np.asarray(st.trip)),
        "shard": int(np.asarray(st.shard)),
        "tripped_at_ns": int(np.asarray(st.tripped_at)),
        "verified_through_ns": int(np.asarray(st.verified_through)),
        "digest": int(np.asarray(st.digest)),
    }
