"""Persistent AOT program store: compile once per program key, serve
serialized executables on every later run.

One entry per program key (compile/buckets.py): `<key>.bin` holds the
pickled `jax.experimental.serialize_executable.serialize(...)` payload
and `<key>.json` a human-readable sidecar (avals digest, code/jax
versions, machine fingerprint, sizes, timings). The store lives under
the claimed compile-cache directory (utils/compcache.py), so the same
machine-fingerprint claim/redirect discipline that protects JAX's own
persistent cache protects the AOT entries: a host with different CPU
features is redirected to its own namespace and never loads foreign
XLA:CPU AOT code.

Safety over speed, always: any corruption, version skew, avals
mismatch, or deserialization error degrades to a fresh
`lower().compile()` — a broken cache entry may cost one compile,
never a crash and never a wrong program. Writes are atomic
(tmp + os.replace), so a killed worker leaves no torn entries.
"""

from __future__ import annotations

import hashlib
import json
import os
import pathlib
import pickle
import time

from shadow_tpu.compile import buckets

STORE_VERSION = 1


def _avals_digest(args, kwargs=None) -> str:
    """Digest of the example call's abstract values (shape/dtype
    tree). The program key should already pin these; the digest is the
    backstop that turns an under-keyed collision into a miss instead
    of a wrongly-served program."""
    import jax
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
    parts = [str(treedef)]
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            parts.append(f"{tuple(leaf.shape)}:{leaf.dtype}")
        else:
            # python scalar: weak-typed at trace time — tag it so a
            # scalar arg and a committed array arg never alias
            parts.append(f"py:{type(leaf).__name__}:"
                         f"{np.asarray(leaf).dtype}")
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


def _compile_outside_xla_cache(lowered):
    """lowered.compile() with jax's persistent compilation cache
    bypassed for this one call. An executable SERVED from that cache
    serializes into a payload whose fusion symbols cannot be re-linked
    at deserialize time (XLA:CPU "Symbols not found"), which would
    poison the store: every save after the first would overwrite a
    good entry with an unloadable one. On this path the AOT store IS
    the persistence layer, so bypassing the XLA cache costs only the
    one fresh compile the store exists to amortize.

    Nulling the config dir alone is NOT enough: the cache module
    latches an is-cache-used bit and the cache object itself at first
    use, so a process that already compiled anything keeps serving
    from the old dir. reset_cache() drops the latch; a second reset
    in the finally re-latches with the restored dir for every later
    ordinary compile in this process."""
    import jax

    try:
        from jax._src import compilation_cache as _cc
    except Exception:
        _cc = None

    prev = jax.config.jax_compilation_cache_dir
    if not prev:
        return lowered.compile()
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        if _cc is not None:
            _cc.reset_cache()
        return lowered.compile()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        if _cc is not None:
            _cc.reset_cache()


def default_root() -> pathlib.Path:
    """Store root: $SHADOW_AOT_DIR, else `aot/` inside the shared
    compile-cache dir (utils/compcache.cache_dir: exactly
    $JAX_COMPILATION_CACHE_DIR when set, else the claimed repo-local
    dir, so foreign-featured hosts get their own namespace exactly
    like the JAX cache)."""
    env = os.environ.get("SHADOW_AOT_DIR")
    if env:
        return pathlib.Path(env)
    from shadow_tpu.utils.compcache import cache_dir

    return cache_dir(log=lambda m: None) / "aot"


class ProgramStore:
    """On-disk map: program key -> serialized compiled executable."""

    def __init__(self, root: os.PathLike | str | None = None):
        self.root = pathlib.Path(root) if root is not None else default_root()

    # -- paths ---------------------------------------------------------
    def bin_path(self, key: str) -> pathlib.Path:
        return self.root / f"{key}.bin"

    def meta_path(self, key: str) -> pathlib.Path:
        return self.root / f"{key}.json"

    # -- read side -----------------------------------------------------
    def read_meta(self, key: str) -> dict | None:
        try:
            meta = json.loads(self.meta_path(key).read_text())
        except (OSError, ValueError):
            return None
        return meta if isinstance(meta, dict) else None

    def _loadable(self, key: str, avals: str) -> dict | None:
        """Sidecar gate: entry exists, store/code/jax/machine versions
        match this process, avals match the caller's example args."""
        import jax

        from shadow_tpu.utils.compcache import machine_fingerprint

        meta = self.read_meta(key)
        if meta is None or not self.bin_path(key).exists():
            return None
        if meta.get("store_version") != STORE_VERSION:
            return None
        if meta.get("code") != buckets.code_version():
            return None
        if meta.get("jax") != jax.__version__:
            return None
        if meta.get("machine") != machine_fingerprint():
            return None
        if meta.get("avals") != avals:
            return None
        return meta

    def load(self, key: str, avals: str):
        """Deserialize the stored executable for `key`, or None on any
        mismatch/corruption (the caller falls back to compiling)."""
        import jax
        from jax.experimental import serialize_executable

        meta = self._loadable(key, avals)
        if meta is None:
            return None
        # Left unset, the loader places the program on EVERY local
        # device: a one-device program then fails at call time on a
        # host with more devices. Load it on the devices it was
        # compiled for.
        n = meta.get("devices")
        if not isinstance(n, int) or not 0 < n <= len(jax.devices()):
            return None
        try:
            payload, in_tree, out_tree = pickle.loads(
                self.bin_path(key).read_bytes())
            compiled = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=jax.devices()[:n])
        except Exception:
            return None
        # LRU touch for gc(): served entries are the ones worth keeping.
        try:
            now = time.time()
            os.utime(self.bin_path(key), (now, now))
        except OSError:
            pass
        return compiled

    # -- write side ----------------------------------------------------
    def save(self, key: str, compiled, avals: str,
             meta: dict | None = None) -> bool:
        """Serialize and persist atomically. Returns False (and leaves
        no partial files) on any failure — persistence is best-effort,
        the in-memory compiled program is already usable."""
        import jax
        from jax.experimental import serialize_executable

        from shadow_tpu.utils.compcache import machine_fingerprint

        try:
            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled)
            blob = pickle.dumps((payload, in_tree, out_tree))
            self.root.mkdir(parents=True, exist_ok=True)
            tmp = self.bin_path(key).with_suffix(".bin.tmp")
            tmp.write_bytes(blob)
            os.replace(tmp, self.bin_path(key))
            sidecar = {
                "key": key,
                "store_version": STORE_VERSION,
                "avals": avals,
                "code": buckets.code_version(),
                "jax": jax.__version__,
                "machine": machine_fingerprint(),
                "devices": len(
                    compiled.runtime_executable().local_devices()),
                "nbytes": len(blob),
            }
            sidecar.update(meta or {})
            tmp = self.meta_path(key).with_suffix(".json.tmp")
            tmp.write_text(json.dumps(sidecar, sort_keys=True) + "\n")
            os.replace(tmp, self.meta_path(key))
            return True
        except Exception:
            for p in (self.bin_path(key).with_suffix(".bin.tmp"),
                      self.meta_path(key).with_suffix(".json.tmp")):
                try:
                    p.unlink()
                except OSError:
                    pass
            return False

    # -- the one entry point dispatch paths use ------------------------
    def get_or_compile(self, key: str, jitted, args, kwargs=None,
                       meta: dict | None = None):
        """Serve `key` warm if stored, else lower+compile `jitted` on
        the example `args` and persist. Returns (compiled, info) where
        info is the manifest `compile` block payload: {key, hit,
        load_s} on a hit, {key, hit, lower_s, compile_s} on a miss."""
        avals = _avals_digest(args, kwargs)
        t0 = time.perf_counter()
        compiled = self.load(key, avals)
        if compiled is not None:
            return compiled, {"key": key, "hit": True,
                              "load_s": time.perf_counter() - t0}
        t0 = time.perf_counter()
        lowered = jitted.lower(*args, **(kwargs or {}))
        t1 = time.perf_counter()
        compiled = _compile_outside_xla_cache(lowered)
        t2 = time.perf_counter()
        info = {"key": key, "hit": False,
                "lower_s": t1 - t0, "compile_s": t2 - t1}
        info["stored"] = self.save(key, compiled, avals, meta)
        if info["stored"] and self.load(key, avals) is None:
            # an entry that cannot be served back is worse than no
            # entry — every later run would miss through it forever
            self.drop(key)
            info["stored"] = False
        return compiled, info

    # -- maintenance (tools/compcache_ctl.py) --------------------------
    def ls(self) -> list[dict]:
        """Every entry, oldest-served first: [{key, nbytes, mtime,
        ...sidecar}]."""
        out = []
        try:
            bins = sorted(self.root.glob("*.bin"))
        except OSError:
            return out
        for b in bins:
            key = b.stem
            meta = self.read_meta(key) or {"key": key}
            try:
                st = b.stat()
                meta["nbytes"] = st.st_size
                meta["mtime"] = st.st_mtime
            except OSError:
                continue
            out.append(meta)
        out.sort(key=lambda m: m.get("mtime", 0.0))
        return out

    def stats(self) -> dict:
        entries = self.ls()
        return {
            "root": str(self.root),
            "entries": len(entries),
            "total_bytes": sum(int(m.get("nbytes", 0)) for m in entries),
            "code_versions": sorted({m.get("code") for m in entries
                                     if m.get("code")}),
            # capability-trimmed variants (compile/specialize.py) — a
            # specialized entry's sidecar carries the vector its
            # program was trimmed under
            "specialized": sum(
                1 for m in entries
                if (m.get("specialization") or {}).get("dropped")),
        }

    def drop(self, key: str) -> None:
        for p in (self.bin_path(key), self.meta_path(key)):
            try:
                p.unlink()
            except OSError:
                pass

    def gc(self, max_bytes: int) -> dict:
        """Evict least-recently-served entries until the store fits in
        `max_bytes`. Entries from other code versions go first — they
        can never be served again."""
        entries = self.ls()
        stale = [m for m in entries if m.get("code") != buckets.code_version()]
        fresh = [m for m in entries if m.get("code") == buckets.code_version()]
        dropped, total = [], sum(int(m.get("nbytes", 0)) for m in entries)
        for m in stale + fresh:
            if total <= max_bytes:
                break
            self.drop(m["key"])
            total -= int(m.get("nbytes", 0))
            dropped.append(m["key"])
        return {"dropped": dropped, "remaining_bytes": total}


_DEFAULT: ProgramStore | None = None


def default_store() -> ProgramStore:
    """Process-wide store rooted at default_root(). Re-rooted when
    SHADOW_AOT_DIR changes (tests point it at tmpdirs)."""
    global _DEFAULT
    root = default_root()
    if _DEFAULT is None or _DEFAULT.root != root:
        _DEFAULT = ProgramStore(root)
    return _DEFAULT
