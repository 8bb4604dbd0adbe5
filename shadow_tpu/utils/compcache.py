"""Shared persistent-compile-cache configuration.

Every entry point (bench.py, tools/scale_run.py, the CLI, the fleet
worker, the test suite) points JAX's persistent compilation cache at
the SAME directory, so one entry point's compile is every other entry
point's cache hit. The directory is part of the cache's key, so it
must not move between runs:

- `JAX_COMPILATION_CACHE_DIR`, when set, is used exactly as given.
  Whoever set it owns that directory; nothing here claims, redirects
  or overrides it.
- Otherwise the fixed repo-local `<checkout>/.jax_cache`.

XLA:CPU cache entries embed the compile machine's CPU features (the
AOT loader refuses — or worse, mis-executes wide-vector code paths —
when the executing host lacks features the compiling host had). The
repo-local directory is therefore CLAIMED by the first host that
writes it: `cache_dir` records the host's CPU-feature fingerprint in
a sidecar (machine.json) and, when a later host's fingerprint
disagrees, logs a warning and redirects that host to a
per-fingerprint subdirectory — a fresh compile namespace instead of
loading foreign AOT entries. SHADOW_NO_COMPILE_CACHE=1 opts out
entirely.
"""

from __future__ import annotations

import hashlib
import json
import os
import pathlib
import platform
import sys


def machine_fingerprint() -> str:
    """Stable digest of the CPU features that XLA:CPU AOT entries
    depend on: ISA + the feature flags /proc/cpuinfo advertises. Two
    hosts with equal fingerprints can safely exchange cache entries;
    unequal fingerprints may not (a narrower host would load code
    compiled for vector extensions it lacks)."""
    feats = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    feats = " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        feats = platform.processor()
    blob = f"{platform.machine()}|{feats}"
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _claim_or_redirect(cache: pathlib.Path, fp: str,
                       log=None) -> pathlib.Path:
    """First fingerprint to write machine.json owns `cache`; a
    mismatched host is redirected to cache/hosts/<fp> with a logged
    warning (fresh compiles there, never foreign AOT loads)."""
    say = log or (lambda m: print(m, file=sys.stderr))
    sidecar = cache / "machine.json"
    try:
        recorded = json.loads(sidecar.read_text()).get("fingerprint")
    except (OSError, ValueError):
        recorded = None
    if recorded is None:
        try:
            cache.mkdir(parents=True, exist_ok=True)
            tmp = sidecar.with_suffix(".json.tmp")
            tmp.write_text(json.dumps(
                {"fingerprint": fp, "machine": platform.machine()},
                sort_keys=True) + "\n")
            os.replace(tmp, sidecar)
        except OSError:
            pass  # read-only checkout: cache still usable, unclaimed
        return cache
    if recorded == fp:
        return cache
    redirect = cache / "hosts" / fp
    say(f"WARNING: compile cache at {cache} holds XLA:CPU AOT entries "
        f"compiled on a host with different CPU features (recorded "
        f"{recorded}, this host {fp}); falling back to fresh compiles "
        f"under {redirect}")
    return redirect


def cache_dir(log=None) -> pathlib.Path:
    """The persistent-cache directory every entry point shares (the
    AOT program store lives under it too, compile/store.py)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return pathlib.Path(env)
    cache = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"
    return _claim_or_redirect(cache, machine_fingerprint(), log)


def enable_compile_cache(log=None) -> None:
    import jax

    if os.environ.get("SHADOW_NO_COMPILE_CACHE"):
        return
    jax.config.update("jax_compilation_cache_dir", str(cache_dir(log)))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
