"""Test harness: run on CPU with 8 virtual devices so multi-chip
sharding paths are exercised without TPU hardware.

A pytest plugin may import jax before this file runs, so env vars are
unreliable — but the backend is initialized lazily, so configuring via
jax.config here (before any device use) takes effect. The chip is
covered by chip_smoke.py, and the TPU kernels' compiles by
tests/test_chip_compile.py.
"""

import importlib.util
import pathlib

import jax

TOOLS = pathlib.Path(__file__).resolve().parent.parent / "tools"


def load_tool(name):
    """Import a script from tools/ by file path (they are not a
    package; the reference's tools are standalone scripts too)."""
    spec = importlib.util.spec_from_file_location(name, TOOLS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# The full device program is large (the whole netstack + TCP state
# machine inlined into one while-loop body); persist compiled binaries
# so the multi-minute XLA compile is paid once per (shape, code)
# rather than once per pytest invocation.
from shadow_tpu.utils.compcache import enable_compile_cache  # noqa: E402

enable_compile_cache()

assert jax.default_backend() == "cpu", jax.default_backend()
assert len(jax.devices()) == 8, jax.devices()
