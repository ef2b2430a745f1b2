"""Test env: force an 8-device virtual CPU mesh before jax initializes.

Multi-chip sharding tests run on xla_force_host_platform_device_count=8:
the suite never opens an accelerator. What runs on the chip is
chip_smoke.py (one chip; ``--chips 4`` for the mesh paths), and
tests/test_tpu_compile.py asks the chip's compiler without a chip.
"""

import os
import sys

# Hard-set, not setdefault: tests never open an accelerator, whatever
# the session env asks for — and the subprocesses they start (agents,
# payload scripts, CLIs) inherit the same pin.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from tony_tpu.utils import compilecache  # noqa: E402

# Persistent XLA compilation cache: the suite is compile-dominated (tiny
# models, many distinct program shapes), and identical programs recompile
# on every pytest invocation. Caching them across runs keeps the tier-1
# wall clock well inside its budget on a warm box and costs a cold run
# only the cache writes. Keys include jax/XLA versions and compile
# options, so a toolchain bump simply misses. Placed by the one rule of
# utils/compilecache.py: JAX_COMPILATION_CACHE_DIR when set, else
# <checkout>/.jax_compile_cache. Only compiles worth a file are kept.
compilecache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavyweight tests excluded from the tier-1 budget "
        "(ROADMAP.md runs -m 'not slow'); run explicitly with -m slow")
