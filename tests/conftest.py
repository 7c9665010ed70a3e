"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Mirrors SURVEY.md §4's "cluster testing without a cluster": sharded plans are
validated on host CPU devices so no TPU pod is needed (the reference's analog
is Spark local[*] / Flink local ExecutionEnvironment). The chip is met by
``chip_smoke.py``, through the chip tool."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# the suite counts cold compiles and spawns worker processes: the
# persistent compile cache (on by default for ``CypherSession.tpu()``)
# stays OFF here, for this process and every child it spawns, unless a
# test opts in by setting this variable back
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax
import pytest


def _memory_map_count() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:  # non-Linux: no 65530 vm.max_map_count default either
        return 0


# Every compiled XLA executable pins a handful of memory mappings (JIT code
# + guard pages); a full-suite run compiles tens of thousands of programs
# and walks the process into the kernel's vm.max_map_count ceiling (65530
# by default), at which point the NEXT LLVM compile mmap fails and the
# whole pytest process dies with SIGSEGV/SIGABRT mid-suite. Dropping the
# jit caches releases the executables (verified: maps fall back to
# baseline), so flush them between modules once the table gets high — a
# cross-module jit cache hit is rare enough that the recompiles cost far
# less than losing the rest of the suite. Threshold: the largest single
# module accumulates ~35k maps from a clean slate, so flushing above 25k
# keeps even (threshold-1) + worst-module under the 65530 ceiling.
_MAPS_FLUSH_THRESHOLD = 25_000


@pytest.fixture(autouse=True, scope="module")
def _bound_jit_code_maps():
    yield
    if _memory_map_count() > _MAPS_FLUSH_THRESHOLD:
        import gc

        gc.collect()  # drop dead tracers/arrays holding executables first
        jax.clear_caches()
