"""Shape bucketing + compile telemetry: kill recompilation on the hot path.

Every data-dependent output size in the TPU backend (a join match count, an
expand frontier total, a filter survivor count) is baked STATIC into its
jitted materialize program (``jnp.nonzero(size=..)``,
``total_repeat_length=..``), so two queries whose intermediates differ only
in row count compile two distinct XLA programs. Under production traffic
the relational plan is stable while data sizes vary per request — making
per-query recompilation the dominant latency term (EmptyHeaded and TrieJax
both get their wins from compiled-once/run-many relational kernels).

This module is the shared policy for the fix:

* ``round_size(n)`` rounds a data-dependent size UP to a bucket lattice
  (``TPU_CYPHER_BUCKET=off|pow2|1.25``); materialize programs run at the
  bucketed size with the TRUE count carried as a traced operand and the pad
  lanes masked invalid — the same pad-masking discipline already proven for
  mesh-sharding pads (``Column.pad`` / ``compact_lookup`` validity gating).
  Two row counts in the same bucket now hit the same compiled program.
* process-wide compile telemetry fed by ``jax.monitoring`` (one
  ``backend_compile`` event per real compilation, persistent-cache
  hit/miss events per disk-tier lookup), served by the unified obs
  registry (``tpu_cypher_xla_compiles_total`` etc.) — surfaced as
  ``result.compile_stats`` and ``session.warmup(..)`` deltas.
* the persistent compilation cache wiring (``enable_persistent_cache``), so
  warm caches survive process restarts.

Bucketing is OFF by default: enable with ``TPU_CYPHER_BUCKET=pow2`` (or the
coarser-memory/finer-latency ``1.25`` lattice). Differential tests pin
bucketed results bit-identical to ``off``.
"""

from __future__ import annotations

import pathlib
import threading
from typing import Dict, Optional

import numpy as np

from ...obs import trace as _obs_trace
from ...obs.metrics import REGISTRY as _REGISTRY
from ...utils.config import BUCKET_MODE as MODE

# off  — no bucketing (every size compiles its own program; seed behavior)
# pow2 — next power of two at/above _BUCKET_FLOOR (<= 2x memory overhead)
# 1.25 — geometric lattice of ratio 1.25 (<= 25% overhead, more programs)
# (declared in utils/config.py; aliased so bucketing.MODE.set(..) keeps
# working on the registry-shared object)

# smallest nonzero bucket: tiny intermediates all share one program
_BUCKET_FLOOR = 32

# 2^62: sorts/compares above every real element id or probe key (graph tags
# live at bits 54+); the pad sentinel for id-sorted device arrays
ID_SENTINEL = np.int64(1) << 62


def mode() -> str:
    m = MODE.get().strip().lower()
    return m if m in ("off", "pow2", "1.25") else "off"


def enabled() -> bool:
    return mode() != "off"


class force_mode:
    """``with force_mode("off"):`` — temporarily pin the bucket mode,
    restoring whatever override (or lack of one) was in place before. The
    degraded ladder rungs use this to re-execute with exact sizes (no pad
    memory overhead) without disturbing the caller's configuration."""

    def __init__(self, m: str):
        self._m = m
        self._prev = None

    def __enter__(self) -> "force_mode":
        self._prev = MODE._override
        MODE.set(self._m)
        return self

    def __exit__(self, *exc) -> None:
        MODE._override = self._prev


def round_up_pow2(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor). THE shared rounding helper —
    also used by ``parallel.shuffle``'s bucket capacities so the shard_map
    program caches collapse onto one lattice."""
    n = max(int(n), int(floor))
    return 1 << (n - 1).bit_length() if n > 1 else 1


def round_up_multiple(n: int, m: int) -> int:
    """Smallest multiple of ``m`` >= ``n`` — the kernel-tile pad helper
    (Pallas grids need the streamed axis padded to a whole number of
    (8, 128) blocks; pad lanes must be mask-dead INSIDE the kernel, see
    docs/pad-invariants.md)."""
    n, m = int(n), int(m)
    return ((n + m - 1) // m) * m


# the fine lattice's floor: a table this small runs at its whole bucket
FINE_FLOOR = 4096


def round_fine(n: int) -> int:
    """``n`` rounded up to a multiple of 1/32 of its power of two, at least
    ``FINE_FLOOR``: the rows a pad-aware sort or scatter over a bucketed
    table keeps (``TpuTable._group_bucketed``). A count that moves by a
    little with the data — a graph's vertices from one draw to the next —
    keeps its program, at under 3.2% more rows where the bucket could
    double them."""
    n = int(n)
    step = 1 << max(n.bit_length() - 6, 0)
    return max(FINE_FLOOR, round_up_multiple(n, step))


# 1.25-lattice, grown lazily; starts at the floor
_LATTICE_125 = [_BUCKET_FLOOR]
_LATTICE_LOCK = threading.Lock()


def _round_125(n: int) -> int:
    with _LATTICE_LOCK:
        while _LATTICE_125[-1] < n:
            prev = _LATTICE_125[-1]
            _LATTICE_125.append(max(prev + 1, int(prev * 1.25)))
        import bisect

        return _LATTICE_125[bisect.bisect_left(_LATTICE_125, n)]


# padded-vs-true row telemetry: every bucketed materialize passes through
# ``round_size`` right after its count sync, making it THE chokepoint where
# the lattice's memory overhead is observable (docs/observability.md)
_ROWS_TRUE = _REGISTRY.counter(
    "tpu_cypher_bucket_rows_true_total",
    "true (pre-pad) rows across bucketed materializes",
)
_ROWS_PADDED = _REGISTRY.counter(
    "tpu_cypher_bucket_rows_padded_total",
    "padded (post-lattice) rows across bucketed materializes",
)


def _active_shards() -> int:
    """Shard count of the active engine mesh (1 when single-device).
    Imported lazily — parallel.shuffle imports this module for the shared
    lattice helpers, so a top-level import would cycle."""
    from ...parallel import mesh as _mesh

    return _mesh.mesh_size()


def _lattice(n: int, m: str) -> int:
    return _round_125(n) if m == "1.25" else round_up_pow2(n, _BUCKET_FLOOR)


def bucket_of(n: int) -> int:
    """What ``round_size`` gives ``n`` on one device, without its telemetry:
    for a static size that sizes no materialize of rows (a grouped
    aggregation's number of groups), which the operator's calibration
    (``optimizer/feedback``, fed by the span's padded rows) must not see."""
    n = int(n)
    m = mode()
    return n if n <= 0 or m == "off" else _lattice(n, m)


def round_size(n: int) -> int:
    """Bucketed size for a data-dependent count ``n`` (0 stays 0 — the
    empty case keeps its own trivially-cheap program). Identity when
    bucketing is off. Each call records the padded-vs-true pair on the
    enclosing trace span and the registry counters.

    While a mesh is active the lattice rounds PER SHARD: the local extent
    ``ceil(n / num_shards)`` rounds up the lattice and the global size is
    that local bucket times the shard count. Every per-shard shape a
    compiled program can see is therefore a plain lattice value regardless
    of the shard count — changing mesh sizes never mints new local shapes —
    and the global size stays shard-divisible so ``NamedSharding`` over the
    row axis is always legal. Spans record the per-shard (true, padded)
    pair alongside the global one."""
    n = int(n)
    if n <= 0:
        return 0
    m = mode()
    if m == "off":
        out = n
        _ROWS_TRUE.inc(n)
        _ROWS_PADDED.inc(out)
        _obs_trace.note_rows(n, out)
        return out
    nsh = _active_shards()
    if nsh > 1:
        local_true = -(-n // nsh)
        local_padded = _lattice(local_true, m)
        out = local_padded * nsh
        _ROWS_TRUE.inc(n)
        _ROWS_PADDED.inc(out)
        _obs_trace.note_rows(
            n, out, shards=nsh, local_true=local_true, local_padded=local_padded
        )
        return out
    out = _lattice(n, m)
    _ROWS_TRUE.inc(n)
    _ROWS_PADDED.inc(out)
    _obs_trace.note_rows(n, out)
    return out


def bucket_pad_host(arr: np.ndarray, fill):
    """Host-side tail pad of ``arr``'s leading dim up to ``round_size``.
    Returns ``(padded array, pad)``; identity when bucketing is off."""
    arr = np.asarray(arr)
    if not enabled() or arr.ndim == 0:
        return arr, 0
    n = arr.shape[0]
    pad = round_size(n) - n
    if pad <= 0:
        return arr, 0
    tail = np.full((pad,) + arr.shape[1:], fill, dtype=arr.dtype)
    return np.concatenate([arr, tail]), pad


# ---------------------------------------------------------------------------
# pre-flight memory admission
# ---------------------------------------------------------------------------

# HBM budget for any single materialize's PADDED footprint; 0 = unlimited.
# Set via env or CypherSession.tpu(memory_budget_bytes=..).
from ...utils.config import MEM_BUDGET  # noqa: E402


def memory_budget_bytes() -> int:
    try:
        return max(int(MEM_BUDGET.get()), 0)
    except (TypeError, ValueError):
        return 0


def estimate_materialize_bytes(rows: int, bytes_per_row: int) -> int:
    """Padded device footprint of materializing ``rows`` output rows:
    the row count rounds UP the active bucket lattice (padded lanes are
    allocated like live ones), each row costing ``bytes_per_row`` (data
    lanes + validity masks across the output columns)."""
    return round_size(int(rows)) * max(int(bytes_per_row), 1)


def admit(rows: int, bytes_per_row: int, site: str) -> None:
    """Pre-flight admission for one materialize: reject BEFORE launching a
    device program whose padded output would exceed the configured HBM
    budget. Raises ``AdmissionRejected`` (downgradable — the session ladder
    retries at the chunked or host-oracle rung). At the chunked rung the
    estimate is per-slice: that is the whole point of the rung."""
    budget = memory_budget_bytes()
    if not budget:
        return
    from ...runtime import guard as G

    chunk = G.chunk_rows()
    eff_rows = min(int(rows), chunk) if chunk is not None else int(rows)
    est = estimate_materialize_bytes(eff_rows, bytes_per_row)
    nsh = _active_shards() if enabled() else 1
    if nsh > 1:
        # row-sharded materialize: each device holds 1/nsh of the padded
        # rows (round_size made the global size shard-divisible), judged
        # against its 1/nsh slice of the whole-mesh budget
        est_judged = est // nsh
        budget_judged = budget // nsh
        scope = f" per shard (x{nsh})"
    else:
        est_judged, budget_judged, scope = est, budget, ""
    if est_judged > budget_judged:
        from ...errors import AdmissionRejected

        raise AdmissionRejected(
            f"materialize at site {site!r} needs ~{est_judged} bytes "
            f"padded{scope} ({rows} rows x {bytes_per_row} B/row on the "
            f"{mode()!r} lattice), over the {budget_judged}-byte HBM "
            f"budget{scope}",
            site=site,
            estimated_bytes=est,
            budget_bytes=budget,
        )


# ---------------------------------------------------------------------------
# compile telemetry: real XLA compilations + persistent-cache hit/miss,
# via jax.monitoring, served by the unified obs registry
# ---------------------------------------------------------------------------

_COMPILES_TOTAL = _REGISTRY.counter(
    "tpu_cypher_xla_compiles_total",
    "real XLA compilations (jit/persistent-cache hits emit none)",
)
_COMPILE_SECONDS_TOTAL = _REGISTRY.counter(
    "tpu_cypher_xla_compile_seconds_total",
    "seconds spent in real XLA compilations",
)
_PCACHE_HITS = _REGISTRY.counter(
    "tpu_cypher_persistent_cache_hits_total",
    "persistent compilation cache hits (a compile avoided by the disk tier)",
)
_PCACHE_MISSES = _REGISTRY.counter(
    "tpu_cypher_persistent_cache_misses_total",
    "persistent compilation cache misses (compile went to XLA)",
)

_PCACHE_LOAD_SECONDS = _REGISTRY.counter(
    "tpu_cypher_persistent_cache_load_seconds_total",
    "seconds spent loading programs from the persistent compilation cache "
    "(the compile-or-load step of a cache hit: not a compile)",
)

_JIT_TRACES = _REGISTRY.counter(
    "tpu_cypher_jit_traces_total",
    "programs traced to a jaxpr: a retrace that a hit in the persistent "
    "cache hides from the compile counters counts here (one per top-level "
    "program; what is traced inside it is part of it)",
)
_JIT_TRACE_SECONDS = _REGISTRY.counter(
    "tpu_cypher_jit_trace_seconds_total",
    "seconds spent tracing programs to jaxprs and lowering them to MLIR",
)
for _c in (_JIT_TRACES, _JIT_TRACE_SECONDS):  # both export from the start
    _c.inc(0)

_LISTENER_INSTALLED = False

# the installed JAX times ``backend_compile_duration`` around the whole
# compile-or-load step, so a program LOADED from the persistent cache fires
# it too — right after its ``cache_hits`` event, on the same thread. The
# flag sends that one duration event to the load counter: a load is not a
# compile, and the two compile counters never see it.
_LOADED = threading.local()


def _on_event_duration(name: str, secs: float, **kw) -> None:
    if name.endswith("jaxpr_trace_duration"):
        _JIT_TRACE_SECONDS.inc(float(secs))
        # JAX fires this for every function it traces, the jitted helpers
        # INSIDE a program included: those end while the outer trace is
        # still being built. The one that ends with no trace left open is
        # the program itself — one retrace of one program counts one.
        if not _obs_trace.inside_jax_trace():
            _JIT_TRACES.inc()
            # on the thread and in the context that traced: the innermost
            # open span is the operator that caused it
            sp = _obs_trace.current_span()
            if sp is not None:
                retraced = sp.attrs.setdefault("retraced", {})
                fun = str(kw.get("fun_name", "?"))
                retraced[fun] = retraced.get(fun, 0) + 1
        return
    if name.endswith("jaxpr_to_mlir_module_duration"):
        _JIT_TRACE_SECONDS.inc(float(secs))
        return
    if name.endswith("backend_compile_duration"):
        if getattr(_LOADED, "from_cache", False):
            _LOADED.from_cache = False
            _PCACHE_LOAD_SECONDS.inc(float(secs))
            return
        _COMPILES_TOTAL.inc()
        _COMPILE_SECONDS_TOTAL.inc(float(secs))


def _on_event(name: str, **_kw) -> None:
    # '/jax/compilation_cache/cache_hits|cache_misses' fire per lookup of
    # the persistent (disk) cache when one is enabled
    if name.endswith("compilation_cache/cache_hits"):
        _PCACHE_HITS.inc()
        _LOADED.from_cache = True
    elif name.endswith("compilation_cache/cache_misses"):
        _PCACHE_MISSES.inc()


def install_compile_listener() -> None:
    """Idempotently hook the process-wide compile + persistent-cache
    counters into ``jax.monitoring``. Cheap: one string check per
    monitoring event."""
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    jax.monitoring.register_event_listener(_on_event)
    _LISTENER_INSTALLED = True


def compile_count() -> int:
    return int(_COMPILES_TOTAL.value())


def compile_snapshot() -> Dict[str, float]:
    return {
        "compiles": int(_COMPILES_TOTAL.value()),
        "compile_seconds": round(_COMPILE_SECONDS_TOTAL.value(), 6),
        "persistent_cache_hits": int(_PCACHE_HITS.value()),
        "persistent_cache_misses": int(_PCACHE_MISSES.value()),
    }


def compile_delta(before: Dict[str, float]) -> Dict[str, float]:
    now = compile_snapshot()
    return {
        "compiles": now["compiles"] - before.get("compiles", 0),
        "compile_seconds": round(
            now["compile_seconds"] - before.get("compile_seconds", 0.0), 6
        ),
        "persistent_cache_hits": now["persistent_cache_hits"]
        - before.get("persistent_cache_hits", 0),
        "persistent_cache_misses": now["persistent_cache_misses"]
        - before.get("persistent_cache_misses", 0),
    }


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

# where compiled programs persist when the environment names no place: one
# fixed directory beside the package (the checkout's root). The path is
# part of the cache key, so it must never move — no tempfile, pid or clock.
DEFAULT_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"
)


def enable_persistent_cache() -> None:
    """Turn on JAX's persistent compilation cache so warm programs survive
    process restarts and are shared by every engine process of a
    deployment (the disk tier under the in-process jit caches; shape
    bucketing keeps the entry count bounded). The directory is placed from
    OUTSIDE: ``JAX_COMPILATION_CACHE_DIR`` (JAX reads it itself) wins, and
    only when nothing has named one does the cache land in
    ``DEFAULT_CACHE_DIR``. Idempotent."""
    import jax

    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # default thresholds skip small/fast programs — the engine's composites
    # are exactly those, and they are the ones worth persisting
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def persistent_cache_dir() -> Optional[str]:
    """The persistent-cache directory in force, or None while the cache is
    off (disabled, or no engine session has enabled it yet)."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    return jax.config.jax_compilation_cache_dir or None
