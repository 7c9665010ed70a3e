"""Device-mesh sharding for the graph kernels.

The reference delegates ALL distribution to Spark/Flink shuffle (SURVEY §2.3);
the TPU-native replacement is a ``jax.sharding.Mesh`` with XLA collectives
over ICI/DCN. Layout:

* edge arrays (``src_idx``, ``col_idx``) are sharded over the ``edges`` mesh
  axis — the analog of hash-partitioned relationship tables,
* node-indexed vectors (frontiers, degree arrays) are replicated — small
  relative to edges (the broadcast-join analog),
* per-shard partial aggregates are combined with ``psum`` over ICI
  (``shard_map``), exactly where the engines would shuffle-reduce.

Works identically on one chip, a v5e-8 slice, or a virtual
``--xla_force_host_platform_device_count`` CPU mesh (tests / dryrun)."""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import trace as _obs_trace
from ..obs.metrics import REGISTRY as _REGISTRY
from ..utils import config as _config

# Every hand-back from a sharded tier to the global (GSPMD-partitioned)
# path while a multi-device mesh is active: the answer stays exact, the
# layout is not the one the deployment asked for, so it is counted.
_MESH_DECLINES = _REGISTRY.counter(
    "tpu_cypher_mesh_declines_total",
    "operations that a sharded tier handed back to the global path while "
    "a multi-device mesh was active",
    labels=("op", "reason"),
)
# the declines a healthy mesh deployment reads 0 on, exported as zeros
for _op, _reason in (
    ("join", "overflow"), ("distinct", "overflow"), ("agg", "gate"),
    ("expand", "unpadded_edges"), ("expand", "chain_constraint"),
    ("expand", "tree_count"),
):
    _MESH_DECLINES.inc(0, op=_op, reason=_reason)

_MESH_EXCHANGE_BYTES = _REGISTRY.counter(
    "tpu_cypher_mesh_exchange_bytes_total",
    "bytes the sharded tiers' exchanges hand to the mesh, reckoned from "
    "their static capacities: all_to_all blocks that leave their chip, a "
    "replicated build side once per chip, the operands of a psum once per "
    "shard",
    labels=("op",),
)


def note_decline(op: str, reason: str) -> None:
    """Count one hand-back to the global path, on ``/metrics`` and on the
    innermost open span."""
    _MESH_DECLINES.inc(op=op, reason=reason)
    _obs_trace.note("mesh_decline", f"{op}:{reason}")


def note_exchange(op: str, nbytes: int) -> None:
    _MESH_EXCHANGE_BYTES.inc(int(nbytes), op=op)
    _obs_trace.note("exchange_bytes", int(nbytes))


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the mesh positional, as every program factory
    here and in ``agg.py``/``shuffle.py`` writes it."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


EDGE_AXIS = "edges"

# engine-level row axis: TpuTable columns and CSR edge arrays are sharded
# over this axis while a mesh is active (SURVEY §2.3 "tables sharded on
# id/hash dim across a TPU mesh")
ROW_AXIS = "rows"

_ACTIVE_MESH: Optional[Mesh] = None


def make_mesh(devices: Optional[Sequence] = None) -> Mesh:
    if devices is None:
        devices = jax.devices()
    return Mesh(np.array(devices), (EDGE_AXIS,))


def make_row_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """1-D engine mesh: every table row dimension shards over ROW_AXIS."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.array(devices), (ROW_AXIS,))


class use_mesh:
    """Context manager activating engine sharding: while active, newly
    created TpuTable columns and GraphIndex edge arrays are laid out as
    ``NamedSharding(mesh, P(ROW_AXIS))`` and every downstream op runs under
    XLA's GSPMD propagation — collectives (all_gather/all_to_all/psum) are
    inserted by the compiler where ops cross shards, the idiomatic
    replacement for the engines' shuffle exchanges (SURVEY §2.3)."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._prev: Optional[Mesh] = None

    def __enter__(self) -> Mesh:
        global _ACTIVE_MESH
        self._prev = _ACTIVE_MESH
        _ACTIVE_MESH = self.mesh
        return self.mesh

    def __exit__(self, *exc) -> None:
        global _ACTIVE_MESH
        _ACTIVE_MESH = self._prev


def resolve_mesh(spec) -> Optional[Mesh]:
    """One mesh-construction chokepoint for every activation surface
    (``CypherSession.tpu(mesh=...)``, the ``TPU_CYPHER_MESH`` env default).

    ``Mesh`` passes through; an integer N builds a row mesh over the first
    N visible devices; ``"auto"``/``"all"`` takes every device. Anything
    that resolves to a single device (or ``""``/``"off"``/``None``) means
    single-device execution and returns None."""
    if spec is None:
        return None
    if isinstance(spec, Mesh):
        return spec
    if isinstance(spec, int):
        n = spec
    else:
        s = str(spec).strip().lower()
        if s in ("", "off", "none", "0", "1"):
            return None
        if s in ("auto", "all"):
            n = len(jax.devices())
        else:
            try:
                n = int(s)
            except ValueError:
                return None
    devs = jax.devices()
    n = min(n, len(devs))
    if n <= 1:
        return None
    return make_row_mesh(devs[:n])


def activate_mesh(mesh: Optional[Mesh]) -> Optional[Mesh]:
    """Set the process-global engine mesh (None deactivates). The session
    factory uses this for persistent activation; scoped activation should
    prefer the ``use_mesh`` context manager."""
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh
    return mesh


# env-default mesh, resolved lazily and memoized per spec string so the
# hot-path current_mesh() stays a dict probe after first use
_ENV_MESH_CACHE: dict = {}


def _env_default_mesh() -> Optional[Mesh]:
    spec = _config.MESH_SPEC.get()
    if spec not in _ENV_MESH_CACHE:
        _ENV_MESH_CACHE[spec] = resolve_mesh(spec)
    return _ENV_MESH_CACHE[spec]


def current_mesh() -> Optional[Mesh]:
    if _ACTIVE_MESH is not None:
        return _ACTIVE_MESH
    return _env_default_mesh()


def shard_rows(arr):
    """Row-shard an ALREADY-SIZED device array over the active mesh when its
    leading dim is divisible by the mesh size (NamedSharding requires
    divisibility); other arrays stay as-is. Engine ingest uses
    ``padded_to_mesh`` instead, which pads arbitrary row counts to a shard
    multiple (a divisible-only skip would silently un-shard real
    workloads — 1,999,987 edges on an 8-mesh)."""
    mesh = current_mesh()
    if mesh is None:
        return arr
    shape = getattr(arr, "shape", None)
    if not shape or shape[0] == 0:
        return arr
    size = int(np.prod(list(mesh.shape.values())))
    if shape[0] % size != 0:
        return arr
    axis = mesh.axis_names[0]
    return jax.device_put(arr, NamedSharding(mesh, P(axis)))


def mesh_size() -> int:
    mesh = current_mesh()
    if mesh is None:
        return 1
    return int(np.prod(list(mesh.shape.values())))


def padded_to_mesh(host_arr, fill) -> Tuple[Any, int]:
    """Device-put a HOST array row-sharded over the active mesh, padding the
    tail with ``fill`` up to the next shard multiple (this JAX requires the
    leading dim divisible by the mesh size — uneven NamedShardings are
    rejected even via jit out_shardings). Returns ``(device array, pad)``.
    Pad rows are semantically inert: table columns mark them invalid
    (``Column.pad``/``pad_synth``), CSR edge arrays keep them outside every
    ``row_ptr`` range, and sorted edge-key arrays use an above-everything
    sentinel. With no active mesh (or an empty input) this is a plain
    ``jnp.asarray`` with pad 0."""
    arr = np.asarray(host_arr)
    mesh = current_mesh()
    if mesh is None or arr.ndim == 0 or arr.shape[0] == 0:
        return jnp.asarray(arr), 0
    size = int(np.prod(list(mesh.shape.values())))
    pad = (-arr.shape[0]) % size
    if pad:
        tail = np.full((pad,) + arr.shape[1:], fill, dtype=arr.dtype)
        arr = np.concatenate([arr, tail])
    axis = mesh.axis_names[0]
    return jax.device_put(arr, NamedSharding(mesh, P(axis))), pad


def pad_edges(src_idx: np.ndarray, col_idx: np.ndarray, num_shards: int):
    """Pad edge arrays to a multiple of the shard count with self-loop-free
    sentinel edges pointing at a dead slot (num_nodes), so shards are equal."""
    e = len(src_idx)
    padded = ((e + num_shards - 1) // num_shards) * num_shards
    pad = padded - e
    if pad:
        src_idx = np.concatenate([src_idx, np.full(pad, -1, src_idx.dtype)])
        col_idx = np.concatenate([col_idx, np.full(pad, -1, col_idx.dtype)])
    return src_idx, col_idx, pad


def shard_edge_arrays(mesh: Mesh, *arrays):
    sharding = NamedSharding(mesh, P(EDGE_AXIS))
    return tuple(jax.device_put(a, sharding) for a in arrays)


# jitted shard_map programs, memoized per mesh (+static sizes): these
# factories used to build a FRESH jitted callable per invocation, which
# recompiled the collective program on every call — the exact hazard the
# recompile-hazard lint rule now catches
_TWO_HOP_CACHE: dict = {}
_WALK_STEP_CACHE: dict = {}
_TRAIN_STEP_CACHE: dict = {}


def sharded_two_hop_count(mesh: Mesh, deg: jnp.ndarray, col_idx: jnp.ndarray):
    """sum over edges of outdeg(dst), edges sharded, psum over ICI."""
    f = _TWO_HOP_CACHE.get(mesh)
    if f is None:

        def kernel(deg_rep, col_shard):
            valid = col_shard >= 0
            local = jnp.sum(jnp.where(valid, deg_rep[jnp.clip(col_shard, 0)], 0).astype(jnp.int64))
            return lax.psum(local, EDGE_AXIS)

        f = _obs_trace.program(jax.jit(
            shard_map(kernel, mesh, in_specs=(P(), P(EDGE_AXIS)), out_specs=P())
        ))
        _TWO_HOP_CACHE[mesh] = f
    return f(deg, col_idx)


def sharded_walk_step(mesh: Mesh, num_nodes: int):
    """One frontier SpMM step: p'[v] = sum over sharded edges (u,v) of p[u].

    The per-shard ``segment_sum`` produces partial next-frontiers combined
    with ``psum`` — the ICI replacement for the engines' shuffle exchange."""
    key = (mesh, num_nodes)
    f = _WALK_STEP_CACHE.get(key)
    if f is not None:
        return f

    def kernel(p, src_shard, col_shard):
        valid = src_shard >= 0
        contrib = jnp.where(valid, p[jnp.clip(src_shard, 0)], 0)
        partial_next = jax.ops.segment_sum(
            contrib, jnp.clip(col_shard, 0), num_segments=num_nodes
        )
        return lax.psum(partial_next, EDGE_AXIS)

    f = _obs_trace.program(jax.jit(
        shard_map(
            kernel, mesh, in_specs=(P(), P(EDGE_AXIS), P(EDGE_AXIS)), out_specs=P()
        )
    ))
    _WALK_STEP_CACHE[key] = f
    return f


def sharded_training_step(mesh: Mesh, num_nodes: int, hops: int):
    """The full multi-hop 'step': iterated sharded SpMM over the mesh +
    a final psum'd 2-hop count — the complete distributed query step used by
    the driver's multi-chip dryrun."""
    key = (mesh, num_nodes, hops)
    cached = _TRAIN_STEP_CACHE.get(key)
    if cached is not None:
        return cached

    def kernel(p0, deg, src_shard, col_shard):
        valid = src_shard >= 0

        def one_hop(p, _):
            contrib = jnp.where(valid, p[jnp.clip(src_shard, 0)], 0)
            nxt = jax.ops.segment_sum(
                contrib, jnp.clip(col_shard, 0), num_segments=num_nodes
            )
            nxt = lax.psum(nxt, EDGE_AXIS)
            return nxt, jnp.sum(nxt)

        p_final, hop_counts = lax.scan(one_hop, p0.astype(jnp.int64), None, length=hops)
        two_hop_local = jnp.sum(
            jnp.where(valid, deg[jnp.clip(col_shard, 0)], 0).astype(jnp.int64)
        )
        two_hop = lax.psum(two_hop_local, EDGE_AXIS)
        return p_final, hop_counts, two_hop

    f = _obs_trace.program(jax.jit(
        shard_map(
            kernel,
            mesh,
            in_specs=(P(), P(), P(EDGE_AXIS), P(EDGE_AXIS)),
            out_specs=(P(), P(), P()),
        )
    ))
    _TRAIN_STEP_CACHE[key] = f
    return f


_RANGE_COUNT_CACHE: dict = {}


def sharded_range_count(mesh: Mesh):
    """Per-query equal-key counts over ROW_AXIS-sharded sorted ``edge_keys``
    — the mesh tier of the WCOJ leapfrog intersect.

    A NamedSharding over the leading dim partitions a sorted array into
    contiguous slices, and searchsorted range counts are ADDITIVE over
    contiguous partitions: each shard counts matches in its local adjacency
    slice with two binary searches and the counts ``psum``-combine, exactly
    where a relational engine would shuffle-reduce. Queries and their
    validity mask stay replicated (they are small relative to edges — the
    broadcast-join analog); sentinel pad keys (above every real key) can
    never match a query so pads contribute zero."""
    f = _RANGE_COUNT_CACHE.get(mesh)
    if f is None:

        def kernel(keys_shard, q, qok):
            lo = jnp.searchsorted(keys_shard, q, side="left")
            hi = jnp.searchsorted(keys_shard, q, side="right")
            local = jnp.where(qok, (hi - lo).astype(jnp.int64), 0)
            return lax.psum(local, ROW_AXIS)

        f = _obs_trace.program(jax.jit(
            shard_map(
                kernel, mesh, in_specs=(P(ROW_AXIS), P(), P()), out_specs=P()
            )
        ))
        _RANGE_COUNT_CACHE[mesh] = f
    return f
