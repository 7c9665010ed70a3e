"""Explicit hash-repartition (shuffle) equi-join for the mesh path.

The reference engines join by hash-SHUFFLING both sides so equal keys meet
on one worker (``SparkTable.scala:178`` joins ride Spark's exchange;
``flink-cypher TableOps.scala:146`` likewise) — the partitioning of the
intermediate is a deliberate plan decision, not an accident of input
layout. The engine's default device join is one global sort + binary-search
probe, which XLA/GSPMD partitions by propagating the INPUT shardings; at
pod scale a global ``lax.sort`` degenerates to an all-gather. This module
is the deliberate alternative (SURVEY §2.3 "distributed join / shuffle"):

* each device buckets its local key block by ``key % n_shards`` — a row's
  bucket depends only on its VALUE, so equal keys land on equal shards;
* ONE ``lax.all_to_all`` per side exchanges the buckets over the mesh axis
  (ICI within a host, DCN across hosts — exactly where the engines
  shuffle);
* each shard then joins its received blocks LOCALLY (sort + searchsorted
  over per-shard data — no global collective in the join itself);
* match pairs return as GLOBAL row indices carried through the exchange.

Static-shape discipline (everything under ``shard_map`` is compiled once):
buckets get a fixed capacity ``cap_factor * fair_share``; a skewed key
distribution that overflows a bucket is detected ON DEVICE and reported
back — the caller falls back to the global sort-probe join, trading layout
quality for unconditional correctness. Join output uses the engine's
count-then-materialize discipline: phase A syncs per-shard match counts,
phase B materializes padded to the max count.

Runs bit-identically on the CPU test mesh (8 virtual devices) and a TPU
pod — only the device list changes."""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..backend.tpu.bucketing import round_size, round_up_pow2
from ..obs import trace as _obs_trace
from ..obs.metrics import REGISTRY as _REGISTRY
from .mesh import (
    current_mesh,
    mesh_size,
    note_decline,
    note_exchange,
    shard_map,
)

_MESH_DISTINCT_TOTAL = _REGISTRY.counter(
    "tpu_cypher_mesh_distinct_total",
    "DISTINCT counts executed on the sharded hash-repartition tier",
)
_MESH_JOIN_TOTAL = _REGISTRY.counter(
    "tpu_cypher_mesh_join_total",
    "equi-joins executed on a sharded tier: broadcast (replicated build "
    "side, local probe) or shuffle (hash repartition of both sides)",
    labels=("tier",),
)

# Key namespace: real keys ship DOUBLED (even numbers — injective, equality
# and bucket assignment preserved); pad slots use per-side odd sentinels that
# can never equal a real key or each other. Invalid rows are dropped at host
# staging, so NO data value needs a reserved encoding — negative keys
# included. Staging rejects |key| >= 2^62 (doubling would overflow).
_L_PAD = 1
_R_PAD = 3
_KEY_LIMIT = 1 << 62


def _mix64(k):
    """splitmix64 finalizer over wrapping uint64 arithmetic: equal keys mix
    equal, and ANY structured key pattern (strided id namespaces, even-only
    ids, graph-tag high bits) spreads uniformly over the shards — a plain
    ``key % nsh`` concentrates every stride that shares a factor with the
    mesh size."""
    k = k.astype(jnp.uint64)
    k = (k ^ (k >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    k = (k ^ (k >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return k ^ (k >> jnp.uint64(31))


@jax.jit
def combine_keys(keys):
    """Fold several int64 key columns into ONE mixed 61-bit join key (the
    composite-key shuffle/broadcast path; the reference serializes
    multi-column keys via codegen ``Serialize.scala``). Collisions are
    possible — callers MUST post-verify every key column on the matched
    pairs."""
    acc = jnp.zeros(keys[0].shape, jnp.uint64)
    for k in keys:
        acc = acc * jnp.uint64(0x9E3779B97F4A7C15) ^ k.astype(jnp.uint64)
        acc = (acc ^ (acc >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
        acc = acc ^ (acc >> jnp.uint64(31))
    return (acc & jnp.uint64((1 << 61) - 1)).astype(jnp.int64)


def _bucketize(keys, rows, nsh: int, cap: int, pad_key: int, axis: str):
    """Route (key, global row) pairs to shard ``mix(key) % nsh`` with ONE
    tiled all_to_all. Keys arrive doubled (even); ``pad_key`` is this
    side's odd pad sentinel (staged pad rows carry it too). Returns
    (received keys, received rows, overflow flag); slots past a bucket's
    fill carry the pad key. Pads and overflowing rows scatter into a
    per-bucket SPILL slot that is sliced off before the exchange, so they
    can never overwrite a real row."""
    n = keys.shape[0]
    # bucket on the PRE-doubled value (arithmetic shift recovers the
    # original, negatives included), mixed so strided key sets spread
    is_pad = keys == pad_key
    tgt = jnp.where(
        is_pad,
        (jnp.arange(n) % nsh).astype(jnp.uint64),
        _mix64(keys >> 1) % jnp.uint64(nsh),
    ).astype(jnp.int32)
    order = jnp.argsort(tgt, stable=True)
    tgt_s = jnp.take(tgt, order)
    is_real = ~jnp.take(is_pad, order)
    # rank REAL rows only: pads sorted ahead within a bucket
    # must not inflate real ranks, or near-capacity buckets trip the
    # overflow fallback spuriously
    creal = jnp.cumsum(is_real.astype(jnp.int64))
    start = jnp.searchsorted(tgt_s, tgt_s, side="left")
    before = jnp.where(start > 0, jnp.take(creal, jnp.maximum(start - 1, 0)), 0)
    rank = creal - 1 - before
    overflow = jnp.any((rank >= cap) & is_real)
    keys_s = jnp.take(keys, order)
    rows_s = jnp.take(rows, order)
    # pads and past-capacity rows land in the spill slot (index cap)
    rank_c = jnp.where(is_real, jnp.minimum(rank, cap), cap)
    buf_k = jnp.full((nsh, cap + 1), pad_key, jnp.int64)
    buf_r = jnp.zeros((nsh, cap + 1), jnp.int64)
    buf_k = buf_k.at[tgt_s, rank_c].set(
        jnp.where(rank_c < cap, keys_s, pad_key)
    )
    buf_r = buf_r.at[tgt_s, rank_c].set(rows_s)
    buf_k = lax.all_to_all(buf_k[:, :cap], axis, 0, 0, tiled=True)
    buf_r = lax.all_to_all(buf_r[:, :cap], axis, 0, 0, tiled=True)
    return buf_k.reshape(-1), buf_r.reshape(-1), overflow


def _local_probe(lk, rk):
    """Sort the received right block, binary-search the received left block.
    Returns (r_sorted_rows-selector pieces) shared by count & materialize.
    Pad keys are odd and per-side distinct, so they never match anything."""
    r_order = jnp.argsort(rk, stable=True)
    rk_s = jnp.take(rk, r_order)
    lo = jnp.searchsorted(rk_s, lk, side="left")
    hi = jnp.searchsorted(rk_s, lk, side="right")
    counts = jnp.where(lk != _L_PAD, hi - lo, 0).astype(jnp.int64)
    return r_order, lo, counts


_COUNT_CACHE: Dict[Any, Any] = {}
_MAT_CACHE: Dict[Any, Any] = {}


def _count_fn(mesh, axis, nsh, cap_l, cap_r):
    key = (mesh, axis, cap_l, cap_r)
    got = _COUNT_CACHE.get(key)
    if got is not None:
        return got

    def local(lk, lrow, rk, rrow):
        lk2, _, ovf_l = _bucketize(lk, lrow, nsh, cap_l, _L_PAD, axis)
        rk2, _, ovf_r = _bucketize(rk, rrow, nsh, cap_r, _R_PAD, axis)
        _, _, counts = _local_probe(lk2, rk2)
        return jnp.sum(counts)[None], (ovf_l | ovf_r)[None]

    spec = P(axis)
    fn = _obs_trace.program(jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(spec, spec, spec, spec),
            out_specs=(spec, spec),
        )
    ))
    _COUNT_CACHE[key] = fn
    return fn


def _materialize_fn(mesh, axis, nsh, cap_l, cap_r, out_cap):
    key = (mesh, axis, cap_l, cap_r, out_cap)
    got = _MAT_CACHE.get(key)
    if got is not None:
        return got

    def local(lk, lrow, rk, rrow):
        lk2, lrow2, _ = _bucketize(lk, lrow, nsh, cap_l, _L_PAD, axis)
        rk2, rrow2, _ = _bucketize(rk, rrow, nsh, cap_r, _R_PAD, axis)
        r_order, lo, counts = _local_probe(lk2, rk2)
        rrow_sorted = jnp.take(rrow2, r_order)
        off = jnp.cumsum(counts)
        total = off[-1] if counts.shape[0] else jnp.asarray(0, jnp.int64)
        slot = jnp.arange(out_cap, dtype=jnp.int64)
        src = jnp.searchsorted(off, slot, side="right")
        src_c = jnp.minimum(src, counts.shape[0] - 1)
        within = slot - jnp.take(off - counts, src_c)
        valid = slot < total
        l_out = jnp.where(valid, jnp.take(lrow2, src_c), 0)
        r_idx = jnp.take(lo, src_c) + within
        r_out = jnp.where(
            valid, jnp.take(rrow_sorted, jnp.minimum(r_idx, rrow_sorted.shape[0] - 1)), 0
        )
        return l_out, r_out, valid

    spec = P(axis)
    fn = _obs_trace.program(jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(spec, spec, spec, spec),
            out_specs=(spec, spec, spec),
        )
    ))
    _MAT_CACHE[key] = fn
    return fn


def _pad_sharded(arr_np: np.ndarray, nsh: int, fill, mesh, axis):
    pad = (-len(arr_np)) % nsh
    if pad:
        arr_np = np.concatenate(
            [arr_np, np.full(pad, fill, dtype=arr_np.dtype)]
        )
    return jax.device_put(arr_np, NamedSharding(mesh, P(axis)))


def _no_pairs():
    z = jnp.zeros(0, jnp.int64)
    return z, z, 0


class CountedJoin(NamedTuple):
    """A sharded join after its count phase: ``total`` is the exact number
    of matching pairs (on the host already), ``pairs()`` goes on to the
    materialize and returns ``(left_rows, right_rows, total)``. A
    ``count(*)`` over the join stops at ``total``."""

    total: int
    pairs: Callable[[], Tuple[Any, Any, int]]


_NO_PAIRS = CountedJoin(0, _no_pairs)


def _compact_pairs(l_out, r_out, valid, total: int):
    """The ``total`` live pairs of a materialize's per-shard blocks, moved
    to the front of arrays of ``round_size(total)`` lanes: the same
    tail-padded form (and the same lattice) as the one-device join's
    ``join_materialize_counted``, so data of another size or seed reuses
    the compiled compact and every gather after it. With bucketing off the
    size is the total itself."""
    from ..backend.tpu.jit_ops import mask_nonzero, tree_take

    idx = mask_nonzero(valid, size=round_size(total))
    l_rows, r_rows = tree_take((l_out, r_out), idx)
    return l_rows, r_rows, total


def _addressable(*arrays) -> bool:
    """Multi-process meshes hold row-sharded GLOBAL arrays whose remote
    shards this process cannot read: host staging would raise."""
    return all(
        a is None or getattr(a, "is_fully_addressable", True) for a in arrays
    )


def _to_host(site: str, arr, dtype=None) -> np.ndarray:
    """One blocking device->host copy of a staged column, seen by
    ``obs.trace.sync`` under ``site``."""
    with _obs_trace.sync(site):
        # tpulint: allow[host-sync] reason=the one staging read of the sharded tiers; every caller passes its own fault_point (shuffle / agg) before it stages a column
        return np.asarray(arr, dtype=dtype)


def _stage_join_sides(l_key, l_valid, r_key, r_valid):
    """Host staging of both join sides: keys and their global row numbers
    with the invalid rows dropped (null keys never match). Returns
    ``(lk, lrow, rk, rrow)`` as NumPy arrays, or None where a key is too
    large to double into the even namespace."""
    with _obs_trace.span("mesh_join:stage", kind="mesh"):
        sides = []
        for key, valid in ((l_key, l_valid), (r_key, r_valid)):
            k_np = _to_host("shuffle", key, np.int64)
            row_np = np.arange(len(k_np), dtype=np.int64)
            if valid is not None:
                keep = _to_host("shuffle", valid)
                k_np, row_np = k_np[keep], row_np[keep]
            if np.abs(k_np).max(initial=0) >= _KEY_LIMIT:
                return None  # doubling would overflow int64
            sides += [k_np, row_np]
        return tuple(sides)


_BCAST_COUNT_CACHE: Dict[Any, Any] = {}
_BCAST_MAT_CACHE: Dict[Any, Any] = {}


def _broadcast_limit() -> int:
    from ..utils.config import BROADCAST_LIMIT

    return int(BROADCAST_LIMIT.get())


def _bcast_count_fn(mesh, axis):
    key = (mesh, axis)
    got = _BCAST_COUNT_CACHE.get(key)
    if got is not None:
        return got

    def local(lk, rk):
        _, _, counts = _local_probe(lk, rk)
        return jnp.sum(counts)[None]

    fn = _obs_trace.program(jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis), P(None)),
            out_specs=P(axis),
        )
    ))
    _BCAST_COUNT_CACHE[key] = fn
    return fn


def _bcast_materialize_fn(mesh, axis, out_cap):
    key = (mesh, axis, out_cap)
    got = _BCAST_MAT_CACHE.get(key)
    if got is not None:
        return got

    def local(lk, lrow, rk, rrow):
        r_order, lo, counts = _local_probe(lk, rk)
        rrow_sorted = jnp.take(rrow, r_order)
        off = jnp.cumsum(counts)
        total = off[-1] if counts.shape[0] else jnp.asarray(0, jnp.int64)
        slot = jnp.arange(out_cap, dtype=jnp.int64)
        src = jnp.searchsorted(off, slot, side="right")
        src_c = jnp.minimum(src, counts.shape[0] - 1)
        within = slot - jnp.take(off - counts, src_c)
        valid = slot < total
        l_out = jnp.where(valid, jnp.take(lrow, src_c), 0)
        r_idx = jnp.take(lo, src_c) + within
        r_out = jnp.where(
            valid,
            jnp.take(rrow_sorted, jnp.minimum(r_idx, rrow_sorted.shape[0] - 1)),
            0,
        )
        return l_out, r_out, valid

    fn = _obs_trace.program(jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(None), P(None)),
            out_specs=(P(axis), P(axis), P(axis)),
        )
    ))
    _BCAST_MAT_CACHE[key] = fn
    return fn


def broadcast_join_count(
    l_key, l_valid, r_key, r_valid
) -> Optional[CountedJoin]:
    """Count phase of the broadcast (replicated-build) equi-join over the
    active mesh: when the build (right) side is small, shuffling it
    through ``all_to_all`` is the wrong plan — replicate it to every device
    and probe the row-sharded left side LOCALLY, with NO collective in the
    join at all (the engines' broadcast join, delegated to Catalyst in the
    reference; SURVEY §2.3 "broadcast small relations"). Returns the
    ``CountedJoin`` — the pairs' number, and the materialize still to run
    for a caller that wants the pairs — or None when no mesh is active or
    the build side exceeds the cost model's broadcast window
    (``optimizer.cost.broadcast_build_limit`` — at least
    ``TPU_CYPHER_BROADCAST_LIMIT`` rows, default 4096, extended past it
    when replication still beats repartitioning both sides; a pinned env
    knob is honoured verbatim)."""
    mesh = current_mesh()
    nsh = mesh_size()
    if mesh is None or nsh <= 1:
        return None
    n_l, n_r = int(l_key.shape[0]), int(r_key.shape[0])
    try:
        from ..optimizer.cost import broadcast_build_limit

        limit = broadcast_build_limit(n_l, nsh)
    except Exception as exc:
        from ..errors import reraise_if_device

        reraise_if_device(exc, site="shuffle.broadcast")
        limit = _broadcast_limit()
    if n_l == 0 or n_r == 0 or n_r > limit:
        return None
    from ..runtime.faults import fault_point

    fault_point("shuffle")
    if not _addressable(l_key, l_valid, r_key, r_valid):
        return None  # hash_repartition_join_count declines next, and counts it
    axis = mesh.axis_names[0]

    staged = _stage_join_sides(l_key, l_valid, r_key, r_valid)
    if staged is None:
        return None  # as above
    lk_np, lrow_np, rk_np, rrow_np = staged
    if len(lk_np) == 0 or len(rk_np) == 0:
        return _NO_PAIRS
    with _obs_trace.span("mesh_join:count", kind="mesh", tier="broadcast"):
        lk = _pad_sharded(lk_np * 2, nsh, _L_PAD, mesh, axis)
        lrow = _pad_sharded(lrow_np, nsh, 0, mesh, axis)
        repl = NamedSharding(mesh, P(None))
        rk = jax.device_put(rk_np * 2, repl)
        rrow = jax.device_put(rrow_np, repl)
        # the build side's keys and row numbers, once to every chip
        note_exchange("broadcast_join", nsh * len(rk_np) * 16)
        counts = _bcast_count_fn(mesh, axis)(lk, rk)
        counts_np = _to_host("shuffle", counts)
    _MESH_JOIN_TOTAL.inc(tier="broadcast")
    _obs_trace.note("join_shards", nsh)
    out_cap = int(counts_np.max()) if counts_np.size else 0
    if out_cap == 0:
        return _NO_PAIRS
    # shared pow2 lattice (see hash_repartition_join_count): one compiled
    # broadcast-materialize per bucket instead of one per match count
    out_cap = round_up_pow2(out_cap, 16)
    total = int(counts_np.sum())

    def pairs():
        with _obs_trace.span("mesh_join:materialize", kind="mesh",
                             tier="broadcast"):
            l_out, r_out, valid = _bcast_materialize_fn(mesh, axis, out_cap)(
                lk, lrow, rk, rrow
            )
            return _compact_pairs(l_out, r_out, valid, total)

    return CountedJoin(total, pairs)


def broadcast_join(
    l_key, l_valid, r_key, r_valid
) -> Optional[Tuple[Any, Any, int]]:
    """``broadcast_join_count`` and then its materialize: the matching
    global row-index pairs and their number, as ``hash_repartition_join``
    returns them, or None where the count phase declines."""
    counted = broadcast_join_count(l_key, l_valid, r_key, r_valid)
    return None if counted is None else counted.pairs()


def hash_repartition_join_count(
    l_key, l_valid, r_key, r_valid, cap_factor: float = 2.0
) -> Optional[CountedJoin]:
    """Count phase of the inner equi-join over the active mesh via explicit
    hash shuffle: ONE exchange of the keys, a local probe per shard, the
    per-shard pair counts read to the host. ``l_key``/``r_key``: int64
    device arrays (element ids); valid masks may be None. Returns the
    ``CountedJoin`` — the exact number of pairs, and the materialize (a
    second exchange of keys and row numbers, then ``_compact_pairs``) still
    to run for a caller that wants the pairs — or None when no multi-device
    mesh is active or a hash bucket overflows its static capacity: the
    caller keeps the global sort-probe join, and the decline is counted."""
    mesh = current_mesh()
    nsh = mesh_size()
    if mesh is None or nsh <= 1:
        return None
    from ..runtime.faults import fault_point

    fault_point("shuffle")
    axis = mesh.axis_names[0]
    n_l, n_r = int(l_key.shape[0]), int(r_key.shape[0])
    if n_l == 0 or n_r == 0:
        return None  # trivial; the default join handles empties cheaply
    if not _addressable(l_key, l_valid, r_key, r_valid):
        # np.asarray staging would raise, so keep the default
        # (GSPMD-partitioned) sort-probe join
        note_decline("join", "not_addressable")
        return None

    # host staging: drop invalid rows, double the keys into the even
    # namespace, pad to shard multiples with odd pad sentinels. (join()
    # depads its inputs, so the clean row sharding must be rebuilt anyway.)
    staged = _stage_join_sides(l_key, l_valid, r_key, r_valid)
    if staged is None:
        note_decline("join", "key_limit")
        return None
    lk_np, lrow_np, rk_np, rrow_np = staged
    if len(lk_np) == 0 or len(rk_np) == 0:
        return _NO_PAIRS
    with _obs_trace.span("mesh_join:count", kind="mesh", tier="shuffle"):
        lk = _pad_sharded(lk_np * 2, nsh, _L_PAD, mesh, axis)
        rk = _pad_sharded(rk_np * 2, nsh, _R_PAD, mesh, axis)
        lrow = _pad_sharded(lrow_np, nsh, 0, mesh, axis)
        rrow = _pad_sharded(rrow_np, nsh, 0, mesh, axis)

        bl = int(lk.shape[0]) // nsh
        br = int(rk.shape[0]) // nsh
        # capacities snap to the SHARED power-of-two lattice
        # (``bucketing.round_up_pow2`` — same helper as the shape buckets):
        # the static cap is baked into the shard_map programs, so rounding
        # makes nearby input sizes reuse one compiled exchange instead of
        # compiling per size. Overflow detection keeps correctness; <=2x
        # buffer slack.
        cap_l = round_up_pow2(int(bl / nsh * cap_factor) + 16, 16)
        cap_r = round_up_pow2(int(br / nsh * cap_factor) + 16, 16)
        # what leaves each chip: nsh - 1 of its nsh blocks, per side; the
        # count exchanges the keys, the materialize keys and row numbers
        moved = nsh * (nsh - 1) * (cap_l + cap_r) * 8

        counts, overflow = _count_fn(mesh, axis, nsh, cap_l, cap_r)(
            lk, lrow, rk, rrow
        )
        note_exchange("shuffle_join", moved)
        counts_np = _to_host("shuffle", counts)
        overflowed = bool(_to_host("shuffle", overflow).any())
    if overflowed:
        # skewed keys: fall back to the global sort-probe join
        note_decline("join", "overflow")
        return None
    _MESH_JOIN_TOTAL.inc(tier="shuffle")
    _obs_trace.note("join_shards", nsh)
    out_cap = int(counts_np.max()) if counts_np.size else 0
    if out_cap == 0:
        return _NO_PAIRS
    # same lattice for the output capacity (slots past the true per-shard
    # total come out valid=False and are compacted away below)
    out_cap = round_up_pow2(out_cap, 16)
    total = int(counts_np.sum())

    def pairs():
        with _obs_trace.span("mesh_join:materialize", kind="mesh",
                             tier="shuffle"):
            l_out, r_out, valid = _materialize_fn(
                mesh, axis, nsh, cap_l, cap_r, out_cap
            )(lk, lrow, rk, rrow)
            note_exchange("shuffle_join", 2 * moved)
            return _compact_pairs(l_out, r_out, valid, total)

    return CountedJoin(total, pairs)


def hash_repartition_join(
    l_key, l_valid, r_key, r_valid, cap_factor: float = 2.0
) -> Optional[Tuple[Any, Any, int]]:
    """``hash_repartition_join_count`` and then its materialize. Returns
    (left_rows, right_rows, total): int64 arrays of matching GLOBAL row
    indices, the ``total`` pairs first and the lanes up to
    ``bucketing.round_size(total)`` pad (``_compact_pairs``), or None where
    the count phase declines."""
    counted = hash_repartition_join_count(
        l_key, l_valid, r_key, r_valid, cap_factor
    )
    return None if counted is None else counted.pairs()


# ---------------------------------------------------------------------------
# sharded DISTINCT: hash-repartition the equivalence keys so equal values
# meet on one shard, count run boundaries locally, psum the partial counts
# ---------------------------------------------------------------------------

_DISTINCT_CACHE: Dict[Any, Any] = {}


def _distinct_fn(mesh, axis, nsh, cap):
    key = (mesh, axis, cap)
    got = _DISTINCT_CACHE.get(key)
    if got is not None:
        return got

    def local(keys, live):
        # route by mixed VALUE so every occurrence of a key lands on one
        # shard; liveness travels as a sidecar lane (packed equivalence
        # keys use the full 63-bit namespace, so no key value can be
        # reserved as a pad sentinel the way the join's doubling does)
        n = keys.shape[0]
        is_live = live != 0
        tgt = jnp.where(
            is_live,
            _mix64(keys) % jnp.uint64(nsh),
            (jnp.arange(n) % nsh).astype(jnp.uint64),
        ).astype(jnp.int32)
        order = jnp.argsort(tgt, stable=True)
        tgt_s = jnp.take(tgt, order)
        is_real = jnp.take(is_live, order)
        creal = jnp.cumsum(is_real.astype(jnp.int64))
        start = jnp.searchsorted(tgt_s, tgt_s, side="left")
        before = jnp.where(
            start > 0, jnp.take(creal, jnp.maximum(start - 1, 0)), 0
        )
        rank = creal - 1 - before
        overflow = jnp.any((rank >= cap) & is_real)
        rank_c = jnp.where(is_real, jnp.minimum(rank, cap), cap)
        keys_s = jnp.take(keys, order)
        buf_k = jnp.zeros((nsh, cap + 1), jnp.int64)
        buf_v = jnp.zeros((nsh, cap + 1), jnp.int64)
        buf_k = buf_k.at[tgt_s, rank_c].set(
            jnp.where(rank_c < cap, keys_s, 0)
        )
        buf_v = buf_v.at[tgt_s, rank_c].set(
            jnp.where(rank_c < cap, is_real.astype(jnp.int64), 0)
        )
        rk = lax.all_to_all(buf_k[:, :cap], axis, 0, 0, tiled=True).reshape(-1)
        rv = lax.all_to_all(buf_v[:, :cap], axis, 0, 0, tiled=True).reshape(-1)
        live2 = rv != 0
        # live rows sort to the front (dead-last), grouped by key: a run
        # boundary among the live prefix is one distinct value
        order2 = jnp.lexsort((rk, (~live2).astype(jnp.int8)))
        k_s = jnp.take(rk, order2)
        l_s = jnp.take(live2, order2)
        idx = jnp.arange(k_s.shape[0])
        first = l_s & ((idx == 0) | (k_s != jnp.roll(k_s, 1)))
        local_distinct = jnp.sum(first.astype(jnp.int64))
        return lax.psum(local_distinct, axis)[None], overflow[None]

    spec = P(axis)
    fn = _obs_trace.program(jax.jit(
        shard_map(
            local, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec)
        )
    ))
    _DISTINCT_CACHE[key] = fn
    return fn


def sharded_distinct_count(
    keys, valid=None, cap_factor: float = 2.0
) -> Optional[int]:
    """Distinct count of int64 equivalence keys over the active mesh: the
    DISTINCT analog of ``hash_repartition_join`` — one tiled ``all_to_all``
    routes every occurrence of a key value to ``mix(value) % n_shards``, so
    each shard's local run-boundary count is over a disjoint slice of the
    value space and the partials ``psum`` exactly. Returns the count, or
    None when no multi-device mesh is active, rows are not addressable
    from this process, or a skewed key distribution overflows the static
    bucket capacity — the caller keeps the global sort path."""
    mesh = current_mesh()
    nsh = mesh_size()
    if mesh is None or nsh <= 1:
        return None
    if not _addressable(keys, valid):
        note_decline("distinct", "not_addressable")
        return None
    from ..runtime.faults import fault_point

    fault_point("shuffle")
    axis = mesh.axis_names[0]
    with _obs_trace.span("mesh_distinct:stage", kind="mesh"):
        k_np = _to_host("shuffle", keys, np.int64)
        if valid is not None:
            k_np = k_np[_to_host("shuffle", valid)]
    n = len(k_np)
    if n == 0:
        return 0
    with _obs_trace.span("mesh_distinct:count", kind="mesh"):
        k = _pad_sharded(k_np, nsh, 0, mesh, axis)
        live = _pad_sharded(np.ones(n, dtype=np.int64), nsh, 0, mesh, axis)
        b = int(k.shape[0]) // nsh
        cap = round_up_pow2(int(b / nsh * cap_factor) + 16, 16)
        counts, overflow = _distinct_fn(mesh, axis, nsh, cap)(k, live)
        # keys and their liveness lane: nsh - 1 of each chip's nsh blocks
        note_exchange("distinct", nsh * (nsh - 1) * cap * 16)
        if bool(_to_host("shuffle", overflow).any()):
            note_decline("distinct", "overflow")
            return None
        _MESH_DISTINCT_TOTAL.inc()
        _obs_trace.note("distinct_shards", nsh)
        return int(_to_host("shuffle", counts)[0])


# every jitted program of this module dispatches under an obs.trace
# ``dispatch`` leaf (last: the decorators above stay plain ``jax.jit``)
_obs_trace.wrap_programs(globals())
