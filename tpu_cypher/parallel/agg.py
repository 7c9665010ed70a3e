"""Sharded segment aggregates: per-shard partials tree-combined over ICI.

The reference delegates grouped aggregation to the engines' shuffle-reduce
(partial aggregates per partition, combined at the exchange — SURVEY §2.3);
the mesh analog computes each shard's ``segment_*`` partial over its local
row block and combines the k-sized partials (``psum``; for min/max a
``psum``-gathered stack reduced locally) inside one ``shard_map`` program,
so no shard ever holds the full row set.

Eligibility is deliberately narrow: INTEGER data (I64/BOOL) and the
aggregates whose combine is exact over the integers (count/sum/min/max,
plus avg as an integer-sum over integer-count divide). Float addition is
not associative, so a float psum could differ from the single-device result
in the last ulp — the differential suite pins sharded results BIT-IDENTICAL
to single-device, and the float kinds keep the global path. Gate:
``TPU_CYPHER_MESH_AGG=off`` disables the tier entirely.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..backend.tpu.jit_ops import segment_aggregate_form, segment_reduce
from ..obs import trace as _obs_trace
from ..obs.metrics import REGISTRY as _REGISTRY
from ..runtime.faults import fault_point
from .mesh import (
    current_mesh,
    mesh_size,
    note_decline,
    note_exchange,
    shard_map,
)
from .shuffle import _addressable, _pad_sharded, _to_host

_MESH_AGG_TOTAL = _REGISTRY.counter(
    "tpu_cypher_mesh_agg_total",
    "grouped aggregates executed on the sharded (per-shard partial + "
    "tree combine) tier",
)

# aggregate names whose per-shard combine is exact over the integers
_INT_NAMES = ("count", "sum", "min", "max", "avg")

# jitted shard_map programs, memoized per (mesh, aggregate, dtype, k) —
# fresh factories per call would recompile the collective every query
# (the recompile-hazard lint rule)
_AGG_CACHE: Dict[Any, Any] = {}


def _agg_fn(mesh, axis: str, name: str, is_bool: bool, k: int):
    key = (mesh, axis, name, is_bool, k)
    got = _AGG_CACHE.get(key)
    if got is not None:
        return got

    nsh = mesh.shape[axis]

    def _every_shard(x):
        """Every shard's ``x``, stacked and replicated — as the SUM
        all-reduce of a one-hot placement, because that is the one int64
        all-reduce the TPU's 64-bit rewriter lowers (``pmin``/``pmax`` of
        int64 are refused by the chip's compiler). k partials per shard
        is tiny."""
        slot = jnp.zeros((nsh,) + x.shape, x.dtype)
        return lax.psum(slot.at[lax.axis_index(axis)].set(x), axis)

    def local(data, valid, seg):
        # pad rows staged valid=False: they contribute the combine identity
        cnt = segment_reduce(valid.astype(jnp.int64), seg, k, "sum")
        cnt = lax.psum(cnt, axis)
        if name == "count":
            return cnt, cnt
        if name in ("sum", "avg"):
            ssum = segment_reduce(
                jnp.where(valid, data, jnp.zeros((), data.dtype)), seg, k, "sum"
            )
            return lax.psum(ssum, axis), cnt
        # min / max: same sentinels as the global segment_aggregate so
        # empty-group payloads (masked invalid anyway) stay bit-identical
        d = data.astype(jnp.int8) if is_bool else data
        big = jnp.asarray(jnp.iinfo(d.dtype).max, d.dtype)
        if name == "min":
            agged = segment_reduce(jnp.where(valid, d, big), seg, k, "min")
            agged = jnp.min(_every_shard(agged), axis=0)
        else:
            agged = segment_reduce(jnp.where(valid, d, -big), seg, k, "max")
            agged = jnp.max(_every_shard(agged), axis=0)
        return agged, cnt

    spec = P(axis)
    fn = _obs_trace.program(jax.jit(
        shard_map(
            local, mesh, in_specs=(spec, spec, spec), out_specs=(P(), P())
        )
    ))
    _AGG_CACHE[key] = fn
    return fn


def _gate_open() -> bool:
    from ..utils.config import MESH_AGG

    return MESH_AGG.get().strip().lower() == "auto"


def sharded_segment_agg(
    data, valid, seg_j, name: str, is_bool: bool, k: int
) -> Optional[Tuple[Any, Any]]:
    """One grouped aggregate as per-shard partials + tree combine.

    ``data``/``seg_j`` device (or host) arrays over the same row extent,
    ``valid`` an optional mask. Returns ``(out_data, out_valid_or_None)``
    in the global ``segment_aggregate`` contract, or None when the tier is
    ineligible (no multi-device mesh, a non-integer-exact aggregate, the
    ``TPU_CYPHER_MESH_AGG=off`` gate, or rows this process cannot stage) —
    the caller keeps the global path."""
    mesh = current_mesh()
    nsh = mesh_size()
    if mesh is None or nsh <= 1:
        return None
    if name not in _INT_NAMES or k <= 0:
        note_decline("agg", "not_integer_exact")
        return None
    if not _gate_open():
        note_decline("agg", "gate")
        return None
    if not _addressable(data, valid, seg_j):
        note_decline("agg", "not_addressable")
        return None
    fault_point("agg")  # staging rows to host for resharding syncs here
    with _obs_trace.span("mesh_agg:stage", kind="mesh"):
        d_np = _to_host("agg", data)
        n = d_np.shape[0]
        if n == 0:
            return None  # nothing to shard: the global path's empty groups
        v_np = (
            np.ones(n, bool) if valid is None
            else _to_host("agg", valid, bool)
        )
        s_np = _to_host("agg", seg_j, np.int64)
    axis = mesh.axis_names[0]
    with _obs_trace.span("mesh_agg:combine", kind="mesh", agg=name):
        d = _pad_sharded(d_np, nsh, 0, mesh, axis)
        v = _pad_sharded(v_np, nsh, False, mesh, axis)
        s = _pad_sharded(s_np, nsh, 0, mesh, axis)
        out, cnt = _agg_fn(mesh, axis, name, bool(is_bool), int(k))(d, v, s)
        # every shard hands the mesh its k counts, and k partials more: as
        # they are for a sum, placed in a stack of nsh for a min or a max
        partials = {"count": 0, "sum": k, "avg": k}.get(name, nsh * k)
        note_exchange("agg", nsh * (k + partials) * 8)
    _MESH_AGG_TOTAL.inc()
    _obs_trace.note("agg_shards", nsh)
    _obs_trace.note_agg_form(segment_aggregate_form(name, d_np.dtype, k))
    if name == "count":
        return out, None
    if name == "sum":
        return out, None
    if name == "avg":
        avg = out.astype(jnp.float64) / jnp.maximum(cnt, 1)
        return avg, cnt > 0
    agged = out.astype(bool) if is_bool else out
    return agged, cnt > 0


# ---------------------------------------------------------------------------
# run-length weighted partials (factorized join intermediates)
# ---------------------------------------------------------------------------


@jax.jit
def _weighted_premultiply(data, valid, weight):
    """Per-row weighted terms: each logical row stands for ``weight``
    identical flat rows, so its count contribution is ``weight`` (0 when
    invalid) and its sum contribution is ``data * weight``."""
    w = weight if valid is None else jnp.where(valid, weight, 0)
    if data is None:
        return None, w
    zero = jnp.zeros((), data.dtype)
    d = data if valid is None else jnp.where(valid, data, zero)
    return d * w.astype(data.dtype), w


@partial(jax.jit, static_argnames=("k",))
def _weighted_segment_sums(pre_sum, pre_cnt, seg_j, k: int):
    wcnt = segment_reduce(pre_cnt, seg_j, k, "sum")
    if pre_sum is None:
        return None, wcnt
    return segment_reduce(pre_sum, seg_j, k, "sum"), wcnt


def weighted_segment_partials(data, valid, weight, seg_j, k: int):
    """Weighted segment partials ``(weighted_sum_or_None, weighted_count)``
    for the factorized group path (``backend/tpu/factorized.py``): every
    source row aggregates as ``weight`` identical flat rows without ever
    decompressing. ``data=None`` computes the count partial only (count(*)
    / count(expr) need no values). Integer inputs ride the sharded tier —
    the premultiplied partials are integer sums, so the psum combine stays
    exact/bit-identical — floats and the no-mesh case take one jitted
    segment program."""
    pre_sum, pre_cnt = _weighted_premultiply(data, valid, weight)
    ints = data is None or jnp.issubdtype(data.dtype, jnp.integer)
    if ints:
        got_cnt = sharded_segment_agg(pre_cnt, None, seg_j, "sum", False, k)
        if got_cnt is not None:
            if pre_sum is None:
                return None, got_cnt[0]
            got_sum = sharded_segment_agg(pre_sum, None, seg_j, "sum", False, k)
            if got_sum is not None:
                return got_sum[0], got_cnt[0]
    return _weighted_segment_sums(pre_sum, pre_cnt, seg_j, k)


# every jitted program of this module dispatches under an obs.trace
# ``dispatch`` leaf (last: the decorators above stay plain ``jax.jit``)
_obs_trace.wrap_programs(globals())
