"""Fused device kernels for the hot query shapes.

The reference's hot loop is the Expand join cascade
(``RelationalPlanner.scala:130-165``: each hop = relationship scan + 2 hash
joins on the engine's shuffle machinery). The TPU-native replacement operates
on CSR topology resident in HBM:

* ``CsrGraph``        — compacted int32-indexed CSR built once per
                        relationship type (ids stay int64 at the table level)
* ``two_hop_count``   — 2-hop path count via degree gather + segment sum
* ``two_hop_expand``  — full 2-hop materialization (static output size via
                        ``total_repeat_length``) + distinct-pair count
* ``triangle_count``  — ExpandInto closure via sorted-edge binary search
* ``walk_counts``     — the iterated-SpMM frontier loop (``lax.scan``) that
                        replaces ``VarLengthExpandPlanner``'s unrolled joins

All kernels are shape-static and fully jittable; sizes that depend on data
(2-hop total) are computed by a tiny count kernel first, then baked as static
arguments — the XLA-friendly version of dynamic join output sizing."""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Tuple

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
from jax import lax

from ...obs import trace as _obs_trace


@dataclass
class CsrGraph:
    """Compacted CSR over one relationship type.

    ``node_ids``: sorted unique int64 element ids (index = compact id)
    ``row_ptr``:  (N+1,) int32 offsets into ``col_idx``
    ``col_idx``:  (E,) int32 target compact ids, sorted within each row
    ``src_idx``:  (E,) int32 source compact id per edge (row-expanded)
    """

    node_ids: jnp.ndarray
    row_ptr: jnp.ndarray
    col_idx: jnp.ndarray
    src_idx: jnp.ndarray

    @property
    def num_nodes(self) -> int:
        return int(self.node_ids.shape[0])

    @property
    def num_edges(self) -> int:
        return int(self.col_idx.shape[0])

    @staticmethod
    def build(node_ids: np.ndarray, src: np.ndarray, dst: np.ndarray) -> "CsrGraph":
        # native C++ path (two stable counting sorts, O(E+N)) when available
        from ...native import build_csr_native

        native = build_csr_native(node_ids, src, dst)
        if native is not None:
            ids, row_ptr, col_idx, src_idx = native
            return CsrGraph(
                jnp.asarray(ids),
                jnp.asarray(row_ptr),
                jnp.asarray(col_idx),
                jnp.asarray(src_idx),
            )
        node_ids = np.unique(np.asarray(node_ids, dtype=np.int64))
        s = np.searchsorted(node_ids, src).astype(np.int32)
        d = np.searchsorted(node_ids, dst).astype(np.int32)
        n = len(node_ids)
        # same contract as the native path: every endpoint must be a node
        if len(s) and (
            s.max(initial=0) >= n
            or d.max(initial=0) >= n
            or not (node_ids[s] == np.asarray(src, dtype=np.int64)).all()
            or not (node_ids[d] == np.asarray(dst, dtype=np.int64)).all()
        ):
            raise ValueError("Edge endpoint id not present in node_ids")
        order = np.lexsort((d, s))
        s, d = s[order], d[order]
        row_ptr = np.searchsorted(s, np.arange(n + 1)).astype(np.int32)
        return CsrGraph(
            jnp.asarray(node_ids),
            jnp.asarray(row_ptr),
            jnp.asarray(d),
            jnp.asarray(s),
        )

    @property
    def degrees(self) -> jnp.ndarray:
        return self.row_ptr[1:] - self.row_ptr[:-1]


# ---------------------------------------------------------------------------
# 2-hop (Expand -> Expand)
# ---------------------------------------------------------------------------


@jax.jit
def two_hop_count(row_ptr: jnp.ndarray, col_idx: jnp.ndarray) -> jnp.ndarray:
    """Number of 2-hop paths a->b->c = sum over edges (a,b) of outdeg(b)."""
    deg = row_ptr[1:] - row_ptr[:-1]
    return jnp.sum(jnp.take(deg, col_idx).astype(jnp.int64))


@partial(jax.jit, static_argnames=("total", "count_distinct"))
def two_hop_expand(
    row_ptr: jnp.ndarray,
    col_idx: jnp.ndarray,
    src_idx: jnp.ndarray,
    total: int,
    count_distinct: bool = True,
):
    """Materialize all 2-hop pairs (a, c); optionally count distinct pairs.

    ``total`` must equal ``two_hop_count`` (computed once host-side); with it
    static, every intermediate is fixed-shape: the join cascade becomes
    repeat + gather, which XLA lays out as pure HBM streaming.

    TPU random-gather throughput (~1e8 elem/s on v5e) is the cost model, so
    the kernel packs everything per-first-edge into ONE int64 word and does a
    single variable repeat plus a single data-dependent gather (``col_idx``
    by second-edge index) instead of five separate gathers — 3x faster than
    the naive lowering of the reference's two joins."""
    num_edges = int(col_idx.shape[0])
    n = row_ptr.shape[0] - 1
    deg = (row_ptr[1:] - row_ptr[:-1]).astype(jnp.int32)
    deg_b = deg[col_idx]  # second-hop fanout per first edge
    # total is static: pick the cumsum dtype so the running sum cannot wrap
    # (the >=2^31-path-count regime falls through to the int64 branch below)
    off_t = jnp.int32 if total < 2**31 else jnp.int64
    excl = jnp.concatenate(
        [jnp.zeros(1, off_t), jnp.cumsum(deg_b, dtype=off_t)]
    )[:-1]
    # pack (source a, biased second-edge base) into one word so one repeat
    # carries both; base = row_ptr[b] - excl + total stays non-negative
    base_bits = max(1, (num_edges + total).bit_length())
    src_bits = 32  # compact ids are int32
    if base_bits + src_bits <= 63:
        shift = base_bits
        pack = (src_idx.astype(jnp.int64) << shift) | (
            (row_ptr[col_idx] - excl + total).astype(jnp.int64)
        )
        r = jnp.repeat(pack, deg_b, total_repeat_length=total)
        a = (r >> shift).astype(jnp.int32)
        second_edge = (r & ((1 << shift) - 1)).astype(jnp.int32) + (
            jnp.arange(total, dtype=jnp.int32) - total
        )
    else:  # enormous graphs: fall back to two repeats
        a = jnp.repeat(src_idx, deg_b, total_repeat_length=total)
        base = (row_ptr[col_idx].astype(jnp.int64) - excl.astype(jnp.int64))
        second_edge = jnp.repeat(base, deg_b, total_repeat_length=total) + jnp.arange(
            total, dtype=jnp.int64
        )
    c = col_idx[second_edge]
    if not count_distinct:
        return a, c
    key = a.astype(jnp.int64) * n + c.astype(jnp.int64)
    sorted_key = jnp.sort(key)
    distinct = jnp.sum(
        jnp.concatenate([jnp.ones(1, bool), sorted_key[1:] != sorted_key[:-1]])
    ) if total > 0 else jnp.int64(0)
    return a, c, distinct


@partial(jax.jit, static_argnames=("total",))
def triangle_count(
    row_ptr: jnp.ndarray,
    col_idx: jnp.ndarray,
    src_idx: jnp.ndarray,
    total: int,
) -> jnp.ndarray:
    """Count directed triangles a->b->c->a (the ExpandInto closure): for every
    2-hop path, a sorted-edge binary search checks the closing edge."""
    a, c = two_hop_expand(row_ptr, col_idx, src_idx, total, count_distinct=False)
    n = row_ptr.shape[0] - 1
    edge_keys = src_idx.astype(jnp.int64) * n + col_idx.astype(jnp.int64)
    # edges are lexsorted by (src, dst) already -> edge_keys sorted; each
    # closing relationship instance is its own match (Cypher counts rel
    # triples), so sum the closing edge's multiplicity
    probe = c.astype(jnp.int64) * n + a.astype(jnp.int64)
    lo = jnp.searchsorted(edge_keys, probe, side="left")
    hi = jnp.searchsorted(edge_keys, probe, side="right")
    return jnp.sum((hi - lo).astype(jnp.int64))


# ---------------------------------------------------------------------------
# Var-length frontier loop (the SpMM replacement for VarLengthExpandPlanner)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("hops", "num_nodes"))
def walk_counts(
    src_idx: jnp.ndarray,
    col_idx: jnp.ndarray,
    start: jnp.ndarray,
    hops: int,
    num_nodes: int,
) -> jnp.ndarray:
    """Iterated sparse frontier propagation: ``p_{k+1}[v] = sum_{(u,v)} p_k[u]``.

    Returns (hops, N) walk counts for k = 1..hops — the lax.scan analog of the
    reference's unrolled join loop (``VarLengthExpandPlanner.scala:233``),
    counting walks (edge-distinctness is enforced in the relational path;
    this kernel backs counting/reachability workloads and the benchmark)."""

    def step(p, _):
        contrib = p[src_idx]
        nxt = jax.ops.segment_sum(contrib, col_idx, num_segments=num_nodes)
        return nxt, nxt

    _, per_hop = lax.scan(step, start.astype(jnp.int64), None, length=hops)
    return per_hop


# every jitted program of this module dispatches under an obs.trace
# ``dispatch`` leaf (last: the decorators above stay plain ``jax.jit``)
_obs_trace.wrap_programs(globals())
