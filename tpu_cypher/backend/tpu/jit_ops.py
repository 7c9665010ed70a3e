"""Cached jitted composites for the TpuTable/expand hot path.

Why this module exists: every EAGER jnp op is its own trace, compile and
dispatch, while a cached jitted program dispatches in microseconds.
The reference never meets this problem (Spark/Flink ship compiled stages to
executors, ``SparkTable.scala:55``); the TPU-native equivalent of "a stage"
is ONE jitted XLA program per relational-operator phase.

Every function here is a MODULE-LEVEL ``jax.jit`` so the compile cache is
keyed only by input shapes/dtypes/pytree structure plus explicit static
arguments. Data-dependent output sizes follow the two-phase discipline the
fused kernels already used: a jitted size pass, one scalar device->host
sync, then a jitted materialize pass with the size baked static
(``total_repeat_length`` / ``jnp.nonzero(size=...)``).

Pytree notes: column dicts map name -> (data, valid_or_None, iflag_or_None);
``None`` is a structural pytree entry, so optional masks cost nothing and
select the right compiled variant automatically.
"""

from __future__ import annotations

import functools
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

I64 = "i64"
F64 = "f64"
BOOL = "bool"
STR = "str"
DUR = "dur"  # int64 (n, 3): months / days / total micros (column.DUR)

# duration order key basis — ONE definition, shared with the oracle
# (api.values.duration_order_us) so device and host ordering can never drift
from ...api.values import _DUR_DAY_US as DUR_DAY_US  # noqa: E402
from ...api.values import _DUR_MONTH_US as DUR_MONTH_US  # noqa: E402
from ...obs import trace as _obs_trace  # noqa: E402


def _dur_order_key(d2):
    return d2[:, 0] * DUR_MONTH_US + d2[:, 1] * DUR_DAY_US + d2[:, 2]


def _exclusive_cumsum(x):
    return jnp.concatenate([jnp.zeros(1, x.dtype), jnp.cumsum(x)])[:-1]


def _expand_rows(starts, counts, total: int):
    """Traced helper shared by every segment-materialize: emit ``counts[i]``
    rows for source row i; returns (row index per output row, flat position
    ``starts[i] + k`` for the k-th emission of row i)."""
    nrows = counts.shape[0]
    row = jnp.repeat(
        jnp.arange(nrows, dtype=jnp.int64), counts, total_repeat_length=total
    )
    base = starts.astype(jnp.int64) - _exclusive_cumsum(counts)
    flat = jnp.repeat(base, counts, total_repeat_length=total) + jnp.arange(
        total, dtype=jnp.int64
    )
    return row, flat


def _pack_fold(keys, pack):
    """Traced helper: fold integer key arrays into one 63-bit key."""
    ints = [k.astype(jnp.int64) for k in keys]
    acc = jnp.zeros_like(ints[0])
    for k, (lo, b) in zip(ints, pack):
        acc = (acc << b) | (k - lo)
    return acc


def _live_lanes(total: int, nvalid):
    """lane < nvalid over a bucket-padded axis (``total`` static lanes,
    ``nvalid`` the traced true count). The shared bucket-pad liveness mask:
    lanes at/past ``nvalid`` are pad lanes whose payload must be masked out
    (see ``bucketing.round_size``)."""
    return jnp.arange(total, dtype=jnp.int64) < nvalid


# ---------------------------------------------------------------------------
# masks / compaction
# ---------------------------------------------------------------------------


@jax.jit
def mask_sum(mask):
    return jnp.sum(mask)


@jax.jit
def row_tail_mask(template, n):
    """bool[len(template)]: lane < n — the row-validity of a tail-padded
    (bucketed/sharded) column axis, shaped off ``template``."""
    return jnp.arange(template.shape[0], dtype=jnp.int64) < n


@jax.jit
def filter_keep_mask(data, valid, n):
    """Filter keep mask over a bucket-padded table: predicate data AND its
    validity AND lane < n (pad rows must never survive a filter even when
    the predicate evaluates truthy on their duplicated payload)."""
    keep = data & valid if valid is not None else data
    return keep & (jnp.arange(keep.shape[0], dtype=jnp.int64) < n)


@jax.jit
def concat_pair(a, b):
    return jnp.concatenate([a, b])


@partial(jax.jit, static_argnames=("size",))
def mask_nonzero(mask, size: int):
    return jnp.nonzero(mask, size=size)[0]


def mask_count(mask) -> int:
    """True lanes of a boolean device mask, on the host: one dispatch and
    ONE scalar sync (site ``compact``). The first phase of every
    compaction, and all of a pushed-down ``count(*)``
    (``TpuTable.filter_count``)."""
    from ...runtime.faults import fault_point

    fault_point("compact")
    n_dev = mask_sum(mask)
    with _obs_trace.sync("compact"):
        return int(n_dev)


def mask_to_idx(mask) -> Tuple[Any, int]:
    """Boolean device mask -> (index array, count); one scalar sync."""
    count = mask_count(mask)
    # tpulint: allow[pad-invariant] reason=the exact-compact primitive itself; bucketed callers go through mask_to_idx_bucketed, and the ladder's bucket-exact rung NEEDS the unrounded size
    return mask_nonzero(mask, size=count), count


@jax.jit
def and_valid_mask(data, valid):
    """filter mask = data & valid (valid=None handled by structure)."""
    return data & valid if valid is not None else data


@jax.jit
def any_true(mask):
    return jnp.any(mask)


@jax.jit
def any_nan_valid(data, valid):
    nan = jnp.isnan(data)
    return jnp.any(nan & valid if valid is not None else nan)


@jax.jit
def take_take(a, idx_outer, idx_inner):
    return jnp.take(a, jnp.take(idx_outer, idx_inner))


# ---------------------------------------------------------------------------
# batched column gathers (one dispatch per table op, not per column)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("first",))
def cols_take(cols: Dict[str, Tuple[Any, Any, Any]], idx, first: Optional[int] = None):
    """Gather every column at ``idx``; a static ``first`` gathers at
    ``idx[:first]`` alone (ORDER BY ... LIMIT: the prefix of a permutation
    is cut here, in the program that is cheap to compile for each new
    LIMIT, and never in the sort that made it)."""
    idx = idx[:first]
    out = {}
    for c, (data, valid, iflag) in cols.items():
        out[c] = (
            jnp.take(data, idx, axis=0),
            jnp.take(valid, idx, axis=0) if valid is not None else None,
            jnp.take(iflag, idx, axis=0) if iflag is not None else None,
        )
    return out


@jax.jit
def cols_take_or_null(cols: Dict[str, Tuple[Any, Any, Any]], idx, in_bounds):
    safe = jnp.where(in_bounds, idx, 0)
    out = {}
    for c, (data, valid, iflag) in cols.items():
        d = jnp.take(data, safe, axis=0)
        v = (
            jnp.take(valid, safe, axis=0)
            if valid is not None
            else jnp.ones(idx.shape[0], bool)
        )
        i = (
            jnp.take(iflag, safe, axis=0) & in_bounds
            if iflag is not None
            else None
        )
        out[c] = (d, v & in_bounds, i)
    return out


@jax.jit
def tree_take(arrays, idx):
    """Gather a pytree of same-length arrays by one index array."""
    return jax.tree.map(lambda a: jnp.take(a, idx, axis=0), arrays)


@jax.jit
def cols_take_counted(cols: Dict[str, Tuple[Any, Any, Any]], idx, count):
    """``cols_take`` for a BUCKET-PADDED gather: ``idx`` has pad lanes past
    the traced true ``count`` (filled with duplicate indices by the sizing
    discipline); gathered rows at those lanes come out INVALID, so the
    output is a tail-padded column set with ``count`` logical rows."""
    live = jnp.arange(idx.shape[0], dtype=jnp.int64) < count
    out = {}
    for c, (data, valid, iflag) in cols.items():
        d = jnp.take(data, idx, axis=0)
        v = (
            jnp.take(valid, idx, axis=0) & live
            if valid is not None
            else live
        )
        i = jnp.take(iflag, idx, axis=0) if iflag is not None else None
        out[c] = (d, v, i)
    return out


@jax.jit
def cols_concat(a_cols, b_cols):
    """UNION ALL for structurally simple columns: same kind/dtype/vocab on
    both sides — one dispatch for the whole table. Mixed valid/iflag
    presence is harmonized inside (None = all-valid / no-int-rows)."""
    out = {}
    for c, (ad, av, ai) in a_cols.items():
        bd, bv, bi = b_cols[c]
        data = jnp.concatenate([ad, bd])
        if av is None and bv is None:
            valid = None
        else:
            valid = jnp.concatenate([
                av if av is not None else jnp.ones(ad.shape[0], bool),
                bv if bv is not None else jnp.ones(bd.shape[0], bool),
            ])
        if ai is None and bi is None:
            iflag = None
        else:
            iflag = jnp.concatenate([
                ai if ai is not None else jnp.zeros(ad.shape[0], bool),
                bi if bi is not None else jnp.zeros(bd.shape[0], bool),
            ])
        out[c] = (data, valid, iflag)
    return out


@jax.jit
def cols_union_counted(a_cols, b_cols, idx, count):
    """``cols_concat`` for BUCKET-PADDED inputs: concatenate the PHYSICAL
    (lattice-shaped) arrays, then gather both sides' logical rows to the
    front through ``idx`` — host-built positions travel as a device
    operand, so logical row counts never key compilation. Lanes at or
    past the traced true ``count`` are dead duplicates; the output is a
    tail-padded column set with ``count`` logical rows, same contract as
    ``cols_take_counted``."""
    live = jnp.arange(idx.shape[0], dtype=jnp.int64) < count
    out = {}
    for c, (ad, av, ai) in a_cols.items():
        bd, bv, bi = b_cols[c]
        data = jnp.take(jnp.concatenate([ad, bd]), idx, axis=0)
        if av is None and bv is None:
            valid = live
        else:
            valid = jnp.take(
                jnp.concatenate([
                    av if av is not None else jnp.ones(ad.shape[0], bool),
                    bv if bv is not None else jnp.ones(bd.shape[0], bool),
                ]),
                idx,
                axis=0,
            ) & live
        if ai is None and bi is None:
            iflag = None
        else:
            iflag = jnp.take(
                jnp.concatenate([
                    ai if ai is not None else jnp.zeros(ad.shape[0], bool),
                    bi if bi is not None else jnp.zeros(bd.shape[0], bool),
                ]),
                idx,
                axis=0,
            ) & live
        out[c] = (data, valid, iflag)
    return out


# ---------------------------------------------------------------------------
# fused CSR expand phases
# ---------------------------------------------------------------------------


@jax.jit
def compact_lookup(dev_ids, ids, valid):
    """Element ids -> (compact positions, present mask)."""
    n = dev_ids.shape[0]
    pos = jnp.clip(jnp.searchsorted(dev_ids, ids), 0, n - 1)
    ok = jnp.take(dev_ids, pos) == ids
    if valid is not None:
        ok = ok & valid
    return pos.astype(jnp.int64), ok


@jax.jit
def expand_degrees_total(rp, pos, present):
    deg = (jnp.take(rp, pos + 1) - jnp.take(rp, pos)).astype(jnp.int64)
    deg = jnp.where(present, deg, 0)
    return deg, jnp.sum(deg)


@jax.jit
def frontier_degree_sum(rp, pos, present):
    """``sum over present frontier rows of (rp[pos+1] - rp[pos])``: the
    single-hop count(*) as one O(frontier) two-gather reduction."""
    return expand_degrees_total(rp, pos, present)[1]


@jax.jit
def range_count(keys, q, qvalid):
    """Per query lane the first position in the ascending int64 ``keys``
    matching ``q`` and the match count (0 where ``qvalid`` is False), plus
    the traced total — the WCOJ leapfrog search step. Pad sentinels
    (``1 << 62``) sort past every real query, so they never enter a
    counted range."""
    lo = jnp.searchsorted(keys, q, side="left")
    hi = jnp.searchsorted(keys, q, side="right")
    counts = jnp.where(qvalid, hi - lo, 0).astype(jnp.int64)
    return lo.astype(jnp.int64), counts, jnp.sum(counts)


@partial(jax.jit, static_argnames=("n",))
def frontier_multiplicity(pos, present, n: int):
    """int64[n] count of frontier rows per compact node (absent rows spill
    into a dropped slot) — the MXU tier's row-weight vector."""
    acc = jnp.zeros(n + 1, jnp.int64).at[jnp.where(present, pos, n)].add(1)
    return acc[:n]


@partial(jax.jit, static_argnames=("total",))
def expand_materialize(rp, ci, eo, pos, deg, total: int):
    """(row, nbr, orig) for one expand half; ``total`` = sum(deg), static."""
    row, edge = _expand_rows(jnp.take(rp, pos), deg, total)
    nbr = jnp.take(ci, edge).astype(jnp.int64)
    orig = jnp.take(eo, edge)
    return row, nbr, orig


@partial(jax.jit, static_argnames=("size",))
def expand_materialize_counted(rp, ci, eo, pos, deg, nvalid, size: int):
    """``expand_materialize`` at a BUCKETED static ``size`` >= the true
    total (``nvalid``, traced): pad lanes are sanitized to row/edge 0 (the
    raw repeat pads run off the edge array — an out-of-bounds gather under
    jit FILLS with int64 min, which must never escape as an index) and
    reported dead via the returned ``live`` mask."""
    row, edge = _expand_rows(jnp.take(rp, pos), deg, size)
    live = _live_lanes(size, nvalid)
    row = jnp.where(live, row, 0)
    edge = jnp.where(live, edge, 0)
    nbr = jnp.take(ci, edge).astype(jnp.int64)
    orig = jnp.take(eo, edge)
    nbr = jnp.where(live, nbr, 0)
    orig = jnp.where(live, orig, 0)
    return row, nbr, orig, live


@jax.jit
def drop_loops_mask(nbr, pos, row):
    return nbr != jnp.take(pos, row)


@jax.jit
def optional_expand_degrees(rp, pos, present, nrows=None):
    """Row counts for a LEFT-OUTER expand: matched rows emit their degree,
    unmatched (or absent-frontier) rows emit exactly ONE null-padded row.
    ``nrows`` (traced, optional): the table's LOGICAL row count — padding
    tail rows (bucket/shard pads past it) are not input rows and emit
    NOTHING (a pad row is not an unmatched row)."""
    deg = (jnp.take(rp, pos + 1) - jnp.take(rp, pos)).astype(jnp.int64)
    deg = jnp.where(present, deg, 0)
    counts = jnp.maximum(deg, 1)
    if nrows is not None:
        real = jnp.arange(counts.shape[0], dtype=jnp.int64) < nrows
        deg = jnp.where(real, deg, 0)
        counts = jnp.where(real, counts, 0)
    return deg, counts, jnp.sum(counts)


@partial(jax.jit, static_argnames=("total",))
def optional_expand_materialize(rp, ci, eo, pos, deg, counts, total: int):
    """(row, nbr, orig, matched) for a left-outer expand half: pad rows
    carry matched=False and clipped (masked-out downstream) gather
    indices — the fused form of the reference's Optional -> left outer
    join (``RelationalPlanner.scala:298``)."""
    row, flat = _expand_rows(jnp.take(rp, pos), counts, total)
    starts = jnp.take(rp, pos).astype(jnp.int64)
    matched = (flat - jnp.take(starts, row)) < jnp.take(deg, row)
    nedges = ci.shape[0]
    safe = jnp.clip(flat, 0, max(nedges - 1, 0))
    nbr = jnp.take(ci, safe).astype(jnp.int64) if nedges else jnp.zeros(total, jnp.int64)
    orig = jnp.take(eo, safe) if nedges else jnp.zeros(total, jnp.int64)
    return row, nbr, orig, matched


@jax.jit
def far_lookup(row_map, nbr):
    far_rows = jnp.take(row_map, nbr)
    return far_rows, far_rows >= 0


@partial(jax.jit, static_argnames=("drop_loops",))
def into_probe(keys, s_pos, t_pos, ok, n, drop_loops: bool):
    """ExpandInto: count closing edges per (src, dst) pair via binary search
    over the sorted (src*N + dst) edge keys."""
    probe = s_pos * n + t_pos
    if drop_loops:
        ok = ok & (s_pos != t_pos)
    lo = jnp.searchsorted(keys, probe, side="left")
    hi = jnp.searchsorted(keys, probe, side="right")
    counts = jnp.where(ok, hi - lo, 0).astype(jnp.int64)
    return lo, counts, jnp.sum(counts)


@partial(
    jax.jit,
    static_argnames=("total", "src_is_base", "num_nodes", "undirected"),
)
def into_close_count(
    rp, ci, pos, deg, akey, mask, keys,
    total: int, src_is_base: bool, num_nodes: int, undirected: bool,
    nvalid=None,
):
    """Final hop of a count(*) triangle/cycle chain: expand the last hop's
    (base key, far position) pairs and, INSTEAD of materializing columns,
    probe the sorted (src*N + dst) edge keys for closing relationships and
    sum their multiplicities — the whole ExpandInto close fused into one
    program (BASELINE config #3's workload; the materialized path needs the
    full 2-hop row set on device first). Mirrors ``into_probe`` semantics
    exactly, including the swapped-orientation half with loops dropped for
    undirected closes.

    ``nvalid`` (traced, optional): true emission count when ``total`` is a
    BUCKETED static size — pad lanes are sanitized and counted dead."""
    row, edge = _expand_rows(jnp.take(rp, pos), deg, total)
    if nvalid is not None:
        live = _live_lanes(total, nvalid)
        row = jnp.where(live, row, 0)
        edge = jnp.where(live, edge, 0)
    nbr = jnp.take(ci, edge).astype(jnp.int64)
    a = jnp.take(akey, row)
    ok = jnp.take(mask, nbr) if mask is not None else jnp.ones(total, bool)
    if nvalid is not None:
        ok = ok & live
    s, t = (a, nbr) if src_is_base else (nbr, a)

    def probe_count(s, t, ok):
        probe = s * num_nodes + t
        lo = jnp.searchsorted(keys, probe, side="left")
        hi = jnp.searchsorted(keys, probe, side="right")
        return jnp.sum(jnp.where(ok, hi - lo, 0).astype(jnp.int64))

    cnt = probe_count(s, t, ok)
    if undirected:
        cnt = cnt + probe_count(t, s, ok & (s != t))
    return cnt


@partial(
    jax.jit,
    static_argnames=(
        "total", "src_is_base", "num_nodes", "mask_idx", "sub_idx", "sub_cur",
    ),
)
def into_close_count_unique(
    rp, ci, eo, pos, deg, akey, mask, keys, keys_by_orig, prevs,
    total: int, src_is_base: bool, num_nodes: int,
    mask_idx: tuple, sub_idx: tuple, sub_cur: bool, nvalid=None,
):
    """``into_close_count`` with openCypher relationship-uniqueness enforced
    IN the fused program (the reference gets the same semantics from explicit
    ``id(r_i) <> id(r_j)`` filters, Neo4j ``AddUniquenessPredicates``):

    * ``prevs``: carried chain-edge scan rows per partial path (one array
      per earlier hop whose rel participates in an enforced pair);
    * ``mask_idx``: indices into ``prevs`` the CURRENT hop's edge must
      differ from (adjacent/any chain-chain pairs) — equal rows are dead;
    * ``sub_cur`` / ``sub_idx``: closing-rel-vs-chain-rel pairs. The probe
      range counts every type-set edge with key (s,t); a chain edge is in
      that range iff its own (src*N+dst) key equals the probe key, so
      subtracting the key-match indicator removes exactly that edge from
      the closing candidates. Two forbidden rels may bind the SAME edge
      (nothing pairs them when the predicates span MATCH clauses or are
      user-written), so each subtraction is gated on differing from every
      already-subtracted edge — each distinct forbidden in-range edge
      subtracts once (parallel edges keep distinct scan rows — exact)."""
    row, edge = _expand_rows(jnp.take(rp, pos), deg, total)
    if nvalid is not None:
        live = _live_lanes(total, nvalid)
        row = jnp.where(live, row, 0)
        edge = jnp.where(live, edge, 0)
    nbr = jnp.take(ci, edge).astype(jnp.int64)
    orig = jnp.take(eo, edge)
    a = jnp.take(akey, row)
    ok = jnp.take(mask, nbr) if mask is not None else jnp.ones(total, bool)
    if nvalid is not None:
        ok = ok & live
    prevs_r = tuple(jnp.take(p, row) for p in prevs)
    for i in mask_idx:
        ok = ok & (orig != prevs_r[i])
    s, t = (a, nbr) if src_is_base else (nbr, a)
    probe = s * num_nodes + t
    lo = jnp.searchsorted(keys, probe, side="left")
    hi = jnp.searchsorted(keys, probe, side="right")
    cnt = (hi - lo).astype(jnp.int64)
    subbed = []
    if sub_cur:
        cnt = cnt - (jnp.take(keys_by_orig, orig) == probe).astype(jnp.int64)
        subbed.append(orig)
    for i in sub_idx:
        p = prevs_r[i]
        ind = jnp.take(keys_by_orig, p) == probe
        for e in subbed:
            ind = ind & (p != e)
        cnt = cnt - ind.astype(jnp.int64)
        subbed.append(p)
    return jnp.sum(jnp.where(ok, cnt, 0))


@partial(jax.jit, static_argnames=("total",))
def into_materialize(eo, lo, counts, total: int):
    row, edge = _expand_rows(lo, counts, total)
    return row, jnp.take(eo, edge)


@partial(jax.jit, static_argnames=("size",))
def into_materialize_counted(eo, lo, counts, nvalid, size: int):
    """``into_materialize`` at a BUCKETED static ``size`` >= the true close
    count (``nvalid``, traced): pad lanes are sanitized to row/edge 0 and
    come out as tail pads masked dead downstream."""
    row, edge = _expand_rows(lo, counts, size)
    live = _live_lanes(size, nvalid)
    row = jnp.where(live, row, 0)
    edge = jnp.where(live, edge, 0)
    return row, jnp.take(eo, edge), live


@jax.jit
def concat_into_halves(row1, orig1, row2, orig2):
    swapped = jnp.concatenate(
        [jnp.zeros(row1.shape[0], bool), jnp.ones(row2.shape[0], bool)]
    )
    return (
        jnp.concatenate([row1, row2]),
        jnp.concatenate([orig1, orig2]),
        swapped,
    )


@jax.jit
def concat_expand_halves(row1, nbr1, orig1, row2, nbr2, orig2):
    swapped = jnp.concatenate(
        [jnp.zeros(row1.shape[0], bool), jnp.ones(row2.shape[0], bool)]
    )
    return (
        jnp.concatenate([row1, row2]),
        jnp.concatenate([nbr1, nbr2]),
        jnp.concatenate([orig1, orig2]),
        swapped,
    )


@jax.jit
def gather_swapped(a_data, b_data, a_valid, b_valid, orig, swapped):
    """Start/End columns of an undirected expand: per-row pick between the
    canonical (a) and flipped (b) rel-scan column, gathered by ``orig``."""
    a = jnp.take(a_data, orig, axis=0)
    b = jnp.take(b_data, orig, axis=0)
    data = jnp.where(swapped, b, a)
    valid = None
    if a_valid is not None or b_valid is not None:
        av = (
            jnp.take(a_valid, orig, axis=0)
            if a_valid is not None
            else jnp.ones(orig.shape[0], bool)
        )
        bv = (
            jnp.take(b_valid, orig, axis=0)
            if b_valid is not None
            else jnp.ones(orig.shape[0], bool)
        )
        valid = jnp.where(swapped, bv, av)
    return data, valid


# ---------------------------------------------------------------------------
# fused count chain: scan -> expand^k -> count(*) as ONE program
# ---------------------------------------------------------------------------


def chain_forms(masked: Sequence[bool], whole: bool) -> Tuple[str, ...]:
    """The form each hop of a count chain takes, in the order executed
    (far end first); ``masked[i]`` says whether that hop's far node
    carries a label mask, ``whole`` whether the frontier holds every node
    exactly once. Chosen from those alone, at trace time and — by the same
    function — on the host that counts them:

    * ``degree``: the weights are still the constant 1, so the hop is the
      degree vector, ``row_ptr`` differences; no edge is read;
    * ``reduce``: the hop next to a whole frontier feeds a plain sum over
      every node, which is ``sum_e w[ci[e]]``: one gather over the edge
      lanes and one reduction, no prefix sums;
    * ``scan``: per-node sums of non-constant weights, the prefix-scan
      SpMV (``_csr_spmv``).

    A chain is a tree without a branch: the rule is ``tree_forms``'."""
    tree = ()
    for k in range(len(masked)):  # the far end is the leaf
        tree = ((k, False, tree),)
    return tree_forms(tree, masked, whole)


def _csr_spmv(rp, ci, w, span=None):
    """(A w)[n] = sum of w[ci[e]] over n's CSR edge range — computed as a
    cumsum difference at row_ptr boundaries: gathers + one scan, ZERO
    scatters (TPU scatter-add serializes; this stays on the VPU). Pad
    safety: a sharding pad tail (``ci`` = -1, clipped to 0) accumulates
    into cumsum positions past ``rp[-1]`` that no boundary ever reads.

    The node side is bounded by the rows the CSR holds, not by the id
    space: ``span`` = ``(window, start)`` (``GraphIndex.csr_row_span``) is
    the slice ``rp[start : start + L + 1]`` and where it starts (traced;
    ``L`` is the slice's static shape). The prefix sums are gathered ONCE,
    at the window's ``L + 1`` row pointers, and differenced; the ``L``
    sums are written into zeros of the node space at ``start`` (a
    ``dynamic_update_slice``: one contiguous copy, no scatter). Any window
    that covers the rows with an edge is exact — a row outside it has
    degree 0 and sums to 0 — so the window's length may follow the bucket
    lattice and its start be clamped to the node space. Without a span the
    window is the whole ``rp`` at 0: the same code."""
    t = jnp.take(w, jnp.clip(ci, 0).astype(jnp.int64))
    with jax.named_scope("scan"):
        ps = jnp.concatenate([jnp.zeros(1, t.dtype), jnp.cumsum(t)])
    window, start = (rp, 0) if span is None else span
    b = jnp.take(ps, window.astype(jnp.int64))
    return lax.dynamic_update_slice(
        jnp.zeros(w.shape[0], ps.dtype), b[1:] - b[:-1], (start,)
    )


# edge lanes a step of ``_edge_sum`` gathers (my chip runs, PR 27, 2**26
# lanes of which 40M real: 2**16 514 ms, 2**18 288 ms, 2**20 293 ms, 2**22
# 301 ms, against 578 ms for the whole array in one gather)
_EDGE_CHUNK = 1 << 18


def _lanes_sum(ci, w):
    """64-bit sum of ``w[ci[e]]`` over these lanes, the pad tail (``ci`` =
    -1) counting 0. The gather runs at ``w``'s own width with the 32-bit
    index ``col_idx`` is stored in (a 64-bit lane costs the chip three
    times a 32-bit one)."""
    t = jnp.take(w, jnp.clip(ci, 0), mode="clip")
    return jnp.sum(jnp.where(ci >= 0, t, jnp.zeros((), w.dtype)), dtype=jnp.int64)


def _edge_sum(ci, w, stop):
    """sum over the real edges of ``w[ci[e]]`` = the sum over every node of
    (A w)[n]: one gather and one reduction, no prefix sums. ``stop`` is the
    number of lanes that hold real edges (traced; every lane from there on
    is pad): the lanes are walked in steps of ``_EDGE_CHUNK`` and the walk
    ends with the real edges, so a bucket's pad tail costs nothing."""
    lanes = ci.shape[0]
    steps = lanes // _EDGE_CHUNK
    with jax.named_scope("reduce"):
        if steps < 2:
            return _lanes_sum(ci, w)

        def more(state):
            return (state[0] < steps) & (state[0] * _EDGE_CHUNK < stop)

        def step(state):
            i, acc = state
            chunk = lax.dynamic_slice(ci, (i * _EDGE_CHUNK,), (_EDGE_CHUNK,))
            return i + 1, acc + _lanes_sum(chunk, w)

        # the first step is taken outright: its sum is the carry's type
        # (inside a shard_map, one that varies over the mesh)
        first = _lanes_sum(ci[:_EDGE_CHUNK], w)
        _, total = lax.while_loop(more, step, (jnp.int32(1), first))
        if lanes % _EDGE_CHUNK:
            total = total + _lanes_sum(ci[steps * _EDGE_CHUNK:], w)
        return total


def _csr_edge_sum(rp, ci, w):
    """``_edge_sum`` over one device's whole edge array: ``rp[-1]`` real
    edges lead it."""
    return _edge_sum(ci, w, rp[-1])


def _sharded_spmv(mesh, axis: str):
    """SpMV over a row-sharded edge array as an EXPLICIT shard_map program:
    per shard a local cumsum of its contiguous edge range, per-node partial
    sums via row_ptr boundaries clipped into the shard, combined with one
    ``psum`` over ICI — the distributed form of ``_csr_spmv`` (SURVEY §2.3's
    shuffle-reduce replacement) and the same algebra: ONE gather of the
    shard's prefix sums, at the window's row pointers clipped into the
    shard, and a difference; the ``psum`` carries the window's ``L`` sums
    and they are written into zeros of the node space at ``start``
    (``span`` as ``_csr_spmv`` takes it; none: the whole ``rp`` at 0).
    Explicit because GSPMD's partitioning of a globally-sharded cumsum
    degenerates (observed: a 400k-edge partitioned scan compiled to a
    ~100s program on the 8-CPU mesh; the shard_map form runs in
    milliseconds). Pad edges (``ci`` = -1) contribute zero."""
    from ...parallel.mesh import shard_map
    from jax.sharding import PartitionSpec as P

    def kernel(window, start, ci_shard, w_r):
        size = ci_shard.shape[0]
        t = jnp.where(
            ci_shard >= 0,
            jnp.take(w_r, jnp.clip(ci_shard, 0).astype(jnp.int64)),
            jnp.zeros((), w_r.dtype),
        )
        with jax.named_scope("scan"):
            ps = jnp.concatenate([jnp.zeros(1, t.dtype), jnp.cumsum(t)])
        lo = lax.axis_index(axis).astype(jnp.int64) * size
        b = jnp.take(ps, jnp.clip(window.astype(jnp.int64) - lo, 0, size))
        sums = lax.psum(b[1:] - b[:-1], axis)
        return lax.dynamic_update_slice(
            jnp.zeros(w_r.shape[0], sums.dtype), sums, (start,)
        )

    def spmv(rp, ci, w, span=None):
        window, start = (rp, 0) if span is None else span
        return shard_map(
            kernel, mesh, in_specs=(P(), P(), P(axis), P()), out_specs=P()
        )(window, jnp.asarray(start, jnp.int32), ci, w)

    return spmv


def _sharded_edge_sum(mesh, axis: str):
    """``_csr_edge_sum`` over a row-sharded edge array: per shard the sum
    of its own real lanes, then a ``psum`` of ONE scalar where the sharded
    SpMV sums a vector of ``num_nodes``."""
    from ...parallel.mesh import shard_map
    from jax.sharding import PartitionSpec as P

    def kernel(rp_r, ci_shard, w_r):
        first = lax.axis_index(axis).astype(jnp.int64) * ci_shard.shape[0]
        return lax.psum(_edge_sum(ci_shard, w_r, rp_r[-1] - first), axis)

    def edge_sum(rp, ci, w):
        return shard_map(
            kernel, mesh, in_specs=(P(), P(axis), P()), out_specs=P()
        )(rp, ci, w)

    return edge_sum


def _degrees(rp):
    """Per-node edge count of one CSR orientation, 32-bit: differences of
    the int32 ``row_ptr``, each at most ``rp[-1]``, so they fit."""
    return rp[1:] - rp[:-1]


def hop_parts(hop):
    """A hop's eight parts, ``(rp_a, ci_a, rp_b, ci_b, loop_cnt, mask,
    span_a, span_b)``; one built without its CSRs' row spans (six parts)
    reads None for both."""
    return tuple(hop) + (None,) * (8 - len(hop))


def _chain_body(dev_ids, ids, valid, hops, num_nodes: int, whole: bool,
                spmv, edge_sum):
    """Shared traced body of the fused count chain (see
    ``path_count_chain``); ``spmv`` / ``edge_sum`` are the single-device or
    the sharded forms. Each hop's operations carry a ``hop<i>`` scope
    (``i`` counts in the order executed; metadata only), so a kept device
    trace tells the hops of one program apart.

    ``w`` is ``None`` while it is the constant 1 on every node, and 32-bit
    only as one orientation's degrees, which are proven to fit; every sum
    is 64-bit."""
    executed = tuple(reversed(hops))
    forms = chain_forms([h[5] is not None for h in executed], whole)
    w = None
    for i, (hop, form) in enumerate(zip(executed, forms)):
        rp_a, ci_a, rp_b, ci_b, loop_cnt, mask, span_a, span_b = hop_parts(hop)
        with jax.named_scope(f"hop{i}"):
            if form == "degree":
                w = _degrees(rp_a)
                if rp_b is not None:
                    w = w.astype(jnp.int64) + _degrees(rp_b) - loop_cnt
                continue
            if mask is not None:  # far-label filter of this hop
                w = (
                    mask.astype(jnp.int32)
                    if w is None
                    else jnp.where(mask, w, jnp.zeros((), w.dtype))
                )
            if form == "reduce":
                total = edge_sum(rp_a, ci_a, w)
                if rp_b is not None:
                    total = total + edge_sum(rp_b, ci_b, w) - jnp.sum(
                        loop_cnt * w, dtype=jnp.int64
                    )
                return total
            w = w.astype(jnp.int64)
            nw = spmv(rp_a, ci_a, w, span_a)
            if rp_b is not None:
                nw = nw + spmv(rp_b, ci_b, w, span_b) - loop_cnt * w
            w = nw
    with jax.named_scope("frontier"):
        if whole:  # every node once: the plain sum (a pad node's w is 0)
            return jnp.sum(w, dtype=jnp.int64)
        # one completion-count gather per input row
        pos = jnp.clip(jnp.searchsorted(dev_ids, ids), 0, num_nodes - 1)
        present = jnp.take(dev_ids, pos) == ids
        if valid is not None:
            present = present & valid
        return jnp.sum(
            jnp.where(present, jnp.take(w, pos), 0), dtype=jnp.int64
        )


@partial(jax.jit, static_argnames=("num_nodes", "whole"))
def path_count_chain(dev_ids, ids, valid, hops, num_nodes: int,
                     whole: bool = False):
    """Total path count of a typed expand chain WITHOUT materializing any
    intermediate row set — ONE program replacing the whole 2k-join cascade.

    Evaluated RIGHT-TO-LEFT: ``w[n]`` = number of chain completions
    starting at node n; far-label filters multiply ``w`` by a node mask;
    the base frontier multiplicities collapse to one gather+sum over the
    input id column. A hop costs an edge pass only where the algebra needs
    one and a prefix scan only where per-node sums of non-constant weights
    are needed (``chain_forms``). ``whole``: the frontier holds every node
    of the graph exactly once (a host fact, ``GraphIndex.scan_is_whole``);
    ``dev_ids`` / ``ids`` / ``valid`` are then not read and may be None.

    ``hops`` (in pattern order, the hop next to the frontier first; the
    LAST is executed first): per hop a tuple
    ``(rp_a, ci_a, rp_b, ci_b, loop_cnt, mask, span_a, span_b)`` —
    fwd: (rp_fwd, ci_fwd, None, None, None, mask, span_fwd, None);
    bwd: (rp_rev, ci_rev, None, None, None, mask, span_rev, None);
    und: both orientations + per-node self-loop counts (primary half counts
    loops once, the opposite half excludes them — subtracting loop_cnt*w
    reproduces exactly the two CsrExpandOp halves). ``span_*`` is the
    window of rows that orientation's CSR holds (``_csr_spmv``; a ``scan``
    hop alone reads it); a hop of six parts has none and its scan reads
    every row pointer."""
    return _chain_body(
        dev_ids, ids, valid, hops, num_nodes, whole, _csr_spmv, _csr_edge_sum
    )


_MESH_CHAIN_CACHE: Dict[Any, Any] = {}


def path_count_chain_on_mesh(mesh, axis: str):
    """Mesh-active variant of ``path_count_chain``: same chain body with
    the shard_map SpMV and edge sum. Jitted once per mesh (cached)."""
    got = _MESH_CHAIN_CACHE.get((mesh, axis))
    if got is not None:
        return got
    spmv = _sharded_spmv(mesh, axis)
    edge_sum = _sharded_edge_sum(mesh, axis)

    @partial(jax.jit, static_argnames=("num_nodes", "whole"))
    def run(dev_ids, ids, valid, hops, num_nodes: int, whole: bool = False):
        return _chain_body(
            dev_ids, ids, valid, hops, num_nodes, whole, spmv, edge_sum
        )

    run = _obs_trace.program(run)
    _MESH_CHAIN_CACHE[(mesh, axis)] = run
    return run


# ---------------------------------------------------------------------------
# count over a pattern that is a TREE of expands: one multiplicity per node
# from the leaves to the root, vectors that meet in a node multiply. The
# chain above is its case without a branch.
#
# ``tree`` (static) is the root's children; a child is ``(k, optional,
# children)`` with ``k`` the hop's place in ``hops`` — a hop as
# ``path_count_chain`` takes it, its CSR's rows the node nearer the root,
# its mask the far node's labels.
# ---------------------------------------------------------------------------


def tree_forms(tree, masked: Sequence[bool], whole: bool) -> Tuple[str, ...]:
    """The form each hop of a tree count takes, by the hop's place in
    ``hops`` — the three ``chain_forms`` describes, and the one rule for
    both: ``degree`` where everything beyond the hop weighs the constant 1
    (a leaf without a mask), ``reduce`` for the one required hop under a
    root that holds every node once, ``scan`` otherwise."""
    forms: Dict[int, str] = {}

    def visit(children, at_root: bool):
        for k, optional, below in children:
            if not below and not masked[k]:
                forms[k] = "degree"
            elif at_root and whole and len(children) == 1 and not optional:
                forms[k] = "reduce"
            else:
                forms[k] = "scan"
            visit(below, False)

    visit(tree, True)
    return tuple(forms[k] for k in sorted(forms))


def _tree_branch(child, hops, forms):
    """int64[num_nodes] (32-bit for a plain degree): per node of the hop's
    near end, the matches of everything beyond the hop — 1 at least where
    the hop is OPTIONAL."""
    k, optional, below = child
    rp_a, ci_a, rp_b, ci_b, loop_cnt, mask, span_a, span_b = hop_parts(hops[k])
    with jax.named_scope(f"hop{k}"):
        if forms[k] == "degree":
            got = _degrees(rp_a)
            if rp_b is not None:
                got = got.astype(jnp.int64) + _degrees(rp_b) - loop_cnt
        else:
            w = _tree_node_weight(below, mask, hops, forms).astype(jnp.int64)
            got = _csr_spmv(rp_a, ci_a, w, span_a)
            if rp_b is not None:
                got = got + _csr_spmv(rp_b, ci_b, w, span_b) - loop_cnt * w
        return jnp.maximum(got, 1) if optional else got


def _tree_node_weight(children, mask, hops, forms):
    """The branches that meet in a node multiplied, under the node's mask;
    None while that is the constant 1."""
    w = None
    for child in children:
        branch = _tree_branch(child, hops, forms).astype(jnp.int64)
        w = branch if w is None else w * branch
    if mask is not None:
        w = (
            mask.astype(jnp.int32)
            if w is None
            else jnp.where(mask, w, jnp.zeros((), w.dtype))
        )
    return w


@partial(jax.jit, static_argnames=("tree", "whole"))
def tree_count(root_weight, live_nodes, hops, tree, whole: bool, rows=None):
    """count(*) of a pattern that is a tree of expands WITHOUT a row of it:
    ONE program. ``root_weight`` is what a node weighs as the root — its
    label mask or the number of input rows it holds; None (``whole``): every
    node once. ``live_nodes`` (traced) is the number of real nodes: a bucket's
    pad node has no edge, but an OPTIONAL branch makes it weigh 1. ``rows``
    (traced; with a ``root_weight`` of input rows) is how many input rows
    there are: one whose root is null, or no node of this graph, is in no
    node's weight — a required branch drops it, and where every branch at
    the root is OPTIONAL it stays once, all nulls."""
    forms = tree_forms(tree, [h[5] is not None for h in hops], whole)
    if "reduce" in forms:
        (k, _, below), = tree
        rp_a, ci_a, rp_b, ci_b, loop_cnt, mask = hops[k][:6]
        with jax.named_scope(f"hop{k}"):
            w = _tree_node_weight(below, mask, hops, forms)
            total = _csr_edge_sum(rp_a, ci_a, w)
            if rp_b is not None:
                total = total + _csr_edge_sum(rp_b, ci_b, w) - jnp.sum(
                    loop_cnt * w, dtype=jnp.int64
                )
            return total
    w = _tree_node_weight(tree, None, hops, forms)
    with jax.named_scope("frontier"):
        live = jnp.arange(w.shape[0], dtype=jnp.int32) < live_nodes
        w = jnp.where(live, w, jnp.zeros((), w.dtype))
        if root_weight is not None:
            w = w * root_weight.astype(jnp.int64)
        total = jnp.sum(w, dtype=jnp.int64)
        if rows is not None and all(optional for _, optional, _ in tree):
            total = total + rows - jnp.sum(root_weight, dtype=jnp.int64)
        return total


# ---------------------------------------------------------------------------
# graph algorithms of the procedures (``backend/tpu/procedures.py``): each a
# fixed point reached inside ONE program, the test on the device. A
# relationship type is undirected there, so each reads both CSR orientations
# of the type, ``orients``: a tuple of ``(rp, ci)``, one per orientation that
# holds an edge. Both end with the value of every input row (``ids``
# searched among ``dev_ids``, as a count chain's frontier is) and how many
# steps ran.
# ---------------------------------------------------------------------------


def _rows_of(dev_ids, ids, valid, values):
    """``values`` (per node position) at each input row, and whether the
    row is a node of the graph."""
    n = dev_ids.shape[0]
    pos = jnp.clip(jnp.searchsorted(dev_ids, ids), 0, n - 1)
    present = jnp.take(dev_ids, pos) == ids
    if valid is not None:
        present = present & valid
    return jnp.take(values, pos), present


# queued lanes one push step goes over, and rows: a step holds up to one row
# for every two lanes, and only rows with a lane in its orientation are
# queued, so a step is short of lanes only where rows of one lane follow one
# another. Every step has this one width: a smaller step costs more a lane,
# and how many of them a traversal needs follows the source.
PUSH_LANES = 1 << 19
_PUSH_LANES_PER_ROW = 2


def _per_lane(excl, values, lanes: int):
    """Each row's value on each of its lanes, the row's first lane at step
    position ``excl``: without a search, each row's first lane adds the
    change of the value from the row before (rows without a lane add a
    change that the next row takes back), and a cumulative sum carries it
    over the row's lanes."""
    change = values - jnp.concatenate([jnp.zeros(1, values.dtype), values[:-1]])
    marks = jnp.zeros(lanes, values.dtype).at[excl].add(change, mode="drop")
    return jnp.cumsum(marks)


def _queue_lanes(rp, ci, queue, k, a, off, lanes: int):
    """The next ``lanes`` lanes of the queued rows of one CSR orientation.
    The rows' lanes, in ``queue`` order (its ``k`` node positions), are one
    sequence; ``(a, off)`` is where this step starts in it: the ``off``-th
    lane of the row ``queue[a]``. Returns (a, off where the next step
    starts, the step's rows, the step position of each row's first lane,
    the far node at each position, whether the position holds a lane, the
    lanes taken)."""
    rows = lanes // _PUSH_LANES_PER_ROW
    q = lax.dynamic_slice(queue, (a,), (rows,))
    t = jnp.arange(rows, dtype=jnp.int32)
    first = jnp.where(t == 0, off, 0)
    base = jnp.take(rp, q, mode="clip") + first
    deg = jnp.where(a + t < k, jnp.take(rp, q + 1, mode="clip") - base, 0)
    incl = jnp.cumsum(deg)
    excl = incl - deg
    took = jnp.minimum(incl[-1], lanes)
    pos = jnp.arange(lanes, dtype=base.dtype)
    lane = pos + _per_lane(excl, base - excl, lanes)  # the CSR lane read
    far = jnp.take(ci, jnp.clip(lane, 0, ci.shape[0] - 1))
    done = jnp.sum(incl <= took, dtype=jnp.int32)  # whole rows: a prefix
    at = jnp.minimum(done, rows - 1)
    off = jnp.where(done < rows, took - excl[at] + first[at], 0)
    return a + done, off, q, excl, far, pos < took, took


def _frontier_queue(frontier):
    """The positions of the frontier's nodes first, ascending, and how many
    there are: one sort of the positions keyed past the node count where
    the node is off the frontier."""
    n = frontier.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    order = lax.sort(jnp.where(frontier, pos, pos + n))
    return jnp.where(order < n, order, 0), jnp.sum(frontier, dtype=jnp.int32)


def _orientation_steps(orients, n: int, step: int):
    """Per orientation: (rp, ci, every node's degree, the lanes a push step
    of it takes: ``step``, fewer where the orientation holds fewer)."""
    return [
        (rp, ci, rp[1:n + 1] - rp[:n],
         min(step, 1 << max(int(ci.shape[0]) - 1, 1).bit_length()))
        for rp, ci in orients
    ]


def _row_queue(rows, deg, lanes: int):
    """The queue of the nodes ``rows`` holds that have a lane
    (``_frontier_queue``), padded for a step's slice, and their number."""
    queue, k = _frontier_queue(rows & (deg > 0))
    pad = jnp.zeros(lanes // _PUSH_LANES_PER_ROW, jnp.int32)
    return jnp.concatenate([queue, pad]), k


@partial(jax.jit, static_argnames=("step",))
def bfs_levels(orients, source, dev_ids, ids, valid, step: int = PUSH_LANES):
    """Level-synchronous BFS from the node at position ``source`` (traced:
    a new source compiles nothing), top-down: a level pushes the lanes of
    its frontier's rows in each orientation (``_queue_lanes``, ``step``
    lanes at a time, fewer where the orientation holds fewer; each lane's
    far node takes the depth ``level + 1`` unless it has a smaller one, a
    scatter-min), so the whole traversal reads each lane of the source's
    component once, whatever the source and the number of levels. Stops at
    the first level that reaches no new node. Returns (int64 depth per
    input row, valid: reached, steps, the lanes the push steps went
    over)."""
    n = dev_ids.shape[0]
    unseen = jnp.iinfo(jnp.int32).max
    depth = lax.dynamic_update_slice(
        jnp.full(n, unseen, jnp.int32), jnp.zeros(1, jnp.int32), (source,)
    )

    orientations = _orientation_steps(orients, n, step)

    def level_step(state):
        level, depth, _, width = state
        frontier = depth == level
        for rp, ci, deg, lanes in orientations:
            queue, k = _row_queue(frontier, deg, lanes)

            def push(state, rp=rp, ci=ci, queue=queue, k=k, lanes=lanes):
                a, off, depth, left, width = state
                a, off, _, _, far, live, took = _queue_lanes(
                    rp, ci, queue, k, a, off, lanes
                )
                depth = depth.at[jnp.where(live, far, n)].min(
                    level + 1, mode="drop"
                )
                return a, off, depth, left - took, width + lanes

            left = jnp.sum(jnp.where(frontier, deg, 0), dtype=jnp.int32)
            _, _, depth, _, width = lax.while_loop(
                lambda s: s[3] > 0, push,
                (jnp.int32(0), jnp.int32(0), depth, left, width),
            )
        return level + 1, depth, jnp.any(depth == level + 1), width

    steps, depth, _, width = lax.while_loop(
        lambda state: state[2], level_step,
        (jnp.int32(0), depth, jnp.bool_(True), jnp.int64(0)),
    )
    at, present = _rows_of(dev_ids, ids, valid, depth)
    reached = at < unseen
    return jnp.where(reached, at, -1).astype(jnp.int64), present & reached, steps, width


# Afforest's neighbour rounds: the lanes at the head of every row, in each
# orientation, that WCC links before it looks for the largest component
WCC_SAMPLED_LANES = 2
# words in a row of ``_as_rows``: on a TPU v5e, 2^22 random int32 reads of a
# 2^22 vector took 23 ms as row gathers and 37 as element gathers
_ROW_WORDS = 8


def _as_rows(x):
    """``x`` as rows of ``_ROW_WORDS`` elements (the last padded), for
    ``_at``."""
    return jnp.pad(x, (0, -x.shape[0] % _ROW_WORDS)).reshape(-1, _ROW_WORDS)


def _at(rows, i):
    """``x[i]`` (``i`` >= 0) of the ``x`` that ``_as_rows`` laid out: the
    row that holds it is gathered, and its element picked out of the
    row."""
    got = jnp.take(rows, i // _ROW_WORDS, axis=0, mode="clip")
    word = jnp.arange(_ROW_WORDS, dtype=i.dtype) == (i % _ROW_WORDS)[:, None]
    return jnp.sum(jnp.where(word, got, 0), axis=1, dtype=rows.dtype)


def _hook(new, lu, lv, live=True):
    """``new`` with the larger of the roots ``lu`` / ``lv`` of each
    ``live`` pair hung under the smaller (a scatter-min; a pair whose roots
    agree writes a root onto itself)."""
    hi = jnp.where(live, jnp.maximum(lu, lv), new.shape[0])
    return new.at[hi].min(jnp.minimum(lu, lv), mode="drop")


def _most_frequent(label):
    """The value ``label`` holds most often, the least among equals: one
    sort, and the longest run of equal values in it."""
    ordered = lax.sort(label)
    pos = jnp.arange(label.shape[0], dtype=jnp.int32)
    head = jnp.concatenate([jnp.ones(1, bool), ordered[1:] != ordered[:-1]])
    run = pos - lax.cummax(jnp.where(head, pos, 0))
    return ordered[jnp.argmax(run)]


def _compress(label):
    """``label`` with every node pointing at its tree's root:
    ``label[label]`` until nothing moves."""

    def jump(state):
        label, _ = state
        up = _at(_as_rows(label), label)
        return up, jnp.any(up != label)

    return lax.while_loop(lambda s: s[1], jump, (label, jnp.bool_(True)))[0]


def _hooking(round_, label, go):
    """Rounds of ``round_`` (a compressed label -> the label with its hooks,
    and whether a root moved) until one moves no root (none where ``go`` is
    false), each round's forest compressed. Returns (rounds, label)."""

    def step(state):
        rounds, label, _ = state
        new, moved = round_(label)
        return rounds + 1, lax.cond(moved, _compress, lambda x: x, new), moved

    rounds, label, _ = lax.while_loop(
        lambda s: s[2], step, (jnp.int32(0), label, go)
    )
    return rounds, label


@partial(jax.jit, static_argnames=("step",))
def wcc_labels(orients, dev_ids, ids, valid, step: int = PUSH_LANES):
    """Weakly connected components by hooking over a sample of the lanes
    first, Afforest's (Sutton, Ben-Nun, Barak, IPDPS 2018).

    A node points at a smaller position of its component, or is a root;
    a root is the smallest position of its tree (a hook hangs the larger
    root under the smaller, ``_hook``; every round ends with the forest
    compressed, ``_compress``). (1) Each node samples the far nodes of the
    first ``WCC_SAMPLED_LANES`` lanes of its row in each orientation (one
    gather of each from ``ci``), points at the least of them and itself,
    and the sampled pairs are hooked until no root moves. (2) The most
    frequent root is the largest sampled component. (3) The
    rows of every node outside it that have a lane are queued
    (``_frontier_queue``) and all their lanes hooked, in push steps
    (``_queue_lanes``, ``step`` lanes at a time), until no root moves.

    Exact whatever the sample: an edge no step read lies in the row of its
    first node in one orientation and of its second in the other, neither
    queued, so both nodes were already in the largest component's tree; and
    the choice of that component only decides how much (3) reads. A
    component's label is its smallest position, the node of the smallest
    id. Returns (int64 id of that node per input row, valid, the rounds of
    (1) and of (3), the lanes they read: the sampled lanes once and the
    queued rows' lanes once a round of (3), and how many rows were
    queued)."""
    n = dev_ids.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    orientations = _orientation_steps(orients, n, step)
    sampled, sampled_lanes = [], jnp.int64(0)
    for rp, ci, deg, _ in orientations:
        lanes = _as_rows(ci)
        for r in range(WCC_SAMPLED_LANES):
            far = _at(lanes, jnp.clip(rp[:n] + r, 0, ci.shape[0] - 1))
            sampled.append(jnp.where(deg > r, far, pos))
            sampled_lanes = sampled_lanes + jnp.sum(deg > r, dtype=jnp.int64)

    def sampled_round(label):
        rows = _as_rows(label)
        far = [_at(rows, v) for v in sampled]
        moved = functools.reduce(
            jnp.logical_or, [jnp.any(lv != label) for lv in far], jnp.bool_(False)
        )

        def hook(new):
            for lv in far:
                new = _hook(new, label, lv)
            return new

        return lax.cond(moved, hook, lambda x: x, label), moved

    # each node's first hook, elementwise: under the least far node it samples
    first = functools.reduce(jnp.minimum, sampled, pos)
    rounds1, label = _hooking(sampled_round, _compress(first), jnp.bool_(True))
    outside = label != _most_frequent(label)
    pushes, queued, queued_lanes = [], jnp.int32(0), jnp.int64(0)
    for rp, ci, deg, lanes in orientations:
        queue, k = _row_queue(outside, deg, lanes)
        total = jnp.sum(jnp.where(outside, deg, 0), dtype=jnp.int32)
        pushes.append((rp, ci, queue, k, lanes, total))
        queued, queued_lanes = queued + k, queued_lanes + total

    def outside_round(label):
        new, rows = label, _as_rows(label)
        for rp, ci, queue, k, lanes, total in pushes:

            def push(state, rp=rp, ci=ci, queue=queue, k=k, lanes=lanes):
                a, off, new, left = state
                a, off, q, excl, far, live, took = _queue_lanes(
                    rp, ci, queue, k, a, off, lanes
                )
                lu = _per_lane(excl, _at(rows, q), lanes)
                lv = _at(rows, jnp.clip(far, 0))
                return a, off, _hook(new, lu, lv, live), left - took

            _, _, new, _ = lax.while_loop(
                lambda s: s[3] > 0, push,
                (jnp.int32(0), jnp.int32(0), new, total),
            )
        return new, jnp.any(new != label)

    rounds3, label = _hooking(outside_round, label, queued > 0)
    at, present = _rows_of(dev_ids, ids, valid, label)
    lanes = sampled_lanes + queued_lanes * rounds3
    return (jnp.take(dev_ids, at), present, jnp.stack([rounds1, rounds3]),
            lanes, queued)


# ---------------------------------------------------------------------------
# count chain under a constraint between two of its nodes, two hops apart:
#   count = sum over wedges (a -> b -> c) of left[a] * right[c] * [constraint]
# ``left`` / ``right`` are the chain's per-node weights on either side of the
# pair (``chain_node_weights``); the wedges with a = c are counted from one
# cached count per edge lane (``two_cycle_sum``), the wedges closed by an
# edge between a and c by intersecting two bit rows a closing pair
# (``wedge_close_sum``). No row of the chain is built.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("num_nodes",))
def chain_node_weights(start, steps, num_nodes: int):
    """int64[num_nodes]: ``start`` (None: 1 on every node) carried through
    ``steps``, each ``(rp, ci, weight)`` or ``(rp, ci, weight, span)`` — the
    sum over a node's CSR edges of the far ends' weights (over the rows
    the CSR holds where its ``span`` says which, ``_csr_spmv``), times the
    node's own: a label mask, a number per node, or None (1)."""
    w = jnp.ones(num_nodes, jnp.int64) if start is None else start.astype(jnp.int64)
    for rp, ci, weight, *span in steps:
        w = _csr_spmv(rp, ci, w, *span)
        if weight is not None:
            w = w * weight.astype(jnp.int64)
    return w


def _real_lanes(rp, ci):
    """bool per edge lane of a CSR: it holds an edge (``rp[-1]`` of them
    lead the array; the rest is the bucket's pad)."""
    return jnp.arange(ci.shape[0], dtype=jnp.int32) < rp[-1]


@jax.jit
def csr_lane_rows(rp, ci):
    """int32 per edge lane of a CSR: the row (node) the lane belongs to; pad
    lanes past ``rp[-1]`` read the last node and are never live."""
    lanes = jnp.arange(ci.shape[0], dtype=jnp.int32)
    rows = jnp.searchsorted(rp[1:], lanes, side="right")
    return jnp.minimum(rows, rp.shape[0] - 2).astype(jnp.int32)


@partial(jax.jit, static_argnames=("num_nodes",))
def csr_back_counts(rp, ci, rows, keys2, num_nodes: int):
    """int32 per lane ``a -> b`` of one CSR: how many lanes ``b -> a`` the
    CSR whose sorted ``row * N + col`` keys are ``keys2`` holds (parallel
    edges each count). Pad lanes read 0."""
    live = _real_lanes(rp, ci)
    q = jnp.clip(ci, 0).astype(jnp.int64) * num_nodes + rows.astype(jnp.int64)
    return range_count(keys2, q, live)[1].astype(jnp.int32)


@jax.jit
def two_cycle_sum(rp, ci, rows, back, mid_mask, w):
    """sum over the lanes ``a -> b`` of ``w[a] * [mid_mask[b]] * back``: the
    weighted number of wedges ``a -> b -> a`` (``back`` from
    ``csr_back_counts``). One 64-bit gather a lane."""
    live = _real_lanes(rp, ci)
    if mid_mask is not None:
        live = live & jnp.take(mid_mask, jnp.clip(ci, 0))
    t = jnp.take(w, rows) * back.astype(jnp.int64)
    return jnp.sum(jnp.where(live, t, 0), dtype=jnp.int64)


def _pair_runs(rp, ci, rows):
    """Per lane of a CSR: (it holds an edge; it is the first lane of its
    (row, col) pair; its place, from 1, among the pair's lanes). The lanes
    of a pair are neighbours: the CSR is sorted by (row, col)."""
    lanes = jnp.arange(ci.shape[0], dtype=jnp.int32)
    live = _real_lanes(rp, ci)
    prev_same = jnp.concatenate([
        jnp.zeros(1, bool), (ci[1:] == ci[:-1]) & (rows[1:] == rows[:-1])
    ])
    first = live & ~prev_same
    return live, first, lanes - lax.cummax(jnp.where(first, lanes, 0)) + 1


@jax.jit
def csr_longest_run(rp, ci, rows):
    """The most lanes any (row, col) pair of a CSR has."""
    live, _, place = _pair_runs(rp, ci, rows)
    return jnp.max(jnp.where(live, place, 0), initial=0)


@partial(jax.jit, static_argnames=("size", "words", "planes"))
def bit_adjacency(rp, ci, rows, row_rank, col_rank, size: int, words: int, planes: int):
    """``planes`` of uint32[size, words]: the CSR's (row, col) pairs as bit
    rows — row ``row_rank[row]``, bit ``col_rank[col]`` (word ``>> 5``, bit
    ``& 31``; a rank of -1: the node is not among them). Plane ``j`` holds
    binary digit ``j`` of the pair's number of parallel lanes (the caller
    has seen that none needs more than ``planes`` digits), so every lane
    counts."""
    live, first, place = _pair_runs(rp, ci, rows)
    # a pair is written from its last lane, whose place is its lanes' number
    goes_on = jnp.concatenate([live[1:] & ~first[1:], jnp.zeros(1, bool)])
    r = jnp.take(row_rank, rows)
    c = jnp.take(col_rank, jnp.clip(ci, 0))
    keep = live & ~goes_on & (r >= 0) & (c >= 0)
    r = jnp.where(keep, r, size)  # dropped
    bit = jnp.uint32(1) << (c & 31).astype(jnp.uint32)
    # a pair is written once, so the adds into a word are of distinct bits
    return tuple(
        jnp.zeros((size, words), jnp.uint32).at[r, c >> 5].add(
            jnp.where((place >> j) & 1 == 1, bit, jnp.uint32(0)), mode="drop"
        )
        for j in range(planes)
    )


def _lane_window(length: int, lo, hi, width: int):
    """``width`` consecutive lanes of an array of ``length`` that hold
    [lo, hi) (``hi - lo <= width <= length``), and which of them are in it:
    the slice may not run off the array, so it starts early where it
    must."""
    start = jnp.clip(lo, 0, length - width)
    lanes = start + jnp.arange(width, dtype=jnp.int32)
    return start, (lanes >= lo) & (lanes < hi)


@jax.jit
def closing_pair_rows(rp, ci, rows, row_rank, col_rank):
    """(int32, int32) per lane ``a -> c`` of a closing CSR: ``row_rank[a]``
    and ``col_rank[c]`` on the first lane of each (a, c) pair where both
    nodes have a rank — the two bit rows ``wedge_close_sum`` intersects for
    the pair — and -1 on every other lane (pad, a further parallel lane, a
    node without a row)."""
    _, first, _ = _pair_runs(rp, ci, rows)
    ra = jnp.take(row_rank, rows)
    kc = jnp.take(col_rank, jnp.clip(ci, 0))
    live = first & (ra >= 0) & (kc >= 0)
    return jnp.where(live, ra, -1), jnp.where(live, kc, -1)


def _weight_rows(w, nodes):
    """uint32[rows, 8]: the 64-bit weight ``w`` of each row's node as a row
    of words, low then high. This chip gathers a row of a table in 3 ns
    and an element of a vector in 15 (my chip run, PR 37, call M4: two
    64-bit weights a lane over 3.9M lanes 0.025 s as rows, 0.241 s as
    elements), so the closing pairs' weights are gathered as their bit rows
    are."""
    w = jnp.take(w, nodes).astype(jnp.uint64)
    words = jnp.stack([w.astype(jnp.uint32), (w >> 32).astype(jnp.uint32)], axis=1)
    return jnp.pad(words, ((0, 0), (0, 6)))


def _weights_at(table, at):
    """int64 per lane: the weights ``_weight_rows`` laid out, at rows ``at``."""
    got = jnp.take(table, at, axis=0, mode="clip")
    return got[:, 0].astype(jnp.int64) | (got[:, 1].astype(jnp.int64) << 32)


@partial(jax.jit, static_argnames=("chunk",))
def wedge_close_sum(
    b1, nodes1, b2t, nodes2, mid_mask, rp_c, ra_c, kc_c, left, right,
    chunk: int,
):
    """sum over the closing pairs (a, c) — each once, however many parallel
    closing lanes — of ``left[a] * right[c] *`` the number of wedges
    ``a -> b -> c`` (parallel lanes each count, ``mid_mask[b]`` holds).

    Per closing pair two bit rows are intersected: ``b1[j][ra_c]``, a's
    first-hop neighbours, and ``b2t[k][kc_c]``, the nodes with a
    second-hop lane into c (``bit_adjacency``: plane ``j`` = digit ``j`` of
    a pair's parallel lanes; ``nodes1`` / ``nodes2``: the node of each row,
    and ``nodes2`` the node of each bit of both; ``closing_pair_rows``: the
    pair's rows, -1 on a lane that is none), so

        wedges(a, c) = sum_jk popcount(b1[j][a] & b2t[k][c] & mid) << (j + k)

    The closing CSR's lanes are walked ``chunk`` at a time, as far as its
    real lanes go (``rp_c[-1]``); two gathered chunks of rows are all the
    program holds beside the bit rows."""
    words = b1[0].shape[1]
    length = ra_c.shape[0]
    if length == 0:  # no closing edge: no wedge is closed
        return jnp.zeros((), jnp.int64)
    real = rp_c[-1]
    mid = None
    if mid_mask is not None:  # packed as the bit rows are: bit r = nodes2[r]
        bits = jnp.pad(jnp.take(mid_mask, nodes2), (0, words * 32 - nodes2.shape[0]))
        mid = jnp.sum(
            bits.reshape(words, 32).astype(jnp.uint32)
            << jnp.arange(32, dtype=jnp.uint32),
            axis=1, dtype=jnp.uint32,
        )
    left, right = _weight_rows(left, nodes1), _weight_rows(right, nodes2)

    def body(i, acc):
        lo = i.astype(jnp.int32) * chunk
        start, live = _lane_window(length, lo, lo + chunk, chunk)
        ra = lax.dynamic_slice(ra_c, (start,), (chunk,))
        kc = lax.dynamic_slice(kc_c, (start,), (chunk,))
        live = live & (ra >= 0)  # a pad lane is no pair's first
        found = jnp.zeros(chunk, jnp.int64)
        for j, plane1 in enumerate(b1):
            from_a = jnp.take(plane1, ra, axis=0, mode="clip")
            if mid is not None:
                from_a = from_a & mid
            for k, plane2 in enumerate(b2t):
                with jax.named_scope("wedge_intersect"):
                    into_c = jnp.take(plane2, kc, axis=0, mode="clip")
                    both = lax.population_count(from_a & into_c)
                    found = found + (
                        jnp.sum(both, axis=1, dtype=jnp.int32).astype(jnp.int64)
                        << (j + k)
                    )
        weight = _weights_at(left, ra) * _weights_at(right, kc)
        return acc + jnp.sum(jnp.where(live, weight * found, 0), dtype=jnp.int64)

    return lax.fori_loop(0, -(-real // chunk), body, jnp.zeros((), jnp.int64))


# ---------------------------------------------------------------------------
# fused var-length expand: per-hop frontier materialize with edge-distinct
# (isomorphism) masks — SURVEY §5's frontier loop, engine-integrated
# ---------------------------------------------------------------------------


@jax.jit
def rel_rows_of_ids(sorted_ids, perm, q, valid):
    """Canonical rel-scan row per queried global relationship id, or -1
    when the id is not in the scan (or the query row is null). Binary
    search over the id-sorted permutation (``GraphIndex.rel_row_index``) —
    the id-space bridge for relationship-isomorphism forbid masks."""
    n = sorted_ids.shape[0]
    if n == 0:
        return jnp.full(q.shape, -1, jnp.int64)
    i = jnp.clip(jnp.searchsorted(sorted_ids, q), 0, n - 1)
    hit = jnp.take(sorted_ids, i) == q
    if valid is not None:
        hit = hit & valid
    return jnp.where(hit, jnp.take(perm, i), jnp.int64(-1))


@partial(jax.jit, static_argnames=("total",))
def varlen_hop(rp, ci, eo, pos, deg, row0, prev_edges, total: int, nvalid=None):
    """One hop of a var-length expansion. State per partial path: origin
    input row ``row0`` (None on the first hop — the expansion row IS the
    origin), current node ``pos``, and the edge ids walked so far
    (``prev_edges``). Paths that would reuse an edge get ``iso=False`` and
    are dead: they emit nothing and expand no further (their next-hop
    degrees are masked to zero), exactly the unrolled planner's
    ``id(step_i) <> id(step_j)`` filters. ``nvalid`` (traced, optional):
    true emission count when ``total`` is a BUCKETED static size — pad
    lanes are sanitized and come out ``iso=False`` (dead paths)."""
    row, edge = _expand_rows(jnp.take(rp, pos), deg, total)
    if nvalid is not None:
        live = _live_lanes(total, nvalid)
        row = jnp.where(live, row, 0)
        edge = jnp.where(live, edge, 0)
    nbr = jnp.take(ci, edge).astype(jnp.int64)
    orig = jnp.take(eo, edge)
    new_row0 = jnp.take(row0, row) if row0 is not None else row
    new_prev = tuple(jnp.take(pe, row) for pe in prev_edges)
    iso = jnp.ones(total, bool) if nvalid is None else _live_lanes(total, nvalid)
    for pe in new_prev:
        iso = iso & (orig != pe)
    return new_row0, nbr, orig, new_prev + (orig,), iso


@jax.jit
def varlen_emit(nbr, iso, row_map):
    """Emission at one path length: far-node scan row (-1 = target labels
    missing), surviving-row mask, surviving count."""
    far = jnp.take(row_map, nbr)
    keep = iso & (far >= 0)
    return far, keep, jnp.sum(keep)


@jax.jit
def varlen_zero(pos, present, row_map):
    """Length-0 emission: each input row whose source node is present and
    carries the target labels emits itself once (target = source)."""
    far = jnp.take(row_map, pos)
    keep = present & (far >= 0)
    return (
        jnp.arange(pos.shape[0], dtype=jnp.int64),
        far,
        keep,
        jnp.sum(keep),
    )


@jax.jit
def concat_rows(parts):
    """Concatenate per-level (row0, far) pairs into one output frame."""
    return (
        jnp.concatenate([p[0] for p in parts]),
        jnp.concatenate([p[1] for p in parts]),
    )


# ---------------------------------------------------------------------------
# fused distinct-endpoints count: scan -> expand^k -> DISTINCT a,c -> count
# ---------------------------------------------------------------------------

_KEY_SENTINEL = (1 << 62) - 1  # sorts after every valid endpoint key


@partial(jax.jit, static_argnames=("total",))
def distinct_hop_materialize(rp, ci, pos, deg, akey, mask, total: int, nvalid=None):
    """One middle hop of a distinct-endpoints chain: expand (pos, akey)
    into per-edge (akey', pos', present') keeping ONLY the base key and the
    current node position — no column assembly at all. ``mask``: far-label
    node mask or None. ``nvalid`` (traced, optional): true emission count
    when ``total`` is bucketed — pad lanes come out present'=False."""
    row, edge = _expand_rows(jnp.take(rp, pos), deg, total)
    if nvalid is not None:
        live = _live_lanes(total, nvalid)
        row = jnp.where(live, row, 0)
        edge = jnp.where(live, edge, 0)
    nbr = jnp.take(ci, edge).astype(jnp.int64)
    akey_out = jnp.take(akey, row)
    present = jnp.take(mask, nbr) if mask is not None else jnp.ones(total, bool)
    if nvalid is not None:
        present = present & live
    return akey_out, nbr, present


@partial(jax.jit, static_argnames=("total", "use_a", "use_c", "num_nodes"))
def distinct_pairs_count_final(
    rp, ci, pos, deg, akey, mask, total: int, use_a: bool, use_c: bool,
    num_nodes: int, nvalid=None,
):
    """Final hop fused with the distinct count: materialize the last
    expansion's (base key, far position) pairs, pack them into one int64
    key, values-only sort (NO argsort payload — ~5x cheaper on TPU), and
    count run boundaries. Masked-out rows (and bucket-pad lanes past the
    traced ``nvalid``) sort to a sentinel tail."""
    row, edge = _expand_rows(jnp.take(rp, pos), deg, total)
    if nvalid is not None:
        live = _live_lanes(total, nvalid)
        row = jnp.where(live, row, 0)
        edge = jnp.where(live, edge, 0)
    nbr = jnp.take(ci, edge).astype(jnp.int64)
    if use_a and use_c:
        key = jnp.take(akey, row) * num_nodes + nbr
    elif use_a:
        key = jnp.take(akey, row)
    else:
        key = nbr
    present = jnp.take(mask, nbr) if mask is not None else None
    if nvalid is not None:
        present = live if present is None else (present & live)
    if present is not None:
        key = jnp.where(present, key, _KEY_SENTINEL)
        valid_n = jnp.sum(present.astype(jnp.int64))
    else:
        valid_n = jnp.asarray(total, jnp.int64)
    s = jax.lax.sort(key)
    if total == 0:
        return jnp.asarray(0, jnp.int64)
    bounds = jnp.sum(
        ((s[1:] != s[:-1]) & (jnp.arange(1, total) < valid_n)).astype(jnp.int64)
    )
    return bounds + (valid_n > 0).astype(jnp.int64)


@partial(jax.jit, static_argnames=("total", "mask_idx"))
def unique_hop_materialize(
    rp, ci, eo, pos, deg, akey, mask, prevs, total: int, mask_idx: tuple,
    nvalid=None,
):
    """``distinct_hop_materialize`` carrying walked-edge scan rows for
    relationship uniqueness: expands into (akey', pos', edge', prevs',
    present'). ``mask_idx`` names the carried arrays the new edge must
    differ from; violating rows come out present'=False (their next-hop
    degrees zero out — the fused analog of the planner's per-step
    ``id(r_i) <> id(r_j)`` filters, same mechanism as ``varlen_hop``)."""
    row, edge = _expand_rows(jnp.take(rp, pos), deg, total)
    if nvalid is not None:
        live = _live_lanes(total, nvalid)
        row = jnp.where(live, row, 0)
        edge = jnp.where(live, edge, 0)
    nbr = jnp.take(ci, edge).astype(jnp.int64)
    orig = jnp.take(eo, edge)
    akey_out = jnp.take(akey, row)
    prevs_out = tuple(jnp.take(p, row) for p in prevs)
    present = jnp.take(mask, nbr) if mask is not None else jnp.ones(total, bool)
    if nvalid is not None:
        present = present & live
    for i in mask_idx:
        present = present & (orig != prevs_out[i])
    return akey_out, nbr, orig, prevs_out, present


@partial(jax.jit, static_argnames=("total", "mask_idx"))
def chain_count_final_unique(
    rp, ci, eo, pos, deg, mask, prevs, total: int, mask_idx: tuple,
    nvalid=None,
):
    """Final hop of a rel-unique chain count(*): materialize the last
    expansion's liveness only and sum it (the SpMV ``path_count_chain``
    cannot express per-path edge identity, so unique chains count via the
    walk)."""
    row, edge = _expand_rows(jnp.take(rp, pos), deg, total)
    if nvalid is not None:
        live = _live_lanes(total, nvalid)
        row = jnp.where(live, row, 0)
        edge = jnp.where(live, edge, 0)
    nbr = jnp.take(ci, edge).astype(jnp.int64)
    orig = jnp.take(eo, edge)
    ok = jnp.take(mask, nbr) if mask is not None else jnp.ones(total, bool)
    if nvalid is not None:
        ok = ok & live
    for i in mask_idx:
        ok = ok & (orig != jnp.take(prevs[i], row))
    return jnp.sum(ok.astype(jnp.int64))


@partial(
    jax.jit,
    static_argnames=("total", "use_a", "use_c", "num_nodes", "mask_idx"),
)
def distinct_pairs_count_final_unique(
    rp, ci, eo, pos, deg, akey, mask, prevs,
    total: int, use_a: bool, use_c: bool, num_nodes: int, mask_idx: tuple,
    nvalid=None,
):
    """``distinct_pairs_count_final`` with walked-edge uniqueness masks:
    rows whose final edge equals a carried chain edge sort to the sentinel
    tail (they are not paths under openCypher rel-isomorphism)."""
    row, edge = _expand_rows(jnp.take(rp, pos), deg, total)
    if nvalid is not None:
        live = _live_lanes(total, nvalid)
        row = jnp.where(live, row, 0)
        edge = jnp.where(live, edge, 0)
    nbr = jnp.take(ci, edge).astype(jnp.int64)
    orig = jnp.take(eo, edge)
    if use_a and use_c:
        key = jnp.take(akey, row) * num_nodes + nbr
    elif use_a:
        key = jnp.take(akey, row)
    else:
        key = nbr
    present = jnp.take(mask, nbr) if mask is not None else jnp.ones(total, bool)
    if nvalid is not None:
        present = present & live
    for i in mask_idx:
        present = present & (orig != jnp.take(prevs[i], row))
    key = jnp.where(present, key, _KEY_SENTINEL)
    valid_n = jnp.sum(present.astype(jnp.int64))
    s = jax.lax.sort(key)
    if total == 0:
        return jnp.asarray(0, jnp.int64)
    bounds = jnp.sum(
        ((s[1:] != s[:-1]) & (jnp.arange(1, total) < valid_n)).astype(jnp.int64)
    )
    return bounds + (valid_n > 0).astype(jnp.int64)


@partial(jax.jit, static_argnames=("kinds", "pack"))
def distinct_count_packed(datas, valids, extra_keys, kinds, pack):
    """Distinct-row count over packable all-integer equivalence keys: fold
    into one int64 key, values-only ``lax.sort``, count run boundaries —
    no argsort payload, no first-occurrence machinery."""
    keys = list(extra_keys) + _equivalence_keys_traced(datas, valids, kinds)
    acc = _pack_fold(keys, pack)
    n = acc.shape[0]
    if n == 0:
        return jnp.asarray(0, jnp.int64)
    s = jax.lax.sort(acc)
    return jnp.sum((s[1:] != s[:-1]).astype(jnp.int64)) + 1


@partial(jax.jit, static_argnames=("kinds", "pack"))
def equivalence_pack_keys(datas, valids, extra_keys, kinds, pack):
    """The per-row packed equivalence key of ``distinct_count_packed``
    WITHOUT the sort: row equality == Cypher equivalence. The sharded
    DISTINCT tier hash-repartitions these keys over the mesh so equal
    values meet on one shard (``parallel.shuffle.sharded_distinct_count``)
    instead of paying a global sort."""
    keys = list(extra_keys) + _equivalence_keys_traced(datas, valids, kinds)
    return _pack_fold(keys, pack)


# ---------------------------------------------------------------------------
# equivalence sort (distinct / group factorization)
# ---------------------------------------------------------------------------


def _equivalence_keys_traced(datas, valids, kinds):
    """Device key arrays whose row equality == Cypher equivalence: null
    payload canonicalized to 0 (outer joins leave arbitrary data under
    valid=False), NaN its own class (separate flag key), -0.0 == 0.0, and
    the null-class key skipped when the column has no nulls (halves the
    stable sorts on the hot id-distinct path). distinct/group ONLY — join
    keys implement ``=`` semantics instead (NaN never matches)."""
    keys = []
    for d, v, k in zip(datas, valids, kinds):
        if k == DUR:
            # one key per component: row equality == Duration.__eq__ (the
            # storage is normalized, so the triple is canonical)
            for j in range(3):
                cj = d[:, j]
                keys.append(cj if v is None else jnp.where(v, cj, 0))
            if v is not None:
                keys.append(~v)
            continue
        if k == F64:
            valid = v if v is not None else jnp.ones(d.shape[0], bool)
            nan = jnp.isnan(d) & valid
            d = jnp.where(valid & ~nan, d, 0.0)
            d = d + 0.0
            keys.append(nan)
        elif k == BOOL:
            d = d.astype(jnp.int8)
        if v is None:
            keys.append(d)
        else:
            keys.append(jnp.where(v, d, jnp.zeros((), d.dtype)))
            keys.append(~v)
    return keys


def _first_flags(keys, order):
    n = order.shape[0]
    diff = jnp.zeros(max(n - 1, 0), bool)
    for k in keys:
        ks = jnp.take(k, order)
        diff = diff | (ks[1:] != ks[:-1])
    return jnp.concatenate([jnp.ones(min(n, 1), bool), diff])


@partial(jax.jit, static_argnames=("kinds",))
def equivalence_minmax(datas, valids, extra_keys, kinds):
    """Per-key (min, max) over the built equivalence keys — host decides
    int-packing from one sync. Only called when every key is integral."""
    keys = list(extra_keys) + _equivalence_keys_traced(datas, valids, kinds)
    ints = [k.astype(jnp.int64) for k in keys]
    return (
        jnp.stack([k.min() for k in ints]),
        jnp.stack([k.max() for k in ints]),
    )


# ---------------------------------------------------------------------------
# MXU dense tier: path counting as blocked A @ A on the systolic array.
# The CSR walk streams gathers through the VPU; for graphs whose dense
# adjacency fits HBM, the same counts are ONE chain of bf16 matmuls with
# f32 accumulation — the shape the MXU was built for. Entries are exact
# small integers (multiplicities <= 256, checked at build), block row-sums
# round back to int64 before accumulating, so results are exact.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("block",))
def mxu_close_count(a1, a2, c, mult, mask_b, mask_c, block: int):
    """count(*) of (a)-[r1]->(b)-[r2]->(c'), (a)-[rc]->(c') as
    sum_a mult[a] * sum_c (A1 @ A2)[a, c] * C[a, c]: per row-block one
    (block, N) @ (N, N) matmul + one elementwise product with the closing
    adjacency. ``mult``: frontier multiplicity per source row (int64);
    masks: optional bf16 0/1 vectors folding far-label filters."""
    n = a1.shape[0]

    def body(i, acc):
        blk = lax.dynamic_slice_in_dim(a1, i * block, block, 0)
        if mask_b is not None:
            blk = blk * mask_b[None, :]
        p2 = jnp.dot(blk, a2, preferred_element_type=jnp.float32)
        cb = lax.dynamic_slice_in_dim(c, i * block, block, 0).astype(
            jnp.float32
        )
        prod = p2 * cb
        if mask_c is not None:
            prod = prod * mask_c[None, :].astype(jnp.float32)
        # f64 row reduction: per-row totals may pass f32's 2^24 exact range
        row = jnp.sum(prod.astype(jnp.float64), axis=1)
        mb = lax.dynamic_slice_in_dim(mult, i * block, block, 0)
        return acc + jnp.sum(jnp.round(row).astype(jnp.int64) * mb)

    return lax.fori_loop(
        0, n // block, body, jnp.asarray(0, jnp.int64)
    )


@partial(jax.jit, static_argnames=("block",))
def mxu_distinct_pairs(a1, a2, present, mask_b, mask_c, block: int):
    """count(DISTINCT a, c) over a 2-hop chain as the nonzero count of the
    boolean product: per row-block (block, N) @ (N, N) then a >0 test.
    ``present``: bool per source row (frontier membership)."""
    n = a1.shape[0]

    def body(i, acc):
        blk = lax.dynamic_slice_in_dim(a1, i * block, block, 0)
        if mask_b is not None:
            blk = blk * mask_b[None, :]
        p2 = jnp.dot(blk, a2, preferred_element_type=jnp.float32)
        hit = p2 > 0.5
        if mask_c is not None:
            hit = hit & (mask_c[None, :] > 0.5)
        pb = lax.dynamic_slice_in_dim(present, i * block, block, 0)
        hit = hit & pb[:, None]
        return acc + jnp.sum(hit.astype(jnp.int64))

    return lax.fori_loop(
        0, n // block, body, jnp.asarray(0, jnp.int64)
    )


@partial(jax.jit, static_argnames=("k", "name"))
def segment_duration_agg(data, valid, seg, k: int, name: str):
    """Duration aggregates over the (months, days, micros) device triple —
    the TPU analog of the reference's CalendarInterval UDAFs
    (``TemporalUdafs.scala``): sum/avg component-wise (avg floors the
    NORMALIZED seconds/micros split separately, matching the oracle's
    ``Duration(m//k, d//k, s//k, us//k)``), min/max by average-length key
    with first-occurrence tie selection (== Python ``min``/``max``).
    Returns (out_data (k,3) int64, any_valid (k,) bool, cnt (k,) int64)."""
    n = data.shape[0]
    v = valid if valid is not None else jnp.ones(n, bool)
    cnt = jax.ops.segment_sum(v.astype(jnp.int64), seg, num_segments=k)
    any_valid = cnt > 0
    if name in ("sum", "avg"):
        zd = jnp.where(v[:, None], data, 0)
        m = jax.ops.segment_sum(zd[:, 0], seg, num_segments=k)
        d = jax.ops.segment_sum(zd[:, 1], seg, num_segments=k)
        us = jax.ops.segment_sum(zd[:, 2], seg, num_segments=k)
        if name == "sum":
            return jnp.stack([m, d, us], axis=1), any_valid, cnt
        c = jnp.maximum(cnt, 1)
        s_n, us_n = us // 1_000_000, us % 1_000_000
        out = jnp.stack(
            [m // c, d // c, (s_n // c) * 1_000_000 + us_n // c], axis=1
        )
        return out, any_valid, cnt
    key = _dur_order_key(data)
    big = jnp.iinfo(jnp.int64).max
    if name == "min":
        best = jax.ops.segment_min(
            jnp.where(v, key, big), seg, num_segments=k
        )
    else:
        best = jax.ops.segment_max(
            jnp.where(v, key, -big), seg, num_segments=k
        )
    hit = v & (key == jnp.take(best, seg))
    rows = jnp.arange(n, dtype=jnp.int64)
    first = jax.ops.segment_min(
        jnp.where(hit, rows, n), seg, num_segments=k
    )
    out = jnp.take(data, jnp.clip(first, 0, max(n - 1, 0)), axis=0)
    return out, any_valid, cnt


@partial(jax.jit, static_argnames=("kinds", "pack"))
def equivalence_sort(datas, valids, extra_keys, kinds, pack=None):
    """(order, first-of-group flags over sorted order, group count).

    ``pack``: None, or a tuple of (lo, bits) per key — fold all-int keys
    into one 63-bit key (one stable sort instead of k)."""
    keys = list(extra_keys) + _equivalence_keys_traced(datas, valids, kinds)
    if pack is not None:
        keys = [_pack_fold(keys, pack)]
    order = jnp.lexsort(tuple(reversed(keys)))
    flags = _first_flags(keys, order)
    return order, flags, jnp.sum(flags)


@partial(jax.jit, static_argnames=("k",))
def first_occurrence_rows(order, flags, k: int):
    """Distinct row indices (original order) from a sorted factorization."""
    idx = jnp.nonzero(flags, size=k)[0]
    return jnp.sort(jnp.take(order, idx))


@jax.jit
def live_first_flags(order, flags, n):
    """First-of-group flags restricted to LIVE rows (original index below
    the traced logical ``n``) plus their count — the distinct discipline
    over pad-carrying tables, where pad rows were keyed into trailing
    groups and must not survive as phantom distinct rows."""
    f = flags & (order < n)
    return f, jnp.sum(f)


@partial(jax.jit, static_argnames=("k",))
def first_occurrence_rows_counted(order, flags, count, k: int):
    """``first_occurrence_rows`` at a BUCKETED static ``k`` >= the traced
    true ``count``: pad lanes take a beyond-end sentinel before the sort
    so the real firsts land in the leading ``count`` lanes (tail-pad
    invariant), then clip back in-bounds as dead duplicates for the
    counted gather (``cols_take_counted`` masks them)."""
    n = order.shape[0]
    pos = jnp.nonzero(flags, size=k)[0]
    rows = jnp.take(order, pos)
    rows = jnp.where(jnp.arange(k, dtype=jnp.int64) < count, rows, n)
    return jnp.clip(jnp.sort(rows), 0, n - 1)


@partial(jax.jit, static_argnames=("k",))
def group_index(order, flags, k: int):
    """(seg_j row->group ids in first-occurrence order, first_rows)."""
    n = order.shape[0]
    flag_idx = jnp.nonzero(flags, size=k)[0]
    seg_sorted = jnp.cumsum(flags.astype(jnp.int64)) - 1
    seg_rows = jnp.zeros(n, jnp.int64).at[order].set(seg_sorted)
    first_rows_keyorder = jnp.take(order, flag_idx)
    rank_order = jnp.argsort(first_rows_keyorder)
    rank = jnp.zeros(k, jnp.int64).at[rank_order].set(
        jnp.arange(k, dtype=jnp.int64)
    )
    seg_j = jnp.take(rank, seg_rows)
    first_rows = jnp.sort(first_rows_keyorder)
    return seg_j, first_rows


@partial(jax.jit, static_argnames=("k",))
def group_index_counted(order, flags, n, count, k: int):
    """``group_index`` over a tail-padded table at a BUCKETED static ``k``
    >= the traced true ``count`` of groups. ``flags`` are restricted to the
    live rows (``live_first_flags``): the stable sort puts a pad row after
    every live row of its key, so no live group loses its first. A pad row's
    group id is ``k``, which no segment reduction keeps; ``first_rows`` past
    ``count`` are dead duplicates for the counted gather."""
    m = order.shape[0]
    seg_sorted = jnp.where(
        order < n, jnp.cumsum(flags.astype(jnp.int64)) - 1, k
    )
    seg_rows = jnp.zeros(m, jnp.int64).at[order].set(seg_sorted)
    lane = jnp.arange(k, dtype=jnp.int64)
    firsts = jnp.take(order, jnp.nonzero(flags, size=k)[0])
    firsts = jnp.where(lane < count, firsts, m)
    rank = jnp.full(k + 1, k, jnp.int64).at[jnp.argsort(firsts)].set(lane)
    seg_j = jnp.take(rank, seg_rows)
    first_rows = jnp.clip(jnp.sort(firsts), 0, m - 1)
    return seg_j, first_rows


@partial(jax.jit, static_argnames=("rows",))
def cols_head(cols, rows: int):
    """The first ``rows`` lanes of every array of a column set, in one
    program (``TpuTable._head``)."""
    return jax.tree.map(lambda a: a[:rows], cols)


# ---------------------------------------------------------------------------
# ORDER BY permutation
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("kinds", "ascs"))
def order_permutation(datas, valids, kinds, ascs, n=None):
    """Stable device lexsort permutation under Cypher orderability
    (numbers < NaN < null ascending; DESC reverses all three ranks).
    Items arrive in ORDER BY priority order; keys are appended reversed so
    lexsort's last-key-primary convention sees item 0 as primary. With the
    traced ``n`` of a tail-padded table, the rows from ``n`` on sort after
    every live row whatever the items say."""
    keys = []
    for d, v, kind, asc in zip(
        reversed(datas), reversed(valids), reversed(kinds), reversed(ascs)
    ):
        null = (
            ~v if v is not None else jnp.zeros(d.shape[0], bool)
        )
        if kind == DUR:
            # average-length key; equal keys keep original order (stable
            # lexsort) — same tie policy as the oracle's order_key
            d = _dur_order_key(d)
        if kind == BOOL:
            d = d.astype(jnp.int8)
        if kind == F64:
            nan = jnp.isnan(d) & ~null
            d = jnp.where(nan, 0.0, d)
        else:
            nan = None
        if v is not None:
            # nulls are ties, broken by the next item: the payload under
            # valid=False is arbitrary (an expression's, an outer join's)
            d = jnp.where(null, jnp.zeros((), d.dtype), d)
        if asc:
            keys.append(d)
            if nan is not None:
                keys.append(nan.astype(jnp.int8))
            keys.append(null.astype(jnp.int8))
        else:
            keys.append(-d)
            if nan is not None:
                keys.append(-nan.astype(jnp.int8))
            keys.append(-null.astype(jnp.int8))
    if n is not None:
        m = datas[0].shape[0]
        keys.append((jnp.arange(m, dtype=jnp.int64) >= n).astype(jnp.int8))
    return jnp.lexsort(tuple(keys)).astype(jnp.int64)


# ---------------------------------------------------------------------------
# grouped aggregation (count/sum/avg/stdev/min/max) as one program per agg
# ---------------------------------------------------------------------------

# the largest number of groups a segment reduction takes as a dense
# compare-and-reduce: the largest k measured at which it beats the scatter
# twice over at both sizes (my chip run, PR 31, TPU v5 lite, device ms of
# one int64 reduction, sum / min, the trace's median of five):
#               n = 448,626                 n = 2^22
#   scatter     32.8 / 33.1 (k = 5),        251.0 / 252.6,
#               34.3 / 33.4 (k = 1,024)     262.6 / 263.1   61-73 ns a row
#   dense k=5   0.042 / 0.026               0.376 / 0.223
#         64    0.095 / 0.099               0.778 / 0.605
#         256   0.679 / 0.680               2.615 / 2.081
#         1,024 1.385 / 1.390               10.24 / 8.10
#         4,096 5.519 / 5.538 (6.0x)        41.10 / 32.38 (6.1x / 7.8x)
#        16,384 22.05 / 22.12 (1.5x)        164.2 / 129.3 (1.5x / 2.0x)
SEGMENT_DENSE_MAX_GROUPS = 4096


def segment_reduce_form(k: int, dtype, op: str) -> str:
    """The form ``segment_reduce`` takes for ``k`` groups of ``dtype``
    values under ``op`` (``sum | min | max``), chosen from those alone, at
    trace time and — by the same function — on the host that counts:

    * ``dense``: every group compares its id against every row and reduces
      what matches; no scatter, ``k`` compare-selects a row. Taken where
      the combine is free of order (integer sums, every min and max), up
      to ``SEGMENT_DENSE_MAX_GROUPS`` groups;
    * ``scatter``: ``jax.ops.segment_<op>``, which the chip serialises row
      by row whatever ``k``. A float sum keeps it at every ``k``: its
      order of addition is part of the result's bits."""
    if op == "sum" and not jnp.issubdtype(dtype, jnp.integer):
        return "scatter"
    return "dense" if k <= SEGMENT_DENSE_MAX_GROUPS else "scatter"


def segment_aggregate_form(name: str, dtype, k: int) -> str:
    """The form of the reduction that is aggregator ``name``'s own in
    ``segment_aggregate``, over values of ``dtype``: what the host counts
    (``tpu_cypher_segment_reduce_total``)."""
    if name in ("stdev", "stdevp"):
        return "scatter"  # the sum of the squared differences is a float's
    if name == "count":
        return segment_reduce_form(k, jnp.int64, "sum")
    return segment_reduce_form(
        k, dtype, name if name in ("min", "max") else "sum"
    )


def _reduce_identity(dtype, op: str):
    """What ``jax.ops.segment_<op>`` leaves in an empty group."""
    if op == "sum":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if op == "min" else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if op == "min" else info.min, dtype)


def segment_reduce(values, seg, k: int, op: str):
    """``jax.ops.segment_<op>(values, seg, num_segments=k)``, bit for bit,
    in the form ``segment_reduce_form`` names (traced helper). A row whose
    id lies outside ``[0, k)`` contributes nothing; an empty group holds the
    op's identity."""
    if segment_reduce_form(k, values.dtype, op) == "scatter":
        return getattr(jax.ops, f"segment_{op}")(values, seg, num_segments=k)
    # 32-bit ids for the k compares of a row (a 64-bit compare costs the
    # chip two), cut to that width as the scatter cuts its indices
    seg32 = seg.astype(jnp.int32)
    hit = seg32[None, :] == jnp.arange(k, dtype=jnp.int32)[:, None]
    identity = _reduce_identity(values.dtype, op)
    picked = jnp.where(hit, values[None, :], identity)
    # one fused compare-select-reduce: no (k, n) array is ever stored
    if op == "sum":
        return jnp.sum(picked, axis=1, dtype=values.dtype)
    reduce = jnp.min if op == "min" else jnp.max
    return reduce(picked, axis=1, initial=identity)


@partial(jax.jit, static_argnames=("name", "kind", "k"))
def segment_aggregate(data, valid, iflag, seg_j, name: str, kind: str, k: int):
    """One aggregator over (value column, group index): the whole segment
    computation — null masking, NaN orderability, Cypher intness tracking —
    as ONE cached program. Returns (out_data, out_valid_or_None,
    out_iflag_or_None, iflag_any_or_None); the host drops an all-false
    int_flag using the scalar so column metadata stays canonical."""
    n = data.shape[0]
    v = valid if valid is not None else jnp.ones(n, bool)
    # the phases carry scopes of their own (metadata only: a kept device
    # trace tells them apart): count, sum, minmax, intness
    with jax.named_scope("count"):
        cnt = segment_reduce(v.astype(jnp.int64), seg_j, k, "sum")
    if name == "count":
        return cnt, None, None, None
    if name in ("sum", "avg", "stdev", "stdevp"):
        zero = jnp.zeros((), data.dtype)
        with jax.named_scope("sum"):
            # a float sum stays the scatter (``segment_reduce_form``)
            ssum = segment_reduce(jnp.where(v, data, zero), seg_j, k, "sum")
        if name == "sum":
            if kind == F64:
                # Cypher sum over no values is the INTEGER 0, and the sum
                # of an all-integer group is an INTEGER — int_flag lets
                # the float column carry both exactly (ints < 2**53)
                empty = cnt == 0
                if iflag is not None:
                    int_if_valid = jnp.where(v, iflag, True)
                    all_int = (
                        segment_reduce(
                            int_if_valid.astype(jnp.int8), seg_j, k, "min"
                        )
                        == 1
                    )
                    out_iflag = all_int | empty
                else:
                    out_iflag = empty
                return (
                    jnp.where(empty, 0.0, ssum), None, out_iflag,
                    jnp.any(out_iflag),
                )
            return ssum, None, None, None
        if name == "avg":
            avg = ssum.astype(jnp.float64) / jnp.maximum(cnt, 1)
            return avg, cnt > 0, None, None
        # stdev (sample) / stdevp (population): two-pass for stability;
        # empty and single-value groups are 0.0 like the oracle
        x = data.astype(jnp.float64)
        mean = ssum.astype(jnp.float64) / jnp.maximum(cnt, 1)
        diff = jnp.where(v, x - jnp.take(mean, seg_j), 0.0)
        ssq = jax.ops.segment_sum(diff * diff, seg_j, num_segments=k)
        denom = jnp.maximum(cnt - (1 if name == "stdev" else 0), 1)
        out = jnp.sqrt(ssq / denom)
        return jnp.where(cnt >= 2, out, 0.0), None, None, None
    # min / max with Cypher orderability: numbers < NaN; nulls skipped
    d = data.astype(jnp.int8) if kind == BOOL else data
    if kind == F64:
        isnan = jnp.isnan(d) & v
        nn_valid = v & ~isnan
        nan_cnt = segment_reduce(isnan.astype(jnp.int64), seg_j, k, "sum")
    else:
        nn_valid = v
        nan_cnt = None
    big = (
        jnp.asarray(jnp.inf, d.dtype)
        if kind == F64
        else jnp.asarray(jnp.iinfo(d.dtype).max, d.dtype)
    )
    if name == "min":
        with jax.named_scope("minmax"):
            agged = segment_reduce(
                jnp.where(nn_valid, d, big), seg_j, k, "min"
            )
        if nan_cnt is not None:
            # all-NaN group: min is NaN (NaN sorts above numbers)
            agged = jnp.where((cnt - nan_cnt == 0) & (nan_cnt > 0), jnp.nan, agged)
    else:
        low = -big if kind != STR else -jnp.ones((), d.dtype)
        with jax.named_scope("minmax"):
            agged = segment_reduce(
                jnp.where(nn_valid, d, low), seg_j, k, "max"
            )
        if nan_cnt is not None:
            # any NaN: NaN is the maximum under Cypher orderability
            agged = jnp.where(nan_cnt > 0, jnp.nan, agged)
    if kind == BOOL:
        agged = agged.astype(bool)
    out_iflag = None
    iflag_any = None
    if kind == F64 and iflag is not None and n:
        # Cypher intness of the winning value: the oracle's min/max keeps
        # the FIRST minimal/maximal element in row order, so take the
        # int_flag of the first row matching the aggregate
        with jax.named_scope("intness"):
            cand = nn_valid & (d == jnp.take(agged, seg_j))
            first_row = segment_reduce(
                jnp.where(cand, jnp.arange(n, dtype=jnp.int64), n),
                seg_j, k, "min",
            )
            safe_row = jnp.clip(first_row, 0, max(n - 1, 0))
            out_iflag = jnp.take(iflag, safe_row) & (first_row < n)
            iflag_any = jnp.any(out_iflag)
    return agged, cnt > 0, out_iflag, iflag_any


@partial(jax.jit, static_argnames=("name", "k"))
def segment_percentile(data, valid, seg_j, p, name: str, k: int):
    """percentileCont/Disc core: one segment-sorted gather program.
    Returns (out_data, out_valid, order, positions) — the caller maps
    gathered rows back for int_flag bookkeeping on the disc variant."""
    n = data.shape[0]
    v = valid if valid is not None else jnp.ones(n, bool)
    cnt = jax.ops.segment_sum(v.astype(jnp.int64), seg_j, num_segments=k)
    # explicit invalid flag as the secondary sort key — a value sentinel
    # (+inf / int max) could tie with legitimate data and let a null
    # row's payload be gathered as the percentile
    order = jnp.lexsort((data, (~v).astype(jnp.int8), seg_j))
    sorted_val = jnp.take(data, order)
    sizes = jax.ops.segment_sum(jnp.ones(n, jnp.int64), seg_j, num_segments=k)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int64), jnp.cumsum(sizes)])[:-1]
    safe_cnt = jnp.maximum(cnt, 1)
    if name == "percentiledisc":
        idx = jnp.where(
            p > 0,
            jnp.ceil(p * safe_cnt.astype(jnp.float64)).astype(jnp.int64) - 1,
            0,
        )
        idx = jnp.clip(idx, 0, safe_cnt - 1)
        pos = jnp.clip(starts + idx, 0, max(n - 1, 0))
        out = jnp.take(sorted_val, pos) if n else jnp.zeros(k, data.dtype)
        return out, cnt > 0, order, pos
    fidx = p * (safe_cnt.astype(jnp.float64) - 1)
    lo = jnp.floor(fidx).astype(jnp.int64)
    hi = jnp.ceil(fidx).astype(jnp.int64)
    frac = fidx - lo.astype(jnp.float64)
    pos_lo = jnp.clip(starts + lo, 0, max(n - 1, 0))
    pos_hi = jnp.clip(starts + hi, 0, max(n - 1, 0))
    if n:
        vlo = jnp.take(sorted_val, pos_lo).astype(jnp.float64)
        vhi = jnp.take(sorted_val, pos_hi).astype(jnp.float64)
        out = vlo * (1 - frac) + vhi * frac
    else:
        out = jnp.zeros(k, jnp.float64)
    return out, cnt > 0, order, pos_lo


# ---------------------------------------------------------------------------
# ORDER BY ... LIMIT k as top-k over one packed key
# ---------------------------------------------------------------------------

# the fewest rows at which ORDER BY ... LIMIT k over integral keys that pack
# into 62 bits is one ``lax.top_k`` over the packed rank (``order_minmax``, a
# blocking read of the ranges, ``order_topk``) and not the prefix of the
# stable sort (``order_permutation``, the prefix cut in the gather). The
# top-k is a third of the sort's device time at every size and flat in k,
# but pays a probe and a blocking read before it can start, 1.9-2.6 ms of
# host more than the sort: it wins where two thirds of the sort are longer
# than that (my chip run, PR 33, call a, TPU v5 lite; a 12-bit key with
# nulls DESC and a 16-bit key ASC; ms, k = 10 / 1,000, the median of seven;
# device from the trace, wall round indices and a 12-column gather with the
# device idle before):
#                    n = 65,645      n = 448,626     n = 2^22
#   sort, device     0.378           2.201           28.68
#   top-k, device    0.141 / 0.141   0.768 / 0.768   8.800 / 8.801
#   sort, wall       1.90 / 2.12     2.89 / 3.19     30.14 / 30.33
#   top-k, wall      3.99 / 4.49     4.01 / 4.39     12.19 / 12.43
# Level at about 700,000 rows by the two slopes; 2^20 is the first lattice
# size past it (the sort 5.9 ms there, the top-k 2.0 and 2 ms of host).
ORDER_TOPK_MIN_ROWS = 1 << 20


@jax.jit
def order_minmax(datas, valids):
    """(min, max) per key over VALID rows only (invalid payloads are
    arbitrary and must not widen the packing range)."""
    mins = []
    maxs = []
    for d, v in zip(datas, valids):
        d = d.astype(jnp.int64)
        if v is not None:
            info = jnp.iinfo(jnp.int64)
            mins.append(jnp.min(jnp.where(v, d, info.max)))
            maxs.append(jnp.max(jnp.where(v, d, info.min)))
        else:
            mins.append(jnp.min(d))
            maxs.append(jnp.max(d))
    return jnp.stack(mins), jnp.stack(maxs)


@partial(jax.jit, static_argnames=("ascs", "k"))
def order_topk(datas, valids, ascs, los, spans, bits, k: int):
    """Row indices of the first ``k`` rows under Cypher orderability,
    computed as ONE ``lax.top_k`` over a packed int64 rank. Keys arrive in
    ORDER BY priority order; each contributes (1 null bit | data bits) with
    DESC keys bit-reversed, so lexicographic order == integer order.
    All-integer keys only; ``los`` / ``spans`` / ``bits`` are each key's
    measured minimum, range and width, traced (a new range is no new
    program; the caller guarantees the bit budget)."""
    acc = jnp.zeros(datas[0].shape[0], jnp.int64)
    for i, (d, v, asc) in enumerate(zip(datas, valids, ascs)):
        d = d.astype(jnp.int64)
        val = d - los[i]
        if v is not None:
            val = jnp.where(v, val, 0)
            null_rank = (~v).astype(jnp.int64)  # nulls last ascending
        else:
            null_rank = jnp.zeros_like(val)
        if not asc:
            val = spans[i] - val
            null_rank = 1 - null_rank  # nulls first descending
        acc = (acc << 1) | null_rank
        acc = (acc << bits[i]) | val
    # stable tiebreak: original row index in the lowest bits (matches the
    # oracle's stable sort; the caller budgets these bits)
    n = acc.shape[0]
    rowbits = max(n - 1, 0).bit_length()
    acc = (acc << rowbits) | jnp.arange(n, dtype=jnp.int64)
    _, idx = jax.lax.top_k(-acc, k)
    return idx.astype(jnp.int64)


# ---------------------------------------------------------------------------
# sort-probe join phases
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("is_f64", "is_bool"))
def join_build(rd, rvalids, is_f64: bool, is_bool: bool):
    """Build-side prep: fold validity masks, NaN-exclude float keys, sort
    valid-first-by-key. Returns (key data, valid, order, valid count)."""
    rvalid = jnp.ones(rd.shape[0], bool)
    for m in rvalids:
        rvalid = rvalid & m
    if is_f64:
        rvalid = rvalid & ~jnp.isnan(rd)
    if is_bool:
        rd = rd.astype(jnp.int8)
    r_order = jnp.lexsort((rd, ~rvalid))
    return rd, r_order, jnp.sum(rvalid)


@partial(jax.jit, static_argnames=("nvalid", "is_f64", "is_bool"))
def join_probe(rd, r_order, ld, lvalids, nvalid: int, is_f64: bool, is_bool: bool):
    """Probe side: binary-search the sorted build keys. Returns
    (valid build row indices, lo, match counts, total)."""
    lvalid = jnp.ones(ld.shape[0], bool)
    for m in lvalids:
        lvalid = lvalid & m
    if is_f64:
        lvalid = lvalid & ~jnp.isnan(ld)
    if is_bool:
        ld = ld.astype(jnp.int8)
    r_idx_valid = r_order[:nvalid]
    r_sorted = jnp.take(rd, r_idx_valid)
    lo = jnp.searchsorted(r_sorted, ld, side="left")
    hi = jnp.searchsorted(r_sorted, ld, side="right")
    counts = jnp.where(lvalid, hi - lo, 0).astype(jnp.int64)
    return r_idx_valid, lo, counts, jnp.sum(counts)


@partial(jax.jit, static_argnames=("total",))
def join_materialize(r_idx_valid, lo, counts, total: int):
    left_rows, flat = _expand_rows(lo, counts, total)
    right_rows = (
        jnp.take(r_idx_valid, flat) if total else jnp.zeros(0, jnp.int64)
    )
    return left_rows, right_rows


@partial(jax.jit, static_argnames=("nvalid_cap", "is_f64", "is_bool"))
def join_probe_bucketed(
    rd, r_order, ld, lvalids, nvalid, nvalid_cap: int, is_f64: bool,
    is_bool: bool,
):
    """``join_probe`` with the build-side valid count as a TRACED operand:
    the static slice is the BUCKETED cap (``nvalid_cap`` >= nvalid), build
    lanes at/past the true count are overwritten with a +max sentinel (the
    array stays sorted: the valid-first build sort puts them at the tail),
    and ``lo``/``hi`` clamp to ``nvalid`` so sentinel lanes can never match
    — even a probe key equal to the sentinel value finds an empty range."""
    lvalid = jnp.ones(ld.shape[0], bool)
    for m in lvalids:
        lvalid = lvalid & m
    if is_f64:
        lvalid = lvalid & ~jnp.isnan(ld)
    if is_bool:
        ld = ld.astype(jnp.int8)
        rd = rd.astype(jnp.int8)
    r_idx_valid = r_order[:nvalid_cap]
    r_sorted = jnp.take(rd, r_idx_valid)
    big = (
        jnp.asarray(jnp.inf, r_sorted.dtype)
        if is_f64
        else jnp.asarray(jnp.iinfo(r_sorted.dtype).max, r_sorted.dtype)
    )
    lane = jnp.arange(nvalid_cap, dtype=jnp.int64)
    r_sorted = jnp.where(lane < nvalid, r_sorted, big)
    lo = jnp.minimum(jnp.searchsorted(r_sorted, ld, side="left"), nvalid)
    hi = jnp.minimum(jnp.searchsorted(r_sorted, ld, side="right"), nvalid)
    counts = jnp.where(lvalid, hi - lo, 0).astype(jnp.int64)
    return r_idx_valid, lo, counts, jnp.sum(counts)


@partial(jax.jit, static_argnames=("size",))
def join_materialize_counted(r_idx_valid, lo, counts, nvalid, size: int):
    """``join_materialize`` at a BUCKETED static ``size`` >= the true match
    total (``nvalid``, traced): pad lanes are sanitized to pair (0, 0) and
    reported dead via the returned ``live`` mask (the raw repeat pads run
    past the build-row array — an out-of-bounds gather fill must never
    escape as a row index)."""
    left_rows, flat = _expand_rows(lo, counts, size)
    live = _live_lanes(size, nvalid)
    left_rows = jnp.where(live, left_rows, 0)
    flat = jnp.where(live, flat, 0)
    n_r = r_idx_valid.shape[0]
    if n_r and size:
        right_rows = jnp.take(
            r_idx_valid, jnp.clip(flat, 0, n_r - 1)
        )
        right_rows = jnp.where(live, right_rows, 0)
    else:
        right_rows = jnp.zeros(size, jnp.int64)
    return left_rows, right_rows, live


@partial(jax.jit, static_argnames=("n",))
def unmatched_mask(hit_rows, n: int):
    """Bool mask of build/probe rows never matched (outer-join padding)."""
    return ~jnp.zeros(n, bool).at[hit_rows].set(True)


@partial(jax.jit, static_argnames=("nmiss", "nmatched"))
def outer_pad_left(left_rows, right_rows, miss_idx, nmiss: int, nmatched: int):
    """Append one all-null-right row per unmatched probe row."""
    left = jnp.concatenate([left_rows, miss_idx])
    right = jnp.concatenate([right_rows, jnp.zeros(nmiss, jnp.int64)])
    matched = jnp.concatenate(
        [jnp.ones(nmatched, bool), jnp.zeros(nmiss, bool)]
    )
    return left, right, matched


@partial(jax.jit, static_argnames=("nmiss", "ncur"))
def outer_pad_right(left_rows, right_rows, right_matched, rmiss_idx, nmiss: int, ncur: int):
    """Append one all-null-left row per unmatched build row (full outer)."""
    left = jnp.concatenate([left_rows, jnp.zeros(nmiss, jnp.int64)])
    right = jnp.concatenate([right_rows, rmiss_idx])
    left_matched = jnp.concatenate([jnp.ones(ncur, bool), jnp.zeros(nmiss, bool)])
    right_matched = jnp.concatenate([right_matched, jnp.ones(nmiss, bool)])
    return left, right, left_matched, right_matched


@partial(jax.jit, static_argnames=("kinds",))
def extra_keys_keep(l_datas, l_valids, r_datas, r_valids, left_rows, right_rows, kinds):
    """Multi-key equi-join post-filter: AND of per-pair ``=`` equality
    (NaN never matches; validity masks carry match-eligibility)."""
    keep = jnp.ones(left_rows.shape[0], bool)
    for ld, lv, rd, rv, k in zip(l_datas, l_valids, r_datas, r_valids, kinds):
        lvals = jnp.take(ld, left_rows)
        rvals = jnp.take(rd, right_rows)
        eq = lvals == rvals
        if k == F64:
            eq = eq & ~jnp.isnan(lvals)
        if lv is not None:
            eq = eq & jnp.take(lv, left_rows)
        if rv is not None:
            eq = eq & jnp.take(rv, right_rows)
        keep = keep & eq
    return keep


# every jitted program of this module dispatches under an obs.trace
# ``dispatch`` leaf (last: the decorators above stay plain ``jax.jit``)
_obs_trace.wrap_programs(globals())
