"""Factorized (compressed) join intermediates: prefix x suffix runs.

A ``FactorizedTable`` is the TrieJax/EmptyHeaded-style representation of an
expand or multiway-join intermediate: a flat *prefix* table (one lane per
path prefix, a plain ``TpuTable``) plus one or more *run levels*, each a
``(lo, cnt)`` pair of per-lane anchor ranges into the sorted CSR — the
adjacency slice ``ci[lo[i]:lo[i]+cnt[i]]`` IS lane ``i``'s suffix run, so
the run bounds come for free from ``graph_index``'s edge-key anchors. The
logical row set is the lazy cross product

    rows = sum_i  prod_j  cnt_j[i]

which never materializes unless an operator genuinely needs flat rows.
Relational ops execute directly on the compressed form where multiplicity
algebra allows it:

* select/rename/drop/project — column bookkeeping only
* filter / with_columns       — on prefix columns, at the lane domain
* count/sum/avg aggregates    — run-length *weighted* segment ops
  (``parallel.agg.weighted_segment_partials``); min/max and DISTINCT
  aggregates are multiplicity-invariant and run on the nonempty prefix
* DISTINCT / distinct_count   — on prefix columns (nonempty lanes)
* ORDER BY (/LIMIT)           — a stable lane permutation: flat enumeration
  order is (lane, suffix) and the lexsort is stable, so sorting lanes
  reproduces the flat sort order exactly, ties included
* skip/limit/collect          — lazy decompression, chunk by chunk

Everything else (joins, UNWIND, weight-sensitive aggregates) flattens
first via ``to_flat_table`` — which is admission-guarded, so a flat blowup
still surfaces as ``AdmissionRejected`` instead of an OOM.

Shape discipline: prefix lanes and every decompression chunk are rounded
on the bucket lattice (``bucketing.round_size``), so the factorized tier
adds ZERO warm recompiles — the decode programs are keyed only by bucket
sizes and level structure. Decode gathers clip indices in-bounds (an OOB
gather under jit FILLS with int64 min) and mask dead lanes via the
explicit ``live`` mask; the weight cumsum is re-masked with the bucket
sentinel before the ``searchsorted`` probe (a cumsum forfeits the pad
mask — pad lanes must be unreachable by construction).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ...api import types as T
from ...api.table import Table
from ...api.types import CypherType
from ...ir import expr as E
from ...obs import trace as _obs_trace
from ...runtime.faults import fault_point
from . import bucketing
from . import jit_ops as J
from .column import (
    F64,
    I64,
    OBJ,
    Column,
    TpuBackendError,
    mask_to_idx,
    mask_to_idx_bucketed,
)
from .compiler import TpuEvaluator, TpuUnsupportedExpr
from .table import TpuTable


def factorize_mode() -> str:
    """The ``TPU_CYPHER_FACTORIZE`` knob, normalized: auto | force | off."""
    from ...utils.config import FACTORIZE

    m = str(FACTORIZE.get()).strip().lower()
    return m if m in ("auto", "force", "off") else "auto"


def decompress_chunk_rows() -> int:
    """Logical rows per decompression chunk (floor 1024)."""
    from ...utils.config import FACTORIZE_CHUNK_ROWS

    return max(int(FACTORIZE_CHUNK_ROWS.get()), 1024)


class RunLevel(NamedTuple):
    """One suffix level: per-lane anchor runs over a sorted CSR domain.

    ``lo``/``cnt`` are int64 device arrays at the lane physical extent
    (``cnt`` is 0 on dead/pad lanes). ``cols`` maps an output column name
    to ``(source_column, maps)``: a flat position ``p`` in the run decodes
    through the gather-map chain left to right (each hop clipped
    in-bounds), e.g. a relationship property is ``(rel_scan_col, (eo,))``
    and an expand far-node property is ``(node_scan_col, (ci, row_map))``.
    """

    lo: Any
    cnt: Any
    cols: Dict[str, Tuple[Column, Tuple[Any, ...]]]


# ---------------------------------------------------------------------------
# jitted decode programs (keyed by bucket sizes + level structure only)
# ---------------------------------------------------------------------------


@jax.jit
def _runs_weights(cnts, nlanes):
    """Per-lane flat-row weight ``w = prod_j cnt_j`` masked to the logical
    lane prefix, the total flat row count, and the inclusive cumsum ``W``
    the decode probes with ``searchsorted``. Pad lanes carry the bucket
    sentinel in ``W`` (the cumsum forfeits the pad mask; the ``where``
    re-establishes it), so a live probe ``f < total`` can never land on
    one."""
    w = None
    for cnt in cnts:
        c = jnp.maximum(cnt.astype(jnp.int64), 0)
        w = c if w is None else w * c
    live = jnp.arange(w.shape[0], dtype=jnp.int64) < nlanes
    w = jnp.where(live, w, 0)
    total = jnp.sum(w)
    W = jnp.where(live, jnp.cumsum(w), bucketing.ID_SENTINEL)
    return w, W, total


@partial(jax.jit, static_argnames=("size",))
def _decode_runs(W, w, los, cnts, base, nvalid, size: int):
    """Flat rows ``[base, base + size)`` -> (lane index, per-level run
    positions, live mask). Lane ``i`` owns flat rows ``[W[i]-w[i], W[i])``;
    the within-lane remainder decodes as a mixed-radix number over the
    level counts (last level fastest — the flat enumeration order). Dead
    probes (``f >= nvalid``) clamp to lane 0 / position ``lo`` and are
    killed by ``live`` downstream."""
    f = base + jnp.arange(size, dtype=jnp.int64)
    live = f < nvalid
    i = jnp.clip(jnp.searchsorted(W, f, side="right"), 0, w.shape[0] - 1)
    inner = jnp.where(live, f - (jnp.take(W, i) - jnp.take(w, i)), 0)
    pos = []
    for lo, cnt in zip(reversed(los), reversed(cnts)):
        c = jnp.maximum(jnp.take(cnt, i), 1)
        pos.append(jnp.take(lo, i) + inner % c)
        inner = inner // c
    return i, tuple(reversed(pos)), live


@jax.jit
def _gather_decoded(prefix_dev, level_dev, i, pos, live):
    """All device-column gathers of one decompression chunk as ONE cached
    program: prefix columns gather at the lane index, level columns walk
    their gather-map chain from the decoded run position (every hop
    clipped in-bounds — an OOB gather under jit fills with int64 min, and
    dead lanes carry clamped positions by design). Validity masks fold the
    ``live`` mask so pad/dead rows come out invalid."""
    out = {}
    for name, (d, v, fl) in prefix_dev.items():
        out[name] = (
            jnp.take(d, i, axis=0),
            (jnp.take(v, i) & live) if v is not None else live,
            jnp.take(fl, i) if fl is not None else None,
        )
    for grp, p in zip(level_dev, pos):
        for name, (d, v, fl, maps) in grp.items():
            idx = p
            for m in maps:
                idx = jnp.take(m, jnp.clip(idx, 0, m.shape[0] - 1))
            idx = jnp.clip(idx, 0, d.shape[0] - 1)
            out[name] = (
                jnp.take(d, idx, axis=0),
                (jnp.take(v, idx) & live) if v is not None else live,
                jnp.take(fl, idx) if fl is not None else None,
            )
    return out


@jax.jit
def _zero_tail(cnt, count):
    live = jnp.arange(cnt.shape[0], dtype=jnp.int64) < count
    return jnp.where(live, cnt, 0)


@jax.jit
def _positive_mask(w, nlanes):
    return (w > 0) & (jnp.arange(w.shape[0], dtype=jnp.int64) < nlanes)


def _expr_cols(expr, header) -> set:
    """Every header column an expression evaluation may touch: the mapped
    column of each sub-expression, plus ALL columns of any element
    variable it mentions (the evaluator resolves element comparisons
    through id columns the walk cannot see). Over-collection is safe — it
    only forces a flat fallback; under-collection would silently evaluate
    a level column at the lane domain."""
    cols = set()
    for sub in expr.iter_nodes():
        c = header.get(sub)
        if c is not None:
            cols.add(c)
        if isinstance(sub, E.Var):
            for e2 in header.expressions_for(sub):
                c2 = header.get(e2)
                if c2 is not None:
                    cols.add(c2)
    return cols


class FactorizedTable(Table):
    """A prefix ``TpuTable`` plus suffix run levels — see module docstring.

    ``nrows`` (the flat row total) may be passed by producers that already
    synced it; otherwise construction costs one scalar device->host sync,
    the same count-sync discipline every size-producing step pays."""

    def __init__(
        self,
        prefix: TpuTable,
        levels: Sequence[RunLevel],
        nrows: Optional[int] = None,
    ):
        self._prefix = prefix
        self._levels = tuple(levels)
        if not self._levels:
            raise TpuBackendError("factorized table needs at least one run level")
        lane_phys = int(self._levels[0].lo.shape[0])
        for lv in self._levels:
            if int(lv.lo.shape[0]) != lane_phys or int(lv.cnt.shape[0]) != lane_phys:
                raise TpuBackendError("factorized level arrays disagree on lane extent")
        for c in prefix._cols.values():
            if c.kind != OBJ and len(c) != lane_phys:
                raise TpuBackendError("factorized prefix misaligned with run levels")
        self._nlanes = prefix.size
        cnts = tuple(lv.cnt for lv in self._levels)
        self._w, self._W, tot = _runs_weights(cnts, self._nlanes)
        if nrows is None:
            fault_point("expand")  # the flat-total scalar sync below
            self._nrows = int(tot)
        else:
            self._nrows = int(nrows)
        self._flat_cache: Optional[TpuTable] = None
        self._nonempty_cache = None

    # -- metadata ----------------------------------------------------------

    @property
    def _lane_phys(self) -> int:
        return int(self._levels[0].lo.shape[0])

    def _level_col_names(self) -> set:
        out = set()
        for lv in self._levels:
            out.update(lv.cols)
        return out

    @property
    def run_count(self) -> int:
        """Suffix runs per level (= logical lanes)."""
        return self._nlanes

    @property
    def physical_columns(self) -> List[str]:
        out = list(self._prefix.physical_columns)
        for lv in self._levels:
            out.extend(c for c in lv.cols if c not in out)
        return out

    def column_type(self, col: str) -> CypherType:
        if self._nrows == 0:
            return T.CTVoid
        if col in self._prefix._cols:
            # prefix lanes can be nonempty while some carry weight 0; the
            # flat column still exists, so delegate metadata to the prefix
            return self._prefix.column_type(col) if self._nlanes else T.CTVoid
        for lv in self._levels:
            if col in lv.cols:
                src, _ = lv.cols[col]
                return src.cypher_type()
        raise KeyError(col)

    @property
    def size(self) -> int:
        return self._nrows

    def __repr__(self) -> str:
        return (
            f"FactorizedTable({self._nrows} rows = {self._nlanes} lanes x "
            f"{len(self._levels)} levels, cols={self.physical_columns})"
        )

    # -- decompression -----------------------------------------------------

    def _decode_chunk(self, lo: int, hi: int, size: int) -> TpuTable:
        """Flat rows ``[lo, hi)`` as a TpuTable at physical ``size``
        (bucket-rounded by callers, so warm chunks reuse one compiled
        decode+gather program per level structure)."""
        fault_point("expand")  # OBJ prefix gathers sync the lane indices
        count = hi - lo
        los = tuple(lv.lo for lv in self._levels)
        cnts = tuple(lv.cnt for lv in self._levels)
        i, pos, live = _decode_runs(
            self._W, self._w, los, cnts, np.int64(lo), np.int64(hi), size
        )
        prefix_dev = {
            c: (col.data, col.valid, col.int_flag)
            for c, col in self._prefix._cols.items()
            if col.kind != OBJ
        }
        level_dev = []
        for lv in self._levels:
            level_dev.append(
                {
                    c: (src.data, src.valid, src.int_flag, maps)
                    for c, (src, maps) in lv.cols.items()
                }
            )
        taken = _gather_decoded(prefix_dev, tuple(level_dev), i, pos, live)
        pad = size - count
        out: Dict[str, Column] = {}
        i_host = None
        for c, col in self._prefix._cols.items():
            if col.kind == OBJ:
                if i_host is None:
                    i_host = np.asarray(i)[:count]
                out[c] = col.take(i_host)
                continue
            d, v, fl = taken[c]
            out[c] = Column(
                col.kind, d, v, col.vocab, int_flag=fl,
                pad=pad, pad_synth=col.valid is None or col.pad_synth,
            )
        for lv in self._levels:
            for c, (src, _) in lv.cols.items():
                d, v, fl = taken[c]
                out[c] = Column(
                    src.kind, d, v, src.vocab, int_flag=fl,
                    pad=pad, pad_synth=src.valid is None or src.pad_synth,
                )
        return TpuTable(out, count)

    def _decompress_range(self, lo: int, hi: int) -> TpuTable:
        """One-shot flat materialization of rows ``[lo, hi)`` — admission
        guarded, so an over-budget flatten surfaces as the typed
        ``AdmissionRejected`` instead of an OOM."""
        lo = max(int(lo), 0)
        hi = min(int(hi), self._nrows)
        count = max(hi - lo, 0)
        ncols = max(len(self.physical_columns), 1)
        bucketing.admit(count, 9 * ncols, "factorized")
        if count == 0:
            return TpuTable(
                {c: _empty_like(self._source_column(c)) for c in self.physical_columns},
                0,
            )
        return self._decode_chunk(lo, hi, bucketing.round_size(count))

    def _source_column(self, col: str) -> Column:
        if col in self._prefix._cols:
            return self._prefix._cols[col]
        for lv in self._levels:
            if col in lv.cols:
                return lv.cols[col][0]
        raise KeyError(col)

    def to_flat_table(self) -> TpuTable:
        """The fully decompressed flat table (memoized; admission guarded).
        ``table.ensure_flat`` duck-types on this method."""
        if self._flat_cache is None:
            self._flat_cache = self._decompress_range(0, self._nrows)
        return self._flat_cache

    _flat = to_flat_table

    def rows_chunked(self, chunk_rows: int) -> Iterator[List[Dict[str, Any]]]:
        """Bounded decompress-then-decode batches — the cursor-streaming
        delivery path (``RelationalCypherRecords.iter_chunks`` prefers
        this), so a 100M-row factorized result streams at O(chunk) host
        memory without ever flattening."""
        chunk_rows = max(int(chunk_rows), 1)
        size = bucketing.round_size(chunk_rows)
        for lo in range(0, self._nrows, chunk_rows):
            hi = min(lo + chunk_rows, self._nrows)
            t = self._decode_chunk(lo, hi, size)
            decoded = {
                c: col.to_values_range(0, hi - lo)
                for c, col in t._cols.items()
            }
            yield [
                {c: v[i] for c, v in decoded.items()} for i in range(hi - lo)
            ]

    def rows(self) -> Iterator[Dict[str, Any]]:
        for batch in self.rows_chunked(decompress_chunk_rows()):
            for r in batch:
                yield r

    def column_values(self, col: str) -> List[Any]:
        out: List[Any] = []
        chunk = decompress_chunk_rows()
        size = bucketing.round_size(chunk)
        for lo in range(0, self._nrows, chunk):
            hi = min(lo + chunk, self._nrows)
            t = self._decode_chunk(lo, hi, size)
            out.extend(t._cols[col].to_values_range(0, t.size))
        return out

    # -- lane-domain helpers -----------------------------------------------

    def _take_lanes(self, idx, count: int) -> "FactorizedTable":
        """Gather a lane subset (prefix + run bounds) — the factorized
        analog of ``TpuTable._take_counted``; counts past ``count`` zero
        out so pad lanes carry no flat rows."""
        pfx = self._prefix._take_counted(idx, count)
        levels = []
        for lv in self._levels:
            lo2, cnt2 = J.tree_take((lv.lo, lv.cnt), idx)
            levels.append(RunLevel(lo2, _zero_tail(cnt2, count), lv.cols))
        return FactorizedTable(pfx, levels)

    def _exact_lanes(self) -> "FactorizedTable":
        """Lane arrays compacted to the exact logical count (drops bucket
        and shard pads) — for ops whose machinery assumes unpadded rows."""
        if self._lane_phys == self._nlanes:
            return self
        idx = jnp.arange(self._nlanes, dtype=jnp.int64)
        return self._take_lanes(idx, self._nlanes)

    def _nonempty_exact(self):
        """(prefix rows whose lanes carry weight > 0 — exact, unpadded —
        their weights, row count). Multiplicity-invariant ops (DISTINCT,
        min/max, group keys) see exactly the flat table's value set."""
        if self._nonempty_cache is None:
            keep = _positive_mask(self._w, self._nlanes)
            idx, count = mask_to_idx(keep)
            pfx = self._prefix._take(idx)
            w2 = J.tree_take(self._w, idx)
            self._nonempty_cache = (pfx, w2, count)
        return self._nonempty_cache

    # -- column bookkeeping (no decompression) -----------------------------

    def select(self, cols: Sequence[str]) -> "FactorizedTable":
        lvl_names = self._level_col_names()
        missing = [
            c for c in cols if c not in self._prefix._cols and c not in lvl_names
        ]
        if missing:
            raise KeyError(missing[0])
        pfx = self._prefix.select([c for c in cols if c in self._prefix._cols])
        levels = [
            RunLevel(lv.lo, lv.cnt, {c: lv.cols[c] for c in cols if c in lv.cols})
            for lv in self._levels
        ]
        return FactorizedTable(pfx, levels, nrows=self._nrows)

    def rename(self, mapping: Dict[str, str]) -> "FactorizedTable":
        pfx = self._prefix.rename(
            {k: v for k, v in mapping.items() if k in self._prefix._cols}
        )
        levels = [
            RunLevel(
                lv.lo, lv.cnt,
                {mapping.get(c, c): s for c, s in lv.cols.items()},
            )
            for lv in self._levels
        ]
        return FactorizedTable(pfx, levels, nrows=self._nrows)

    def drop(self, cols: Sequence[str]) -> "FactorizedTable":
        d = set(cols)
        pfx = self._prefix.drop([c for c in cols if c in self._prefix._cols])
        # a level whose columns all drop KEEPS its (lo, cnt) runs: the
        # suffix multiplicity still weights every surviving row
        levels = [
            RunLevel(lv.lo, lv.cnt, {c: s for c, s in lv.cols.items() if c not in d})
            for lv in self._levels
        ]
        return FactorizedTable(pfx, levels, nrows=self._nrows)

    def project(self, pairs) -> "FactorizedTable":
        pfx = self._prefix.project(
            [(old, new) for old, new in pairs if old in self._prefix._cols]
        )
        levels = [
            RunLevel(
                lv.lo, lv.cnt,
                {new: lv.cols[old] for old, new in pairs if old in lv.cols},
            )
            for lv in self._levels
        ]
        return FactorizedTable(pfx, levels, nrows=self._nrows)

    def cache(self) -> "FactorizedTable":
        self._prefix.cache()
        for lv in self._levels:
            lv.cnt.block_until_ready()
        return self

    # -- prefix-domain execution -------------------------------------------

    def _prefix_evaluable(self, exprs, header) -> bool:
        deps = set()
        for e in exprs:
            deps |= _expr_cols(e, header)
        return not (deps & self._level_col_names()) and deps <= set(
            self._prefix._cols
        )

    def filter(self, expr, header, parameters) -> Table:
        if not self._prefix_evaluable([expr], header):
            return self._flat().filter(expr, header, parameters)
        fault_point("filter")
        try:
            ev = TpuEvaluator(self._prefix, header, parameters)
            ev.n = self._lane_phys
            c = ev.eval(expr)
        except TpuUnsupportedExpr:
            return self._flat().filter(expr, header, parameters)
        if c.kind == OBJ:
            return self._flat().filter(expr, header, parameters)
        keep = J.filter_keep_mask(c.data, c.valid, self._nlanes)
        if bucketing.enabled():
            idx, count = mask_to_idx_bucketed(keep)
        else:
            idx, count = mask_to_idx(keep)
        return self._take_lanes(idx, count)

    def _alias_physical(self, src: str, dst: str) -> Optional["FactorizedTable"]:
        """Bind ``dst`` to the same device column as ``src`` without
        decompressing (``dst`` replaced wherever it already lives);
        ``None`` when ``src`` isn't physically present."""
        pfx_cols = dict(self._prefix._cols)
        pfx_cols.pop(dst, None)
        levels = [dict(lv.cols) for lv in self._levels]
        for d in levels:
            d.pop(dst, None)
        if src in pfx_cols:
            pfx_cols[dst] = pfx_cols[src]
        else:
            for i, lv in enumerate(self._levels):
                if src in lv.cols:
                    levels[i][dst] = lv.cols[src]
                    break
            else:
                return None
        return FactorizedTable(
            TpuTable(pfx_cols, self._nlanes),
            [
                RunLevel(lv.lo, lv.cnt, cols)
                for lv, cols in zip(self._levels, levels)
            ],
            nrows=self._nrows,
        )

    def with_columns(self, items, header, parameters) -> Table:
        # pure aliases of already-materialized columns stay compressed: a
        # suffix-run column projected into a RETURN name is the same runs
        # under a second name (the common RETURN <far>.prop AS x shape)
        out, residual = self, []
        for expr, name in items:
            src = header.column(expr) if expr in header else None
            if src == name and name in out.physical_columns:
                continue
            alias = out._alias_physical(src, name) if src is not None else None
            if alias is None:
                residual.append((expr, name))
            else:
                out = alias
        if not residual:
            return out
        if out is not self:
            return out.with_columns(residual, header, parameters)
        items = residual
        if not self._prefix_evaluable([e for e, _ in items], header):
            return self._flat().with_columns(items, header, parameters)
        new_pfx = self._prefix.with_columns(items, header, parameters)
        aligned = new_pfx._nrows == self._nlanes and all(
            c.kind == OBJ or len(c) == self._lane_phys
            for c in new_pfx._cols.values()
        )
        if not aligned:
            # the prefix path depadded (host fallback) — realign via flat
            return self._flat().with_columns(items, header, parameters)
        return FactorizedTable(new_pfx, self._levels, nrows=self._nrows)

    def with_row_index(self, col: str) -> Table:
        return self._flat().with_row_index(col)

    def explode(self, expr, col: str, header, parameters) -> Table:
        return self._flat().explode(expr, col, header, parameters)

    def join(self, other, kind, join_cols) -> Table:
        return self._flat().join(ensure_flat(other), kind, join_cols)

    def union_all(self, other) -> Table:
        return self._flat().union_all(ensure_flat(other))

    # -- ordering ----------------------------------------------------------

    def _orderable_on_prefix(self, items) -> bool:
        return all(
            c in self._prefix._cols and self._prefix._cols[c].kind != OBJ
            for c, _ in items
        )

    def order_by(self, items: Sequence[Tuple[str, bool]]) -> Table:
        if not items:
            return self
        if not self._orderable_on_prefix(items):
            return self._flat().order_by(items)
        # flat enumeration order is (lane, suffix) and the lexsort is
        # stable, so permuting LANES reproduces the flat sort exactly —
        # ties included — while staying compressed
        t = self._exact_lanes()
        datas = tuple(t._prefix._cols[c].data for c, _ in items)
        valids = tuple(t._prefix._cols[c].valid for c, _ in items)
        kinds = tuple(t._prefix._cols[c].kind for c, _ in items)
        ascs = tuple(bool(asc) for _, asc in items)
        idx = J.order_permutation(datas, valids, kinds, ascs)
        return t._take_lanes(idx, t._nlanes)

    def order_by_limit(
        self, items: Sequence[Tuple[str, bool]], k: int
    ) -> Optional[Table]:
        """ORDER BY + LIMIT without flattening: sort the lanes, then
        decompress only the first ``k`` flat rows. Returns None (caller
        falls back to ``order_by().limit()`` — same result, here) when
        the keys are not prefix columns. Every lane is still gathered in
        sorted order (a lane holds any number of flat rows, none included,
        so the first ``k`` rows are no fixed prefix of the lanes): counted
        as ``full``."""
        if not items or self._nrows == 0 or k == 0:
            return None
        if not self._orderable_on_prefix(items):
            return None
        _obs_trace.note_order_limit("full")
        return self.order_by(items).limit(min(k, self._nrows))

    def skip(self, n: int) -> Table:
        return self._decompress_range(min(n, self._nrows), self._nrows)

    def limit(self, n: int) -> Table:
        return self._decompress_range(0, min(n, self._nrows))

    # -- distinct / aggregation --------------------------------------------

    def distinct(self, cols: Optional[Sequence[str]] = None) -> Table:
        if any(lv.cols for lv in self._levels):
            return self._flat().distinct(cols)
        # no level columns survive projection: distinct rows are distinct
        # PREFIX rows among lanes that carry at least one flat row
        pfx, _, _ = self._nonempty_exact()
        return pfx.distinct(cols)

    def distinct_count(self, cols: Sequence[str]) -> Optional[int]:
        if not cols or set(cols) & self._level_col_names():
            return None
        if not set(cols) <= set(self._prefix._cols):
            return None
        if self._nrows == 0:
            return 0
        pfx, _, _ = self._nonempty_exact()
        return pfx.distinct_count(cols)

    def group(self, by, aggregations, header, parameters) -> Table:
        try:
            got = self._group_factorized(by, aggregations, header, parameters)
        except (TpuUnsupportedExpr, TpuBackendError):
            got = None
        if got is not None:
            return got
        return self._flat().group(by, aggregations, header, parameters)

    def _group_factorized(self, by, aggregations, header, parameters):
        """Grouped aggregation on the compressed form, or None when any
        aggregate is weight-sensitive without a weighted formulation.

        Every lane stands for ``w`` identical flat rows, so count/sum/avg
        aggregate as weighted segment sums (``weighted_segment_partials``)
        while min/max and DISTINCT aggregates are multiplicity-invariant
        and reuse the flat segment machinery on the nonempty prefix. The
        group factorization itself runs over nonempty lanes only — a lane
        with zero suffix rows contributes no group, same as flat."""
        for _, agg in aggregations:
            if not isinstance(agg, E.Agg):
                return None
            name = agg.name.lower()
            if agg.distinct:
                if name not in ("count", "sum", "avg", "min", "max", "collect"):
                    return None
            elif name == "count":
                pass
            elif name in ("sum", "avg", "min", "max"):
                if agg.expr is None:
                    return None
            else:
                # collect repeats per multiplicity; stdev/percentile have
                # no weighted formulation here
                return None
        exprs = [agg.expr for _, agg in aggregations if agg.expr is not None]
        if not self._prefix_evaluable(exprs, header):
            return None
        if any(
            c not in self._prefix._cols or self._prefix._cols[c].kind == OBJ
            for c in by
        ):
            return None
        fault_point("agg")
        if not by and all(
            agg.name.lower() == "count" and agg.expr is None
            for _, agg in aggregations
        ):
            # global count(*): the flat total is already host-known
            return TpuTable(
                {
                    out_col: Column.from_numpy(np.array([self._nrows], np.int64))
                    for out_col, _ in aggregations
                },
                1,
            )
        from ...parallel.agg import weighted_segment_partials

        pfx, w, n = self._nonempty_exact()
        out_cols: Dict[str, Column] = {}
        if by and n > 0:
            order, flags, cnt = pfx._first_occurrence_index(by)
            k = int(cnt)
            seg_j, first_rows = J.group_index(order, flags, k=k)
            by_dev = {
                c: (pfx._cols[c].data, pfx._cols[c].valid, pfx._cols[c].int_flag)
                for c in by
            }
            taken = J.cols_take(by_dev, first_rows)
            for c in by:
                col = pfx._cols[c]
                d, v, fl = taken[c]
                out_cols[c] = Column(col.kind, d, v, col.vocab, int_flag=fl)
        elif by:  # zero nonempty lanes with keys: no groups at all
            return None
        else:  # global aggregation: one group, even over zero rows
            seg_j = jnp.zeros(n, dtype=jnp.int64)
            k = 1
        ev = TpuEvaluator(pfx, header, parameters)
        for out_col, agg in aggregations:
            name = agg.name.lower()
            if agg.expr is None:  # count(*): every flat row counts
                _, wcnt = weighted_segment_partials(None, None, w, seg_j, k)
                out_cols[out_col] = Column(I64, wcnt, None)
                continue
            col = ev.eval(agg.expr)
            if col.kind == OBJ:
                raise TpuUnsupportedExpr("object-valued aggregation input")
            if agg.distinct:
                seg_a, col_a, n_a = pfx._dedup_seg_values(seg_j, col)
                out_cols[out_col] = pfx._segment_agg(
                    name, agg, seg_a, col_a, n_a, k, parameters
                )
                continue
            if name in ("min", "max"):
                out_cols[out_col] = pfx._segment_agg(
                    name, agg, seg_j, col, n, k, parameters
                )
                continue
            # weighted count/sum/avg — match the flat segment semantics
            # (jit_ops.segment_aggregate) value for value
            if name in ("sum", "avg") and (
                col.kind not in (I64, F64) or col.int_flag is not None
            ):
                raise TpuUnsupportedExpr(f"weighted {name} over {col.kind}")
            wsum, wcnt = weighted_segment_partials(
                None if name == "count" else col.data, col.valid, w, seg_j, k
            )
            if name == "count":
                out_cols[out_col] = Column(I64, wcnt, None)
            elif name == "avg":
                out_cols[out_col] = Column(
                    F64, _weighted_avg(wsum, wcnt), _nonzero_mask(wcnt)
                )
            elif col.kind == F64:
                # Cypher sum over no values is the INTEGER 0
                data, iflag = _weighted_sum_f64(wsum, wcnt)
                if not bool(jnp.any(iflag)):
                    iflag = None
                out_cols[out_col] = Column(F64, data, None, int_flag=iflag)
            else:
                out_cols[out_col] = Column(col.kind, wsum, None, col.vocab)
        return TpuTable(out_cols, k)


@jax.jit
def _weighted_avg(wsum, wcnt):
    return wsum.astype(jnp.float64) / jnp.maximum(wcnt, 1)


@jax.jit
def _nonzero_mask(wcnt):
    return wcnt > 0


@jax.jit
def _weighted_sum_f64(wsum, wcnt):
    empty = wcnt == 0
    return jnp.where(empty, 0.0, wsum), empty


def _empty_like(src: Column) -> Column:
    if src.kind == OBJ:
        return Column.from_values([])
    return Column(
        src.kind,
        jnp.zeros((0,) + src.data.shape[1:], src.data.dtype),
        None,
        src.vocab,
    )


def note_factorized(true_rows: int, padded_rows: int, run_count: int) -> None:
    """Stamp the factorized-operator span note: (true flat rows, padded
    lane extent, run count) — ``result.profile()`` and the
    static-vs-runtime agreement coverage read this."""
    _obs_trace.note(
        "factorized",
        {
            "true_rows": int(true_rows),
            "padded_rows": int(padded_rows),
            "run_count": int(run_count),
        },
    )


def ensure_flat(t):
    """Flatten a factorized table to its ``TpuTable`` form (identity on
    anything already flat). Duck-typed so callers need no import."""
    to_flat = getattr(t, "to_flat_table", None)
    return to_flat() if to_flat is not None else t


# every jitted program of this module dispatches under an obs.trace
# ``dispatch`` leaf (last: the decorators above stay plain ``jax.jit``)
_obs_trace.wrap_programs(globals())
