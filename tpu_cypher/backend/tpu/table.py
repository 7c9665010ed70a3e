"""TpuTable: the JAX/TPU columnar Table implementation.

The TPU-native analog of the reference's ``DataFrameTable``/``FlinkTable``
(``SparkTable.scala:55`` / ``FlinkTable.scala:49``): columns are device
arrays (``column.Column``) with validity masks, and every relational hot-path
operator executes on device. Output sizes are data-dependent, so each
size-producing STEP performs one scalar device->host sync (the count — e.g.
a join syncs the build-side valid count, the match total, and outer-pad
counts) and then uses fixed-size device primitives (``jnp.nonzero(size=..)``,
``jnp.repeat(total_repeat_length=..)``); bulk row data never crosses to the
host — the eager-mode analog of the count-then-materialize discipline the
fused kernels use under jit:

* filter        = compiled predicate -> device mask -> count sync ->
                  fixed-size nonzero + gather
* join          = device sort + searchsorted probe (build side lexsorted
                  valid-first); inner/left/right/full outer all on device;
                  extra key pairs become device post-filters; string keys
                  join on unified dictionary codes
* union_all     = columnwise concat (string vocabs unified)
* order_by      = device lexsort over Cypher-orderability keys + gather;
                  under a LIMIT k the gather reads the rows at the
                  permutation's first k entries alone (``order_by_limit``)
* distinct      = stable device lexsort + neighbour-difference flags ->
                  first-occurrence gather
* group         = device lexsort factorization (same equivalence classes as
                  distinct) + segment reductions (``jit_ops.segment_reduce``)
* skip/limit    = contiguous device slices (no gather)
* with_columns  = compiled expressions

Aggregators run on device too: count/sum/avg/min/max (numeric, temporal,
and duration columns), stdev/stdevp, percentileCont/Disc, collect, and the
DISTINCT variants via a device pre-dedup (``_DEVICE_AGGS``). Operations with
no device representation (list values, regex, string concat, exotic
functions, object columns) transparently fall back to the local oracle
backend per expression, keeping full Cypher semantics."""

from __future__ import annotations

from typing import (
    Any,
    Callable,
    Dict,
    Iterator,
    List,
    Mapping,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
)

import numpy as np

import jax.numpy as jnp

from ...api import types as T
from ...runtime import guard as _guard
from ...runtime.faults import fault_point
from ...api.table import Table
from ...api.types import CypherType
from . import bucketing
from . import jit_ops as J
from . import procedures as _procedures  # its counters export from the start
from .column import (
    BOOL,
    DATE,
    DUR,
    F64,
    I64,
    INTEGRAL_KINDS,
    LDT,
    OBJ,
    STR,
    Column,
    TpuBackendError,
    constant_column,
    mask_to_idx,
    mask_to_idx_bucketed,
)
from .compiler import TpuEvaluator, TpuUnsupportedExpr


from ...obs import trace as _obs_trace
from ...obs.metrics import REGISTRY as _OBS_REGISTRY
from ...parallel.mesh import mesh_size as _mesh_size
from ...parallel.mesh import note_decline as _note_mesh_decline

_FALLBACKS = _OBS_REGISTRY.counter(
    "tpu_cypher_fallbacks_total",
    "local-oracle fallbacks / host islands by reason",
    labels=("reason",),
)


class _FallbackCounter:
    """Counts local-oracle fallbacks so host-bound regressions are visible
   .

    Served by the unified obs registry (``tpu_cypher_fallbacks_total``),
    keeping both legacy tiers of the read path: the process-global
    AGGREGATE (``snapshot``/``reset`` — the TCK corpus gate in
    tests/test_fallback_telemetry.py reads this) and CONTEXT-LOCAL scopes
    (``scope``) for per-result attribution — the registry's scopes ride a
    ``contextvars`` stack, so concurrent/interleaved queries (threads,
    asyncio, nested view execution) can never cross-pollute each other's
    ``result.fallbacks``."""

    def record(self, reason: str) -> None:
        _FALLBACKS.inc(reason=reason)

    @property
    def total(self) -> int:
        return sum(self.snapshot().values())

    def reset(self) -> None:
        _FALLBACKS.reset()

    def snapshot(self) -> Dict[str, int]:
        return {
            lbl["reason"]: int(v)
            for lbl, v in _FALLBACKS.items()
            if int(v) > 0
        }

    def scope(self) -> "_FallbackScope":
        """``with FALLBACK_COUNTER.scope() as events:`` — ``events`` is a
        mapping that fills with only the fallbacks recorded in THIS context
        while the scope is open (nested scopes each see their own copy),
        readable during and after the block."""
        return _FallbackScope()


class _FallbackScope(Mapping):
    """Mapping view (reason -> count) over a registry scope, restricted to
    the fallback counter."""

    def __init__(self):
        self._scope = _OBS_REGISTRY.scope()

    def __enter__(self) -> "_FallbackScope":
        self._scope.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._scope.__exit__(*exc)

    def _events(self) -> Dict[str, int]:
        return {
            k: int(v)
            for k, v in self._scope.label_counts(
                "tpu_cypher_fallbacks_total", "reason"
            ).items()
        }

    def __getitem__(self, key: str) -> int:
        return self._events()[key]

    def __iter__(self):
        return iter(self._events())

    def __len__(self) -> int:
        return len(self._events())

    def __repr__(self) -> str:
        return f"_FallbackScope({self._events()!r})"


FALLBACK_COUNTER = _FallbackCounter()


def _fold_valids(valids):
    """AND a tuple of validity masks into one (None = all valid)."""
    out = None
    for v in valids:
        if v is None:
            continue
        out = v if out is None else (out & v)
    return out


def _cols_take_maybe_chunked(dev, idx, first: Optional[int] = None):
    """``jit_ops.cols_take`` (at ``idx[:first]``, cut inside that program)
    — unless the CHUNKED ladder rung is active and the gather is large, in
    which case the index splits into bounded slices gathered independently
    and concatenated, so no single device program allocates the whole
    output at once (the degraded-memory materialize; docs/robustness.md)."""
    chunk = _guard.chunk_rows()
    n = int(idx.shape[0]) if first is None else first
    if chunk is None or n <= chunk:
        return J.cols_take(dev, idx, first=first)
    idx = idx[:n]
    pieces = [
        J.cols_take(dev, idx[start : min(start + chunk, n)])
        for start in range(0, n, chunk)
    ]
    out = {}
    for c in dev:
        datas = [p[c][0] for p in pieces]
        valids = [p[c][1] for p in pieces]
        iflags = [p[c][2] for p in pieces]
        out[c] = (
            jnp.concatenate(datas),
            jnp.concatenate(valids) if valids[0] is not None else None,
            jnp.concatenate(iflags) if iflags[0] is not None else None,
        )
    return out


def ensure_flat(t):
    """Flatten a factorized table (``factorized.FactorizedTable``) to its
    ``TpuTable`` form — identity on anything already flat. Duck-typed on
    ``to_flat_table`` so this module never imports ``factorized`` (which
    imports this one). Every fused-operator input boundary and binary-op
    ``other`` side passes through here: the flatten is admission-guarded,
    so an over-budget decompress surfaces as ``AdmissionRejected``."""
    to_flat = getattr(t, "to_flat_table", None)
    return to_flat() if to_flat is not None else t


class _JoinProbe(NamedTuple):
    """The device equi-join after its count phase (``TpuTable._join_probe``):
    ``total`` match pairs — exact unless ``packed_all_keys`` (a composite
    key packed by ``combine_keys``: hash collisions are screened on the
    pairs); ``pairs()`` materializes ``(left_rows, right_rows)``, tail-padded
    past ``total`` where the lattice rounds."""

    total: int
    pairs: Callable[[], Tuple[Any, Any]]
    packed_all_keys: bool


# key kinds whose one-column probe total needs no look at the pairs
_EXACT_PROBE_KINDS = (I64, BOOL, DATE, LDT)


class TpuTable(Table):
    def __init__(self, cols: Dict[str, Column], nrows: Optional[int] = None):
        self._cols = dict(cols)
        if nrows is None:
            nrows = (
                next(iter(cols.values())).logical_len if cols else 0
            )
        self._nrows = nrows
        self._depadded: Optional["TpuTable"] = None

    # -- sharding-pad handling --------------------------------------------

    def _depad(self) -> "TpuTable":
        """Slice off mesh-sharding pad rows before an eager relational op.

        Ingested tables under an active mesh carry device columns padded to
        a shard multiple (``Column.pad`` phantom tail rows, always invalid).
        The FUSED expand/count paths consume the padded arrays in place —
        ``jit_ops.compact_lookup`` gates on the validity mask, so pad rows
        contribute nothing while the big arrays keep their even
        ``NamedSharding`` layout. Eager relational ops instead see the
        logical rows: this memoized slice is the boundary."""
        if all(c.pad == 0 for c in self._cols.values()):
            return self
        if self._depadded is None:
            # one eager slice per device array of every padded column
            # (data, valid, int_flag): op-by-op programs, not one dispatch
            with _obs_trace.span(
                "depad", kind="step", columns=len(self._cols)
            ):
                self._depadded = TpuTable(
                    {c: col.depad() for c, col in self._cols.items()},
                    self._nrows,
                )
        return self._depadded

    @property
    def _phys(self) -> int:
        """Physical device row count (logical + sharding pad)."""
        return max(
            (len(c) for c in self._cols.values() if c.kind != OBJ),
            default=self._nrows,
        )

    # -- construction -----------------------------------------------------

    @staticmethod
    def from_columns(cols: Dict[str, List[Any]]) -> "TpuTable":
        return TpuTable({c: Column.from_values(v) for c, v in cols.items()})

    @staticmethod
    def from_rows(columns: Sequence[str], rows: Sequence[Sequence[Any]]) -> "TpuTable":
        cols = {c: [r[i] for r in rows] for i, c in enumerate(columns)}
        return TpuTable.from_columns(cols)

    @staticmethod
    def from_numpy(cols: Dict[str, Any]) -> "TpuTable":
        """Bulk construction from numpy arrays (one H2D copy per column)."""
        return TpuTable({c: Column.from_numpy(v) for c, v in cols.items()})

    @classmethod
    def from_arrays(cls, cols: Dict[str, Any]) -> "TpuTable":
        """Mixed construction: numeric/bool numpy arrays take the bulk H2D
        path, anything else (value lists, string/object arrays) decodes per
        value — the ingestion SPI the LDBC loader uses at SF10 scale."""
        out: Dict[str, Column] = {}
        for c, v in cols.items():
            if isinstance(v, np.ndarray) and (
                np.issubdtype(v.dtype, np.number) or v.dtype == np.bool_
            ):
                out[c] = Column.from_numpy(v)
            else:
                out[c] = Column.from_values(list(v))
        return TpuTable(out)

    @staticmethod
    def empty(columns: Sequence[str] = ()) -> "TpuTable":
        return TpuTable(
            {c: Column(I64, jnp.zeros(0, jnp.int64), None) for c in columns}, 0
        )

    @staticmethod
    def unit() -> "TpuTable":
        return TpuTable({}, 1)

    # -- local-oracle fallback --------------------------------------------

    def _to_local(self, _reason: str = "unspecified"):
        from ..local.table import LocalTable

        t = self._depad()
        FALLBACK_COUNTER.record(_reason)
        return LocalTable(
            {c: col.to_values() for c, col in t._cols.items()}, t._nrows
        )

    @staticmethod
    def _from_local(lt) -> "TpuTable":
        return TpuTable(
            {c: Column.from_values(v) for c, v in lt._cols.items()}, lt._nrows
        )

    # -- metadata ---------------------------------------------------------

    @property
    def physical_columns(self) -> List[str]:
        return list(self._cols.keys())

    def column_type(self, col: str) -> CypherType:
        if self._nrows == 0:
            return T.CTVoid
        c = self._cols[col]
        if c.kind == OBJ:
            # O(n) decode — computed once and cached on the (immutable)
            # column so planner metadata probes stay O(1)
            if c._obj_type is None:
                c._obj_type = T.join_types(T.type_of_value(v) for v in c.to_values())
            return c._obj_type
        return c.cypher_type()

    @property
    def size(self) -> int:
        return self._nrows

    def column_values(self, col: str) -> List[Any]:
        # decode the PHYSICAL column and slice host-side: a device depad
        # here would compile one dynamic_slice program per logical row
        # count (defeating shape bucketing on every result delivery); pad
        # rows decode to None and fall off the list slice
        return self._cols[col].to_values()[: self._nrows]

    def rows(self) -> Iterator[Dict[str, Any]]:
        # host-side decode + slice, same rationale as ``column_values``
        decoded = {
            c: col.to_values()[: self._nrows] for c, col in self._cols.items()
        }
        for i in range(self._nrows):
            yield {c: v[i] for c, v in decoded.items()}

    def rows_chunked(self, chunk_rows: int) -> Iterator[List[Dict[str, Any]]]:
        """Yield row dicts in bounded batches of ``chunk_rows`` WITHOUT
        ever materializing the whole decoded result: per chunk, each
        column decodes only its ``[lo, hi)`` slice host-side
        (``Column.to_values_range`` — one cached D2H per column for the
        table's lifetime). The cursor-streaming delivery path lives on
        this, keeping peak host memory at O(chunk) for arbitrarily large
        results."""
        chunk_rows = max(int(chunk_rows), 1)
        for lo in range(0, self._nrows, chunk_rows):
            hi = min(lo + chunk_rows, self._nrows)
            decoded = {
                c: col.to_values_range(lo, hi)
                for c, col in self._cols.items()
            }
            yield [
                {c: v[i] for c, v in decoded.items()} for i in range(hi - lo)
            ]

    # -- simple ops --------------------------------------------------------

    def select(self, cols: Sequence[str]) -> "TpuTable":
        return TpuTable({c: self._cols[c] for c in cols}, self._nrows)

    def rename(self, mapping: Dict[str, str]) -> "TpuTable":
        return TpuTable(
            {mapping.get(c, c): v for c, v in self._cols.items()}, self._nrows
        )

    def drop(self, cols: Sequence[str]) -> "TpuTable":
        d = set(cols)
        return TpuTable(
            {c: v for c, v in self._cols.items() if c not in d}, self._nrows
        )

    def _take(self, idx, first: Optional[int] = None) -> "TpuTable":
        """Gather all columns' device arrays in ONE jitted dispatch (not
        one eager gather per column): the rows at ``idx``, or at its first
        ``first`` entries alone, cut inside that dispatch."""
        n = int(idx.shape[0]) if hasattr(idx, "shape") else len(idx)
        if first is not None:
            n = first = min(first, n)
        dev = {
            c: (col.data, col.valid, col.int_flag)
            for c, col in self._cols.items()
            if col.kind != OBJ
        }
        taken = _cols_take_maybe_chunked(dev, idx, first) if dev else {}
        out: Dict[str, Column] = {}
        for c, col in self._cols.items():
            if col.kind == OBJ:
                out[c] = col.take(idx[:n])
            else:
                d, v, i = taken[c]
                out[c] = Column(col.kind, d, v, col.vocab, int_flag=i)
        return TpuTable(out, n)

    def _take_counted(self, idx, count: int) -> "TpuTable":
        """Bucketed gather: ``idx`` is padded to a shape bucket with
        duplicate indices past the true ``count``; gathered device columns
        come out tail-invalid past ``count`` (``cols_take_counted``), OBJ
        columns gather the exact prefix. The bucketed analog of ``_take`` —
        two tables whose counts share a bucket reuse one compiled gather."""
        size = int(idx.shape[0])
        if size == count:
            return self._take(idx)
        dev = {
            c: (col.data, col.valid, col.int_flag)
            for c, col in self._cols.items()
            if col.kind != OBJ
        }
        taken = J.cols_take_counted(dev, idx, count) if dev else {}
        idx_host = None
        out: Dict[str, Column] = {}
        for c, col in self._cols.items():
            if col.kind == OBJ:
                if idx_host is None:
                    fault_point("compact")
                    idx_host = np.asarray(idx)[:count]
                out[c] = col.take(idx_host)
            else:
                d, v, i = taken[c]
                out[c] = Column(
                    col.kind, d, v, col.vocab, int_flag=i,
                    pad=size - count,
                    pad_synth=col.valid is None or col.pad_synth,
                )
        return TpuTable(out, count)

    def skip(self, n: int) -> "TpuTable":
        t = self._depad()
        if t is not self:
            return t.skip(n)
        n = min(n, self._nrows)
        return TpuTable(
            {c: col.slice(n, self._nrows) for c, col in self._cols.items()},
            self._nrows - n,
        )

    def limit(self, n: int) -> "TpuTable":
        t = self._depad()
        if t is not self:
            return t.limit(n)
        n = min(n, self._nrows)
        return TpuTable({c: col.slice(0, n) for c, col in self._cols.items()}, n)

    def cache(self) -> "TpuTable":
        for col in self._cols.values():
            if col.kind != OBJ:
                col.data.block_until_ready()
        return self

    # -- device compaction helper -----------------------------------------

    _mask_to_idx = staticmethod(mask_to_idx)

    # -- filter ------------------------------------------------------------

    def filter(self, expr, header, parameters) -> "TpuTable":
        fault_point("filter")
        if bucketing.enabled():
            return self._filter_bucketed(expr, header, parameters)
        t = self._depad()
        if t is not self:
            return t.filter(expr, header, parameters)
        keep = self._filter_keep(expr, header, parameters)
        if isinstance(keep, str):
            return self._filter_local(keep, expr, header, parameters)
        idx, _ = self._mask_to_idx(keep)
        return self._take(idx)

    def _filter_local(self, reason: str, expr, header, parameters) -> "TpuTable":
        """The local oracle's filter, counted as a fallback under ``reason``."""
        return self._from_local(
            self._to_local(reason).filter(expr, header, parameters)
        )

    def _filter_keep(self, expr, header, parameters):
        """The rows a filter keeps, as a device mask over the PHYSICAL rows
        (predicate AND its validity; under bucketing AND the row tail, so a
        pad row is never kept) — or, where the predicate has no device
        form, the reason as the fallback counter names it (a ``str``).
        Shared by the rows paths (``filter`` / ``_filter_bucketed``) and
        ``filter_count``."""
        try:
            ev = TpuEvaluator(self, header, parameters)
            if bucketing.enabled():
                ev.n = self._phys
            c = ev.eval(expr)
        except TpuUnsupportedExpr:
            return 'filter:expr'
        if c.kind == OBJ:
            return 'filter:obj-mask'
        if bucketing.enabled():
            return J.filter_keep_mask(c.data, c.valid, self._nrows)
        return J.and_valid_mask(c.data, c.valid)

    def _obj_under_pad(self) -> bool:
        """OBJ columns are host arrays of logical length: a padded table
        carrying one cannot evaluate over its physical rows."""
        return self._phys > self._nrows and any(
            col.kind == OBJ for col in self._cols.values()
        )

    def _filter_bucketed(self, expr, header, parameters) -> "TpuTable":
        """Pad-aware filter: the predicate evaluates over the PHYSICAL
        (bucket/shard-padded) arrays, the keep mask is AND-ed with the
        row-tail validity (pad rows must never survive, whatever the
        predicate computed on their duplicated payload — IS NULL is true on
        them), and the survivor set compacts to a BUCKETED size. OBJ
        columns are host arrays of logical length, so a table carrying one
        takes the exact (depadded) path instead."""
        if self._obj_under_pad():
            t = self._depad()
            return TpuTable.filter(t, expr, header, parameters)
        keep = self._filter_keep(expr, header, parameters)
        if isinstance(keep, str):
            return self._filter_local(keep, expr, header, parameters)
        idx, count = mask_to_idx_bucketed(keep)
        return self._take_counted(idx, count)

    def filter_count(self, expr, header, parameters) -> Optional[int]:
        """Rows ``filter`` would keep, from the keep mask alone: the same
        predicate, validity and row tail, one ``mask_sum`` and its one host
        read — no compaction and no gather of the kept rows. None where
        ``filter`` leaves the device (unsupported expression, OBJ mask, OBJ
        columns under a padded table): the rows path answers unchanged."""
        if bucketing.enabled():
            if self._obj_under_pad():
                return None
        else:
            t = self._depad()
            if t is not self:
                return t.filter_count(expr, header, parameters)
        fault_point("filter")
        keep = self._filter_keep(expr, header, parameters)
        if isinstance(keep, str):
            return None
        return J.mask_count(keep)

    # -- join --------------------------------------------------------------

    def join(self, other: "TpuTable", kind, join_cols) -> "TpuTable":
        other = ensure_flat(other)
        # bucketed mode keeps pads: the device join folds explicit row-tail
        # masks instead (pad rows can never match), so two inputs whose row
        # counts share a bucket reuse one compiled join pipeline
        if not bucketing.enabled():
            t, o = self._depad(), other._depad()
            if t is not self or o is not other:
                return t.join(o, kind, join_cols)
        if kind == "cross":
            n, m = self._nrows, other._nrows
            bucketing.admit(
                n * m,
                9 * max(len(self._cols) + len(other._cols), 1),
                "join",
            )
            li = jnp.repeat(jnp.arange(n), m)
            ri = jnp.tile(jnp.arange(m), n)
            return self._combine(other, li, ri)
        if not join_cols:
            # keyless equi-join (uncorrelated OPTIONAL MATCH and friends):
            # every row matches every row; outer kinds pad when a side is empty
            if kind == "inner" or (self._nrows and other._nrows):
                return self.join(other, "cross", [])
            if kind == "left_outer":
                return self._join_empty_result(other, "left_outer")
            if kind == "right_outer" and other._nrows == 0:
                return self.join(other, "cross", [])
            return self._join_empty_result(other, "full_outer")
        if kind == "right_outer":
            # mirror of left_outer; the flipped _combine emits right-table
            # columns first, so restore canonical (left-first) column order
            flipped = [(r, l) for l, r in join_cols]
            res = other._join_device_or_local(
                self, "left_outer", flipped, swap_sides=True
            )
            ordered = {c: res._cols[c] for c in (*self._cols, *other._cols)}
            return TpuTable(ordered, res._nrows)
        return self._join_device_or_local(other, kind, join_cols, swap_sides=False)

    def _join_device_or_local(self, other, kind, join_cols, swap_sides) -> "TpuTable":
        lcols = [self._cols[l] for l, _ in join_cols]
        rcols = [other._cols[r] for _, r in join_cols]
        if any(c.kind == OBJ for c in lcols + rcols):
            if swap_sides:
                lt = other._to_local('join:obj-keys').join(self._to_local('join:obj-keys'), "right_outer",
                                            [(r, l) for l, r in join_cols])
                return self._from_local(lt)
            lt = self._to_local('join:obj-keys').join(other._to_local('join:obj-keys'), kind, join_cols)
            return self._from_local(lt)
        return self._join_device(other, kind, join_cols)

    def join_count(self, other, kind, join_cols) -> Optional[int]:
        """Rows of an inner equi-join on ONE key column, from the join's
        count phase (``_join_probe``): the sharded tiers stop after their
        first exchange, the one-device join after its probe — no pairs, no
        gathers, no admission for rows that are not built. None wherever
        the number is not exact at that point or the keys leave the device
        path: outer kinds, composite keys (packed keys need the pairs
        post-verified), string, float, mixed and OBJ keys."""
        if kind != "inner" or len(join_cols) != 1:
            return None
        other = ensure_flat(other)
        lk, rk = self._cols[join_cols[0][0]], other._cols[join_cols[0][1]]
        if lk.kind != rk.kind or lk.kind not in _EXACT_PROBE_KINDS:
            return None
        if not bucketing.enabled():
            t, o = self._depad(), other._depad()
            if t is not self or o is not other:
                return t.join_count(o, kind, join_cols)
        fault_point("join")
        return self._join_probe(other, kind, join_cols).total

    def _join_probe(self, other, kind, join_cols) -> Optional["_JoinProbe"]:
        """Key preparation and count phase of the device equi-join, shared
        by ``_join_device`` (which goes on to the pairs) and ``join_count``
        (which stops here). None where the key kinds can never be equal."""
        lk, rk = self._cols[join_cols[0][0]], other._cols[join_cols[0][1]]
        if lk.kind == STR or rk.kind == STR:
            if lk.kind != STR or rk.kind != STR:
                return None
            from .column import _unify_vocab

            lk, rk = _unify_vocab(lk, rk)
        elif lk.kind != rk.kind:
            if {lk.kind, rk.kind} == {I64, F64}:
                # exact mixed numeric equality: casting the int side to f64
                # would collapse ints above 2**53 (graph-tagged ids live at
                # 2**54+) — instead the float side joins as exact int64
                # where it is integral & in range, and never matches elsewhere
                if lk.kind == F64:
                    lk = _float_as_exact_int(lk)
                else:
                    rk = _float_as_exact_int(rk)
            else:  # cross-kind keys never match
                return None
        # validity masks beyond the probe key's own (extra key columns must
        # be non-null to match) — folded on device inside the jitted phases
        l_extra_valid = tuple(
            c.valid
            for c in (self._cols[l] for l, _ in join_cols[1:])
            if c.valid is not None and c.kind != OBJ
        )
        r_extra_valid = tuple(
            c.valid
            for c in (other._cols[r] for _, r in join_cols[1:])
            if c.valid is not None and c.kind != OBJ
        )
        lvalids = l_extra_valid + ((lk.valid,) if lk.valid is not None else ())
        rvalids = r_extra_valid + ((rk.valid,) if rk.valid is not None else ())
        bucketed = bucketing.enabled()
        if bucketed:
            # pad rows (bucket or shard tails) are NOT rows: fold explicit
            # row-tail masks so they can never match, independent of any
            # per-column mask bookkeeping
            if int(lk.data.shape[0]) > self._nrows:
                lvalids = lvalids + (J.row_tail_mask(lk.data, self._nrows),)
            if int(rk.data.shape[0]) > other._nrows:
                rvalids = rvalids + (J.row_tail_mask(rk.data, other._nrows),)
        if (
            kind in ("inner", "left_outer", "full_outer")
            and lk.kind == I64
            and rk.kind == I64
        ):
            # mesh path: the broadcast tier when the build side is small
            # (replicate + local probe, NO collective), else the DELIBERATE
            # hash-repartition join (all_to_all shuffle + per-shard local
            # joins — the engines' shuffled hash join, SparkTable.scala:178)
            # instead of relying on GSPMD to partition a global sort.
            # None = no mesh / bucket overflow. Outer shapes ride the same
            # match pairs: the unmatched-row padding downstream is
            # tier-independent.
            from ...parallel.shuffle import (
                broadcast_join_count,
                combine_keys,
                hash_repartition_join_count,
            )

            lv = _fold_valids(lvalids)
            rv = _fold_valids(rvalids)
            lkd, rkd = lk.data, rk.data
            packed_all_keys = False
            if len(join_cols) > 1 and all(
                self._cols[l].kind == I64 and other._cols[r].kind == I64
                for l, r in join_cols[1:]
            ):
                # composite keys: shuffle/broadcast on ONE mixed key over
                # all columns (avoids first-key blowup when the leading key
                # is low-cardinality); hash collisions are screened by the
                # post-verification of EVERY key column below
                lkd = combine_keys(
                    (lkd,) + tuple(self._cols[l].data for l, _ in join_cols[1:])
                )
                rkd = combine_keys(
                    (rkd,) + tuple(other._cols[r].data for _, r in join_cols[1:])
                )
                packed_all_keys = True
            counted = broadcast_join_count(lkd, lv, rkd, rv)
            if counted is None:
                counted = hash_repartition_join_count(lkd, lv, rkd, rv)
            if counted is not None:
                return _JoinProbe(
                    counted.total, lambda: counted.pairs()[:2], packed_all_keys
                )
        elif _mesh_size() > 1:
            # the sharded tiers join inner and left/full outer shapes on
            # 64-bit integer keys only
            _note_mesh_decline(
                "join",
                "key_kind" if lk.kind != I64 or rk.kind != I64 else "join_kind",
            )
        is_f64 = lk.kind == F64
        is_bool = lk.kind == BOOL
        # phase 1: build side sorted valid-first (one jitted dispatch,
        # one scalar sync for the valid count)
        rd_s, r_order, nvalid_dev = J.join_build(rk.data, rvalids, is_f64=is_f64, is_bool=is_bool)
        nvalid = int(nvalid_dev)
        if bucketed:
            # phases 2+3 at BUCKETED static sizes: the valid count and
            # the match total ride as traced operands, so any inputs
            # whose counts share buckets reuse these compiled programs
            cap = min(
                bucketing.round_size(nvalid), int(r_order.shape[0])
            )
            r_idx_valid, lo, counts, total_dev = J.join_probe_bucketed(
                rd_s, r_order, lk.data, lvalids, nvalid_dev,
                nvalid_cap=cap, is_f64=is_f64, is_bool=is_bool,
            )
            total = int(total_dev)
            return _JoinProbe(
                total,
                lambda: J.join_materialize_counted(
                    r_idx_valid, lo, counts, total_dev,
                    size=bucketing.round_size(total),
                )[:2],
                False,
            )
        # phase 2: probe by binary search (one dispatch, one sync)
        r_idx_valid, lo, counts, total_dev = J.join_probe(
            rd_s, r_order, lk.data, lvalids, nvalid=nvalid, is_f64=is_f64, is_bool=is_bool
        )
        total = int(total_dev)
        # phase 3: materialize match pairs (one dispatch, static total)
        return _JoinProbe(
            total,
            lambda: J.join_materialize(r_idx_valid, lo, counts, total=total),
            False,
        )

    def _join_device(self, other, kind, join_cols) -> "TpuTable":
        """Device sort-probe equi-join (the TPU analog of the engines'
        shuffled hash join, ``SparkTable.scala:178``): the build (right) side
        is lexsorted valid-first-by-key once, the probe side binary-searches
        it (``_join_probe``: the count phase, on the mesh a sharded tier's);
        matches materialize via fixed-size repeat+gather. Multi-key joins
        probe on the first key and post-filter the rest on device."""
        fault_point("join")
        probe = self._join_probe(other, kind, join_cols)
        if probe is None:
            return self._join_empty_result(other, kind)
        total, packed_all_keys = probe.total, probe.packed_all_keys
        bucketed = bucketing.enabled()
        # padded per-output-row cost of the match-pair arrays + the
        # gathered output columns (8B data + 1B mask per column, 2 int64
        # index lanes) — the admission estimate for every join materialize
        join_row_bytes = 16 + 9 * max(len(self._cols) + len(other._cols), 1)
        bucketing.admit(total, join_row_bytes, "join")
        left_rows, right_rows = probe.pairs()
        # match-pair arrays padded past ``total``
        match_bucketed = int(left_rows.shape[0]) != total
        # packed-key matches verify EVERY key column (hash collisions);
        # otherwise the probe key matched exactly and only extras need it
        post_cols = join_cols if packed_all_keys else join_cols[1:]
        if post_cols and total:
            never_match = False
            l_datas, l_valids2, r_datas, r_valids2, kinds = [], [], [], [], []
            for (lcn, rcn) in post_cols:
                lc, rc = self._cols[lcn], other._cols[rcn]
                if lc.kind == STR or rc.kind == STR:
                    if lc.kind != STR or rc.kind != STR:
                        never_match = True
                        continue
                    from .column import _unify_vocab

                    lc, rc = _unify_vocab(lc, rc)
                elif {lc.kind, rc.kind} == {I64, F64}:
                    # same exact mixed numeric equality as the probe key;
                    # recast keys carry match-eligibility in their validity
                    # mask (fractional/NaN floats -> invalid, data 0)
                    if lc.kind == F64:
                        lc = _float_as_exact_int(lc)
                    else:
                        rc = _float_as_exact_int(rc)
                elif lc.kind != rc.kind:
                    never_match = True
                    continue
                l_datas.append(lc.data)
                l_valids2.append(lc.valid)
                r_datas.append(rc.data)
                r_valids2.append(rc.valid)
                kinds.append(lc.kind)
            if never_match:
                left_rows = jnp.zeros(0, jnp.int64)
                right_rows = jnp.zeros(0, jnp.int64)
                total = 0
                match_bucketed = False
            elif kinds:
                keep = J.extra_keys_keep(
                    tuple(l_datas), tuple(l_valids2), tuple(r_datas),
                    tuple(r_valids2), left_rows, right_rows, kinds=tuple(kinds),
                )
                if match_bucketed:
                    # pad lanes duplicate a real pair and might pass the
                    # key check — they are not matches
                    keep = keep & J.row_tail_mask(keep, total)
                if bucketed:
                    idx, total = mask_to_idx_bucketed(keep)
                    left_rows, right_rows = J.tree_take((left_rows, right_rows), idx)
                    match_bucketed = int(idx.shape[0]) != total
                else:
                    idx, _ = self._mask_to_idx(keep)
                    left_rows, right_rows = J.tree_take((left_rows, right_rows), idx)
        nmatched = total if bucketed else int(left_rows.shape[0])
        if kind != "inner" and match_bucketed:
            # outer shapes run the exact unmatched-row machinery: slice the
            # tail-form match pairs to their true count first (one device
            # slice; the outer pads would otherwise interleave with bucket
            # pads and break the tail-pad invariant)
            left_rows = left_rows[:nmatched]
            right_rows = right_rows[:nmatched]
            match_bucketed = False
        left_matched = None
        right_matched = None
        matched_right = right_rows
        if kind in ("left_outer", "full_outer"):
            miss = J.unmatched_mask(left_rows, n=self._nrows)
            miss_idx, nmiss = self._mask_to_idx(miss)
            left_rows, right_rows, right_matched = J.outer_pad_left(
                left_rows, right_rows, miss_idx, nmiss=nmiss, nmatched=nmatched
            )
        if kind == "full_outer":
            rmiss = J.unmatched_mask(matched_right, n=other._nrows)
            rmiss_idx, rnmiss = self._mask_to_idx(rmiss)
            left_rows, right_rows, left_matched, right_matched = J.outer_pad_right(
                left_rows, right_rows, right_matched, rmiss_idx,
                nmiss=rnmiss, ncur=int(left_rows.shape[0]),
            )
        return self._combine(
            other, left_rows, right_rows, right_matched, left_matched,
            count=nmatched if match_bucketed else None,
        )

    def _join_empty_result(self, other: "TpuTable", kind) -> "TpuTable":
        """Key kinds can never be equal: inner = empty, outer = all-null."""
        z = jnp.zeros(0, jnp.int64)
        if kind == "inner":
            return self._combine(other, z, z)
        if kind == "left_outer":
            li = jnp.arange(self._nrows, dtype=jnp.int64)
            return self._combine(
                other, li, jnp.zeros(self._nrows, jnp.int64),
                jnp.zeros(self._nrows, bool), None,
            )
        # full_outer: left rows with null right, then right rows with null left
        nl, nr = self._nrows, other._nrows
        li = jnp.concatenate([jnp.arange(nl, dtype=jnp.int64), jnp.zeros(nr, jnp.int64)])
        ri = jnp.concatenate([jnp.zeros(nl, jnp.int64), jnp.arange(nr, dtype=jnp.int64)])
        rm = jnp.concatenate([jnp.zeros(nl, bool), jnp.ones(nr, bool)])
        lm = jnp.concatenate([jnp.ones(nl, bool), jnp.zeros(nr, bool)])
        return self._combine(other, li, ri, rm, lm)

    def _combine(
        self,
        other: "TpuTable",
        li,
        ri,
        right_in_bounds=None,
        left_in_bounds=None,
        count: Optional[int] = None,
    ) -> "TpuTable":
        """``count``: bucketed inner joins pass the TRUE pair count — the
        index arrays are tail-padded past it, gathered device columns come
        out tail-invalid, OBJ columns gather the exact prefix."""
        out: Dict[str, Column] = {}
        for c in other._cols:
            if c in self._cols:
                raise TpuBackendError(f"Join column collision: {c}")
        size = int(li.shape[0])
        if count is not None and count == size:
            count = None
        for cols, idx, in_bounds in (
            (self._cols, li, left_in_bounds),
            (other._cols, ri, right_in_bounds),
        ):
            # one jitted dispatch per side for all device columns
            dev = {
                c: (col.data, col.valid, col.int_flag)
                for c, col in cols.items()
                if col.kind != OBJ and (in_bounds is None or len(col) > 0)
            }
            if dev and count is not None:
                taken = J.cols_take_counted(dev, idx, count)
            elif dev:
                taken = (
                    _cols_take_maybe_chunked(dev, idx)
                    if in_bounds is None
                    else J.cols_take_or_null(dev, idx, in_bounds)
                )
            else:
                taken = {}
            idx_host = None
            for c, col in cols.items():
                if c in taken:
                    d, v, i = taken[c]
                    if count is not None:
                        out[c] = Column(
                            col.kind, d, v, col.vocab, int_flag=i,
                            pad=size - count,
                            pad_synth=col.valid is None or col.pad_synth,
                        )
                    else:
                        out[c] = Column(col.kind, d, v, col.vocab, int_flag=i)
                elif count is not None:
                    if idx_host is None:
                        idx_host = np.asarray(idx)[:count]
                    out[c] = col.take(idx_host)
                elif in_bounds is None:
                    out[c] = col.take(idx)
                else:
                    out[c] = col.take_or_null(idx, in_bounds)
        n = count if count is not None else size
        return TpuTable(out, n)

    # -- union -------------------------------------------------------------

    def union_all(self, other: "TpuTable") -> "TpuTable":
        other = ensure_flat(other)
        if set(self._cols) != set(other._cols):
            raise TpuBackendError("unionAll column mismatch")
        if bucketing.enabled():
            padded = self._union_all_padded(other)
            if padded is not None:
                return padded
        t, o = self._depad(), other._depad()
        if t is not self or o is not other:
            return t.union_all(o)
        # structurally simple columns (same kind/dtype, shared vocab) concat
        # in ONE jitted dispatch; kind promotion / vocab unification /
        # object columns keep the per-column host path
        simple = {}
        for c, a in self._cols.items():
            b = other._cols[c]
            if (
                a.kind != OBJ
                and a.kind == b.kind
                and a.vocab is b.vocab
                and a.data.dtype == b.data.dtype
            ):
                simple[c] = (a, b)
        out: Dict[str, Column] = {}
        if simple:
            merged = J.cols_concat(
                {c: (a.data, a.valid, a.int_flag) for c, (a, b) in simple.items()},
                {c: (b.data, b.valid, b.int_flag) for c, (a, b) in simple.items()},
            )
            for c, (d, v, i) in merged.items():
                a = self._cols[c]
                out[c] = Column(a.kind, d, v, a.vocab, int_flag=i)
        for c in self._cols:
            if c not in out:
                out[c] = self._cols[c].concat(other._cols[c])
        ordered = {c: out[c] for c in self._cols}
        return TpuTable(ordered, self._nrows + other._nrows)

    def _union_all_padded(self, other: "TpuTable") -> Optional["TpuTable"]:
        """UNION ALL that never leaves the bucket lattice: concatenate the
        PHYSICAL (bucket/shard-padded) arrays and gather both sides'
        logical rows to the front at a bucket-rounded size
        (``jit_ops.cols_union_counted``). The compile key is the
        (physical, physical, rounded-output) shape triple — all lattice
        values — so snapshot scans over a growing base/delta pair reuse
        one compiled union across commits AND compactions, where the
        depadded path would recompile on every logical row-count drift.
        Returns None (caller takes the exact depadded path) unless every
        column on both sides is device-resident and structurally
        aligned."""
        a_n, b_n = self._nrows, other._nrows
        a_phys, b_phys = self._phys, other._phys
        out_n = a_n + b_n
        if out_n == 0:
            return None
        a_cols = dict(self._cols)
        b_cols = dict(other._cols)
        for c, a in a_cols.items():
            b = b_cols[c]
            if a.kind != b.kind and a.kind != OBJ and b.kind != OBJ:
                # same discipline as ``Column.concat``: an all-null side
                # carries no payload (scan alignment fills absent
                # properties with I64 null constants) — adopt the other
                # side's kind instead of losing the one-dispatch path
                if len(b) == 0 or b.is_all_null():
                    b = b_cols[c] = a.null_like(len(b))
                elif len(a) == 0 or a.is_all_null():
                    a = a_cols[c] = b.null_like(len(a))
            if (
                a.kind == OBJ
                or a.kind != b.kind
                or a.vocab is not b.vocab
                or a.data is None
                or b.data is None
                or a.data.dtype != b.data.dtype
                or len(a) != a_phys
                or len(b) != b_phys
            ):
                return None
        # output physical size = SUM of the input physical sizes, not
        # ``round_size(out_n)``: both inputs are already lattice-shaped, so
        # the sum is stable while the logical sum ``out_n`` drifts — the
        # union's compile key then changes only when an INPUT crosses its
        # own bucket, never on a within-bucket row-count change
        out_phys = a_phys + b_phys
        idx = np.zeros(out_phys, np.int64)
        idx[:a_n] = np.arange(a_n, dtype=np.int64)
        idx[a_n:out_n] = a_phys + np.arange(b_n, dtype=np.int64)

        # null-free columns carry ``valid=None`` — but ONLY while the table
        # has pad rows to mark; a table that exactly fills its bucket keeps
        # None. That structural flip would re-key the jit across
        # compactions, so synthesize a concrete mask on the way in and
        # always keep one on the way out: the program shape is then a pure
        # function of the lattice sizes
        def _dev(cols: Dict[str, Column], phys: int):
            return {
                c: (
                    col.data,
                    col.valid
                    if col.valid is not None
                    else jnp.ones(phys, bool),
                    col.int_flag,
                )
                for c, col in cols.items()
            }

        merged = J.cols_union_counted(
            _dev(a_cols, a_phys), _dev(b_cols, b_phys), idx, out_n
        )
        pad = out_phys - out_n
        out: Dict[str, Column] = {}
        for c, (d, v, i) in merged.items():
            a, b = a_cols[c], b_cols[c]
            synth = pad > 0 and (a.valid is None or a.pad_synth) and (
                b.valid is None or b.pad_synth
            )
            out[c] = Column(
                a.kind, d, v, a.vocab, int_flag=i, pad=pad, pad_synth=synth,
            )
        return TpuTable({c: out[c] for c in self._cols}, out_n)

    # -- ordering ----------------------------------------------------------

    def order_by_limit(
        self, items: Sequence[Tuple[str, bool]], k: int
    ) -> Optional["TpuTable"]:
        """First ``k`` rows under ORDER BY, row for row what
        ``order_by(items).limit(k)`` gives (ties included): the same stable
        permutation (``jit_ops.order_permutation``), and one batched gather
        of the rows at its first ``min(k, n)`` entries alone, cut inside
        the gather's program — never the sorted table. From
        ``jit_ops.ORDER_TOPK_MIN_ROWS`` rows up, integral keys whose
        measured ranges pack into 62 bits take one ``lax.top_k`` to the
        same indices instead (``_order_topk``). None (the caller sorts,
        then slices) only for what ``order_by`` does not sort on the device
        (OBJ keys), an empty table, no items, or ``k == 0``."""
        t = self._depad()
        if t is not self:
            return t.order_by_limit(items, k)
        if not items or self._nrows == 0 or k == 0:
            return None
        k = min(k, self._nrows)
        idx, path = self._order_topk(items, k), "topk"
        if idx is None:
            idx, path = self._order_permutation(items), "sort_prefix"
        if idx is None:
            return None
        _obs_trace.note_order_limit(path)
        return self._take(idx, first=k)

    def _order_topk(self, items: Sequence[Tuple[str, bool]], k: int):
        """The first ``k`` (rounded up to a power of two: one program a
        binade of LIMITs) row indices in ORDER BY order as one top-k over
        the keys packed into an int64 rank, after a min/max probe and one
        blocking read; None under ``jit_ops.ORDER_TOPK_MIN_ROWS`` rows
        (the sort is sooner there, the table stands above the constant),
        for a key that is not integral (ints, bools, dictionary-coded
        strings), or if the ranges, a null bit a key and the row index pass
        62 bits."""
        n = self._nrows
        cols = [self._cols[c] for c, _ in items]
        if n < J.ORDER_TOPK_MIN_ROWS or any(
            c.kind not in INTEGRAL_KINDS for c in cols
        ):
            return None
        datas = tuple(c.data for c in cols)
        valids = tuple(c.valid for c in cols)
        mins, maxs = J.order_minmax(datas, valids)
        with _obs_trace.sync("order"):
            mins = np.asarray(mins)
            maxs = np.asarray(maxs)
        los, spans = zip(*(
            (lo, hi - lo) if lo <= hi else (0, 0)  # all-null: no data bit
            for lo, hi in zip(mins.tolist(), maxs.tolist())
        ))
        bits = [span.bit_length() for span in spans]
        # a null bit a key, the row index as the stable tiebreak
        if sum(bits) + len(cols) + (n - 1).bit_length() > 62:
            return None
        ascs = tuple(bool(asc) for _, asc in items)
        return J.order_topk(
            datas, valids, ascs,
            np.array(los, np.int64), np.array(spans, np.int64), np.array(bits, np.int64),
            k=min(n, 1 << (k - 1).bit_length()),
        )

    def order_by(self, items: Sequence[Tuple[str, bool]]) -> "TpuTable":
        """ORDER BY: one jitted stable lexsort under Cypher orderability
        (``jit_ops.order_permutation``) + one batched gather of every row;
        OBJ keys sort on the local backend."""
        if not items:
            return self
        if self._pad_aware():
            # the sort runs over the physical rows, the pad rows last, and
            # the gather keeps the bucket: a row count that moves within
            # its bucket (a grouped aggregation's groups) keeps its program
            idx = self._order_permutation(items, live=self._nrows)
            return self._take_counted(idx, self._nrows)
        t = self._depad()
        if t is not self:
            return t.order_by(items)
        idx = self._order_permutation(items)
        if idx is None:
            return self._from_local(self._to_local('order_by:obj-keys').order_by(items))
        return self._take(idx)

    def _order_permutation(self, items: Sequence[Tuple[str, bool]], live=None):
        """Row indices in ORDER BY order; None if a key has no device
        representation. ``live``: the logical row count of a tail-padded
        table, whose pad rows then sort last."""
        cols = [self._cols[c] for c, _ in items]
        if any(c.kind == OBJ for c in cols):
            return None
        return J.order_permutation(
            tuple(c.data for c in cols),
            tuple(c.valid for c in cols),
            tuple(c.kind for c in cols),
            tuple(bool(asc) for _, asc in items),
            live,
        )

    def _pad_aware(self) -> bool:
        """Whether an operator may run over this table's physical rows and
        keep its bucket: bucketing on, a tail of pad rows, one device, and
        every column on the device at the physical length."""
        phys = self._phys
        return (
            bucketing.enabled()
            and phys > self._nrows
            and _mesh_size() <= 1
            and all(
                c.kind != OBJ and len(c) == phys for c in self._cols.values()
            )
        )

    def _head(self, rows: int) -> "TpuTable":
        """The first ``rows`` physical rows of every column (``nrows <= rows
        <= phys``) in one program, the rows past ``nrows`` still pad."""
        if rows == self._phys:
            return self
        cut = J.cols_head(
            {c: (col.data, col.valid, col.int_flag) for c, col in self._cols.items()},
            rows,
        )
        pad = rows - self._nrows
        out = {}
        for c, col in self._cols.items():
            d, v, i = cut[c]
            synth = col.pad_synth and pad > 0
            out[c] = Column(
                col.kind, d, None if col.pad_synth and not pad else v,
                col.vocab, int_flag=i, _np_cache=col._np_cache,
                _np_valid=col._np_valid, pad=pad, pad_synth=synth,
            )
        return TpuTable(out, self._nrows)

    # -- distinct / group factorization ------------------------------------

    def _first_occurrence_index(
        self, on: Sequence[str], extra_keys: Sequence[Any] = ()
    ) -> Tuple[Any, Any, Any]:
        """Stable device lexsort over Cypher-equivalence keys -> (sorted row
        order, first-of-group flags over the sorted order, device group
        count). The stable sort makes the first row of each equal-key run
        the earliest original row of that group. ``extra_keys`` prepend
        higher-priority key arrays (e.g. a group index for DISTINCT
        aggregates). All-integer key sets whose ranges fit 63 bits are
        PACKED into one key — one sort instead of k (group order is
        irrelevant here: callers renumber by first occurrence). Two cached
        jitted dispatches: a min/max probe (host decides packing) + the
        sort itself (``jit_ops.equivalence_sort``)."""
        datas = tuple(self._cols[c].data for c in on)
        valids = tuple(self._cols[c].valid for c in on)
        kinds = tuple(self._cols[c].kind for c in on)
        extras = tuple(extra_keys)
        pack = self._equiv_pack(datas, valids, kinds, extras, min_keys=2)
        return J.equivalence_sort(datas, valids, extras, kinds, pack=pack)

    def _equiv_pack(self, datas, valids, kinds, extras, min_keys: int):
        """Int-packing spec for the equivalence keys over these columns, or
        None when not all-integer / ranges exceed 63 bits / fewer than
        ``min_keys`` keys (one jitted min/max probe + one scalar sync)."""
        packable = (
            self._nrows > 0
            and all(k in INTEGRAL_KINDS for k in kinds)
            and all(jnp.issubdtype(e.dtype, jnp.integer) or e.dtype == jnp.bool_
                    for e in extras)
        )
        if not packable:
            return None
        # key count is a pure host function of the inputs (1 data key per
        # column + a null-class key when it has a validity mask + extras):
        # short-circuit BEFORE paying the device min/max probe
        nkeys = len(extras) + sum(1 if v is None else 2 for v in valids)
        if nkeys < min_keys:
            return None
        mins, maxs = J.equivalence_minmax(datas, valids, extras, kinds)
        with _obs_trace.sync("agg"):
            mins = np.asarray(mins)
            maxs = np.asarray(maxs)
        bits = [(int(hi) - int(lo)).bit_length() for lo, hi in zip(mins, maxs)]
        if sum(bits) > 63:
            return None
        return tuple((int(lo), b) for lo, b in zip(mins, bits))

    def distinct_count(self, cols: Sequence[str]) -> Optional[int]:
        t = self._depad()
        if t is not self:
            return t.distinct_count(cols)
        """Number of distinct rows over ``cols`` WITHOUT materializing them
        (count-over-distinct pushdown). All-integer key sets take a packed
        VALUES-ONLY sort (``lax.sort`` without an argsort payload is ~5x
        cheaper on TPU); everything else reuses the first-occurrence
        factorization."""
        if not cols or any(self._cols[c].kind == OBJ for c in cols):
            return None
        if self._nrows == 0:
            return 0
        # the pushed-down distinct count syncs one scalar: an agg-class
        # device sync, so it gets the agg fault site (injection + deadline)
        fault_point("agg")
        on = list(cols)
        datas = tuple(self._cols[c].data for c in on)
        valids = tuple(self._cols[c].valid for c in on)
        kinds = tuple(self._cols[c].kind for c in on)
        pack = self._equiv_pack(datas, valids, kinds, (), min_keys=1)
        if pack is not None:
            sharded = self._sharded_distinct_count(datas, valids, kinds, pack)
            if sharded is not None:
                return sharded
            cnt = J.distinct_count_packed(datas, valids, (), kinds, pack)
        else:
            if _mesh_size() > 1:
                # the exchange routes ONE packed 63-bit key per row
                _note_mesh_decline("distinct", "unpackable")
            # unpackable keys: sort unpacked directly — re-probing min/max
            # via _first_occurrence_index would repeat the device round trip
            _, _, cnt = J.equivalence_sort(datas, valids, (), kinds, pack=None)
        with _obs_trace.sync("agg"):
            return int(cnt)

    def _sharded_distinct_count(self, datas, valids, kinds, pack):
        """Mesh tier of the distinct-count pushdown: hash-repartition the
        packed equivalence keys so equal values meet on one shard, count
        run boundaries per shard, ``psum`` the partials. None when no
        multi-device mesh is active, the ``TPU_CYPHER_MESH_AGG`` gate is
        off, or the shuffle declines (skew overflow / non-addressable
        rows) — the global values-only sort stays the fallback."""
        if _mesh_size() <= 1:
            return None
        from ...utils.config import MESH_AGG

        if MESH_AGG.get().strip().lower() != "auto":
            _note_mesh_decline("distinct", "gate")
            return None
        from ...parallel.shuffle import sharded_distinct_count

        keys = J.equivalence_pack_keys(datas, valids, (), kinds, pack)
        return sharded_distinct_count(keys)

    def distinct(self, cols: Optional[Sequence[str]] = None) -> "TpuTable":
        if bucketing.enabled():
            out = self._distinct_bucketed(cols)
            if out is not None:
                return out
        t = self._depad()
        if t is not self:
            return t.distinct(cols)
        on = list(cols) if cols is not None else self.physical_columns
        if any(self._cols[c].kind == OBJ for c in on):
            return self._from_local(self._to_local('distinct:obj-keys').distinct(on))
        if not on:
            return self.limit(1) if self._nrows > 1 else self
        if self._nrows == 0:
            return self
        order, flags, cnt = self._first_occurrence_index(on)
        first = J.first_occurrence_rows(order, flags, k=int(cnt))
        return self._take(first)

    def _distinct_bucketed(
        self, cols: Optional[Sequence[str]]
    ) -> Optional["TpuTable"]:
        """Pad-aware DISTINCT: the first-occurrence factorization runs over
        the PHYSICAL (bucket/shard-padded) arrays with a prepended
        pad-group key — pad rows sort into trailing groups of their own,
        first flags are then restricted to live rows
        (``jit_ops.live_first_flags``), and the survivor gather lands on a
        BUCKETED static size. Two tables whose distinct counts share a
        bucket reuse one compiled pipeline, so snapshot dedup never
        recompiles across compactions. Returns None (caller takes the
        exact depadded path) when a key is host-resident or a pad-carrying
        table holds OBJ columns the counted gather cannot align."""
        n, phys = self._nrows, self._phys
        on = list(cols) if cols is not None else self.physical_columns
        if not on or n == 0:
            return None
        if any(self._cols[c].kind == OBJ for c in on):
            return None
        if phys > n and any(c.kind == OBJ for c in self._cols.values()):
            return None
        if any(
            c.kind != OBJ and len(c) != phys for c in self._cols.values()
        ):
            return None
        # the pad-group key rides along even when the table exactly fills
        # its bucket (all-False then): dropping it would re-key the sort
        # whenever a compaction lands a table on a bucket boundary
        extras = (np.arange(phys) >= n,)
        order, flags, _ = self._first_occurrence_index(on, extra_keys=extras)
        flags, cnt = J.live_first_flags(order, flags, n)
        with _obs_trace.sync("distinct"):
            cnt = int(cnt)
        first = J.first_occurrence_rows_counted(
            order, flags, cnt, k=bucketing.round_size(cnt)
        )
        return self._take_counted(first, cnt)

    # -- aggregation / projection / explode --------------------------------

    # aggregators the device path handles (durations and other
    # object-valued inputs still use the local oracle)
    _DEVICE_AGGS = frozenset(
        {
            "count",
            "sum",
            "avg",
            "min",
            "max",
            "stdev",
            "stdevp",
            "percentilecont",
            "percentiledisc",
            "collect",
        }
    )
    # DISTINCT runs as a device pre-dedup of (group, value) pairs
    _DISTINCT_AGGS = frozenset({"count", "sum", "avg", "min", "max", "collect"})

    # aggregators the pad-aware grouping takes: each a segment reduction
    # that drops a row whose group id lies past the groups
    _BUCKETED_AGGS = frozenset({"count", "sum", "avg", "min", "max"})

    def group(self, by, aggregations, header, parameters) -> "TpuTable":
        if by and self._pad_aware():
            try:
                out = self._group_bucketed(by, aggregations, header, parameters)
            except (TpuUnsupportedExpr, TpuBackendError):
                out = None
            if out is not None:
                return out
        t = self._depad()
        if t is not self:
            return t.group(by, aggregations, header, parameters)
        try:
            return self._group_device(by, aggregations, header, parameters)
        except (TpuUnsupportedExpr, TpuBackendError):
            lt = self._to_local('group:agg').group(by, aggregations, header, parameters)
            return self._from_local(lt)

    def _group_bucketed(
        self, by, aggregations, header, parameters
    ) -> Optional["TpuTable"]:
        """Pad-aware grouped aggregation: the factorization and the segment
        reductions run over the first ``bucketing.round_fine(nrows)``
        physical rows (at most the bucket), and the groups come out on the
        bucket lattice, tail-padded past their true number. A row count
        that moves a little with the data (a graph's vertices from one draw
        to the next) and a group count that moves within its bucket keep
        every program. None (the caller takes the exact path) for an
        aggregator outside ``_BUCKETED_AGGS``, a DISTINCT one, or an input
        the device holds as objects or durations."""
        from ...ir import expr as E

        for _, agg in aggregations:
            if (
                not isinstance(agg, E.Agg)
                or agg.distinct
                or agg.name.lower() not in self._BUCKETED_AGGS
            ):
                return None
        n = self._nrows
        t = self._head(min(self._phys, bucketing.round_fine(n)))
        ev = TpuEvaluator(t, header, parameters)
        ev.n = t._phys
        inputs = []
        for out_col, agg in aggregations:
            col = None if agg.expr is None else ev.eval(agg.expr)
            if col is not None and col.kind in (OBJ, DUR):
                return None
            inputs.append((out_col, agg, col))
        order, flags, _ = t._first_occurrence_index(by)
        flags, cnt = J.live_first_flags(order, flags, n)
        with _obs_trace.sync("agg"):  # the group count
            k = int(cnt)
        size = bucketing.bucket_of(k)
        seg_j, first_rows = J.group_index_counted(order, flags, n, k, k=size)
        keys = TpuTable({c: t._cols[c] for c in by}, n)
        out_cols = dict(keys._take_counted(first_rows, k)._cols)
        live = J.row_tail_mask(first_rows, k) if size > k else None
        for out_col, agg, col in inputs:
            if col is None:  # count(*): every live row counts
                _obs_trace.note_agg_form(
                    J.segment_aggregate_form("count", seg_j.dtype, size)
                )
                cnt_j, _, _, _ = J.segment_aggregate(
                    seg_j, None, None, seg_j, name="count", kind=I64, k=size
                )
                c = Column(I64, cnt_j, None)
            else:
                c = t._segment_agg(
                    agg.name.lower(), agg, seg_j, col, t._phys, size, parameters
                )
            if live is not None:
                c = Column(
                    c.kind, c.data, live if c.valid is None else c.valid & live,
                    c.vocab, int_flag=c.int_flag, pad=size - k,
                    pad_synth=c.valid is None,
                )
            out_cols[out_col] = c
        return TpuTable(out_cols, k)

    def _group_device(self, by, aggregations, header, parameters) -> "TpuTable":
        """Grouped aggregation as device segment ops: group assignment reuses
        ``distinct``'s device lexsort factorization (null/NaN equivalence
        classes), then count/sum/avg/min/max run as segment reductions
        over the group index (``jit_ops.segment_reduce``: dense over a small
        number of groups, a scatter past it) — the TPU replacement for the
        engines' shuffle aggregate (reference ``Table.group``)."""
        from ...ir import expr as E

        for _, agg in aggregations:
            if not isinstance(agg, E.Agg) or agg.name.lower() not in self._DEVICE_AGGS:
                raise TpuUnsupportedExpr(f"device agg {getattr(agg, 'name', agg)}")
            if agg.distinct and agg.name.lower() not in self._DISTINCT_AGGS:
                raise TpuUnsupportedExpr(f"device agg DISTINCT {agg.name}")
        if any(self._cols[c].kind == OBJ for c in by):
            raise TpuUnsupportedExpr("object-valued group keys")

        n = self._nrows
        out_cols: Dict[str, Column] = {}
        if not by and all(
            isinstance(agg, E.Agg)
            and agg.name.lower() == "count"
            and agg.expr is None
            for _, agg in aggregations
        ):
            # global count(*): the row count is already host-known — no
            # device work at all (the fused count-only expand path ends here)
            return TpuTable(
                {
                    out_col: Column.from_numpy(np.array([n], np.int64))
                    for out_col, _ in aggregations
                },
                1,
            )
        if by and n > 0:
            order, flags, cnt = self._first_occurrence_index(by)
            with _obs_trace.sync("agg"):  # the group count
                k = int(cnt)
            # group ids renumbered in first-occurrence order (= the local
            # oracle), one jitted dispatch
            seg_j, first_rows = J.group_index(order, flags, k=k)
            by_dev = {
                c: (self._cols[c].data, self._cols[c].valid, self._cols[c].int_flag)
                for c in by
            }
            taken = J.cols_take(by_dev, first_rows)
            for c in by:
                col = self._cols[c]
                d, v, i = taken[c]
                out_cols[c] = Column(col.kind, d, v, col.vocab, int_flag=i)
        elif by:  # zero rows with keys: no groups at all
            return self._from_local(
                self._to_local('group:zero-rows').group(by, aggregations, header, parameters)
            )
        else:  # global aggregation: one group, even over zero rows
            seg_j = jnp.zeros(n, dtype=jnp.int64)
            k = 1

        ev = TpuEvaluator(self, header, parameters)
        for out_col, agg in aggregations:
            name = agg.name.lower()
            if agg.expr is None:  # count(*): every row counts
                _obs_trace.note_agg_form(
                    J.segment_aggregate_form("count", seg_j.dtype, k)
                )
                # the jitted count over the group index alone (the values
                # give it their length, no validity: every row is counted)
                cnt, _, _, _ = J.segment_aggregate(
                    seg_j, None, None, seg_j, name="count", kind=I64, k=k
                )
                out_cols[out_col] = Column(I64, cnt, None)
                continue
            col = ev.eval(agg.expr)
            if col.kind == OBJ:
                raise TpuUnsupportedExpr("object-valued aggregation input")
            if agg.distinct:
                seg_a, col_a, n_a = self._dedup_seg_values(seg_j, col)
            else:
                seg_a, col_a, n_a = seg_j, col, n
            out_cols[out_col] = self._segment_agg(
                name, agg, seg_a, col_a, n_a, k, parameters
            )
        return TpuTable(out_cols, k)

    def _dedup_seg_values(self, seg_j, col: Column):
        """Device dedup of (group, value) pairs for DISTINCT aggregates:
        first occurrence per Cypher-equivalence class within each group
        (the group index is the leading sort key), original row order
        preserved (collect DISTINCT emits values in first-appearance order,
        like the oracle)."""
        tmp = TpuTable({"__v": col}, int(seg_j.shape[0]))
        order, flags, cnt = tmp._first_occurrence_index(["__v"], extra_keys=[seg_j])
        rows = J.first_occurrence_rows(order, flags, k=int(cnt))
        return J.tree_take(seg_j, rows), col.take(rows), int(rows.shape[0])

    def _segment_agg(
        self, name: str, agg, seg_j, col: Column, n: int, k: int, parameters=None
    ) -> Column:
        """One aggregator over (value column, group index) as ONE jitted
        segment program (``jit_ops.segment_aggregate``) — the TPU analog of
        the engines' shuffle aggregate plus the codegen UDAFs (reference
        ``PercentileUdafs.scala``, ``TemporalUdafs.scala``)."""
        fault_point("agg")
        data, kind, vocab = col.data, col.kind, col.vocab
        if name == "collect":
            # output is host lists by definition; only this column decodes
            valid_np = np.asarray(col.valid) if col.valid is not None else None
            vals = col.to_values()
            seg_np = np.asarray(seg_j)
            lists: List[List[Any]] = [[] for _ in range(k)]
            for i in range(n):
                if valid_np is None or valid_np[i]:
                    lists[int(seg_np[i])].append(vals[i])
            from .column import _obj_array

            return Column(OBJ, _obj_array(lists), None)
        if kind == DUR:
            # device duration aggregates (reference TemporalUdafs.scala)
            if name not in ("count", "sum", "avg", "min", "max"):
                raise TpuUnsupportedExpr(f"{name} over durations")
            if n == 0:
                if name == "count":
                    return Column(I64, jnp.zeros(k, jnp.int64), None)
                if name == "sum":
                    # empty duration sum is INTEGER 0 in the oracle — a
                    # kind the device duration column cannot hold
                    raise TpuUnsupportedExpr("sum over empty duration group")
                return Column(
                    DUR, jnp.zeros((k, 3), jnp.int64), jnp.zeros(k, bool)
                )
            out_data, any_valid, cnt = J.segment_duration_agg(
                data, col.valid, seg_j, k=k, name=name
            )
            if name == "count":
                return Column(I64, cnt, None)
            all_valid = int(J.mask_sum(any_valid)) == k
            if name == "sum" and not all_valid:
                raise TpuUnsupportedExpr("sum over empty duration group")
            return Column(DUR, out_data, None if all_valid else any_valid)
        if name in ("sum", "avg", "stdev", "stdevp") and kind not in (I64, F64):
            raise TpuUnsupportedExpr(f"{name} over {kind}")
        if name in ("percentilecont", "percentiledisc"):
            return self._segment_percentile(name, agg, seg_j, col, n, k, parameters)
        # mesh tier: integer aggregates as per-shard partials tree-combined
        # over the mesh — integer combines are exact, so the sharded
        # result is bit-identical to single-device (floats keep the global
        # path; see parallel/agg.py)
        if (
            kind in (I64, BOOL)
            and col.int_flag is None
            and (kind == I64 or name in ("count", "min", "max"))
        ):
            from ...parallel.agg import sharded_segment_agg

            mesh_out = sharded_segment_agg(
                data, col.valid, seg_j, name, kind == BOOL, k
            )
            if mesh_out is not None:
                out_data, out_valid = mesh_out
                if name == "count":
                    return Column(I64, out_data, None)
                out_kind = F64 if name == "avg" else kind
                return Column(out_kind, out_data, out_valid, vocab)
        elif _mesh_size() > 1:
            # a float combine is not associative: the global path keeps it
            _note_mesh_decline("agg", "not_integer")
        # kernel tier: the Pallas masked segment reduce when eligible
        # (dispatch falls back to the plain ``segment_aggregate``; see
        # backend/tpu/pallas/aggregate.py)
        from .pallas import segment_aggregate

        plain_ran = []

        def plain():
            plain_ran.append(True)
            return J.segment_aggregate(
                data, col.valid, col.int_flag, seg_j, name=name, kind=kind, k=k
            )

        out_data, out_valid, out_iflag, iflag_any = segment_aggregate(
            data, col.valid, col.int_flag, seg_j, name=name, kind=kind, k=k,
            plain=plain,
        )
        if plain_ran:  # an aggregator the kernel took is in its own counter
            _obs_trace.note_agg_form(
                J.segment_aggregate_form(name, data.dtype, k)
            )
        if name == "count":
            return Column(I64, out_data, None)
        if out_iflag is not None and not bool(iflag_any):
            out_iflag = None  # canonical metadata: no integer rows at all
        out_kind = F64 if name in ("avg", "stdev", "stdevp") else kind
        return Column(out_kind, out_data, out_valid, vocab, int_flag=out_iflag)

    def _segment_percentile(
        self, name: str, agg, seg_j, col: Column, n: int, k: int, parameters=None
    ) -> Column:
        """percentileCont/Disc as a jitted segment-sorted gather (reference
        ``PercentileUdafs.scala`` sorts per group on the JVM)."""
        from ...ir import expr as E

        if not agg.extra:
            raise TpuUnsupportedExpr("percentile without fraction")
        pe = agg.extra[0]
        if isinstance(pe, E.Lit):
            p = pe.value
        elif isinstance(pe, E.Param):
            p = (parameters or {}).get(pe.name)
        else:
            raise TpuUnsupportedExpr("non-literal percentile fraction")
        if not isinstance(p, (int, float)) or not 0 <= float(p) <= 1:
            # let the oracle raise the proper CypherTypeError
            raise TpuUnsupportedExpr("percentile fraction out of range")
        p = float(p)
        fault_point("agg")
        data, kind, vocab = col.data, col.kind, col.vocab
        if kind in (OBJ, BOOL, DATE, LDT, DUR):
            # STR stays: percentileDisc over order-preserving dictionary
            # codes is a device sort+gather; temporal kinds keep the
            # oracle's type-error semantics
            raise TpuUnsupportedExpr(f"percentile over {kind}")
        if name == "percentilecont" and kind not in (I64, F64):
            raise TpuUnsupportedExpr("percentileCont over non-numeric")
        if kind == F64 and bool(J.any_nan_valid(data, col.valid)):
            raise TpuUnsupportedExpr("percentile over NaN values")
        out, out_valid, order, pos = J.segment_percentile(
            data, col.valid, seg_j, p, name=name, k=k
        )
        if name == "percentiledisc":
            iflag = None
            if n and kind == F64 and col.int_flag is not None:
                iflag = J.take_take(col.int_flag, order, pos)
            return Column(kind, out, out_valid, vocab, int_flag=iflag)
        return Column(F64, out, out_valid)

    def with_columns(self, items, header, parameters) -> "TpuTable":
        phys = self._phys
        if phys > self._nrows:
            from ...ir import expr as E

            if all(isinstance(e, E.Lit) for e, _ in items):
                # scan alignment adds literal columns (HasLabel flags,
                # absent-property nulls) to freshly ingested tables; build
                # them at PHYSICAL length with the shared pad mask so the
                # sharded layout survives to the fused expand path
                # (depadding here would un-shard every scan)
                # ONLY a synthesized-for-padding mask qualifies: a nullable
                # column's mask carries genuine null holes that must not
                # leak into the new literal columns
                mask = next(
                    (
                        c.valid
                        for c in self._cols.values()
                        if c.kind != OBJ and c.pad > 0 and c.pad_synth
                        and c.valid is not None
                    ),
                    None,
                )
                out = dict(self._cols)
                pad = phys - self._nrows
                for e, col in items:
                    c = constant_column(e.value, phys)
                    if e.value is None or mask is None:
                        # null constants are already all-invalid; without a
                        # shared mask fall back to the constant as-is
                        out[col] = Column(
                            c.kind, c.data, c.valid, c.vocab, pad=pad,
                            pad_synth=False,
                        )
                    else:
                        out[col] = Column(
                            c.kind, c.data, mask, c.vocab, pad=pad,
                            pad_synth=True,
                        )
                return TpuTable(out, self._nrows)
            if bucketing.enabled() and not any(
                c.kind == OBJ for c in self._cols.values()
            ):
                # pad-aware evaluation (same discipline as
                # ``_filter_bucketed``): expressions run over the PHYSICAL
                # bucket/shard-padded arrays — one compiled program per
                # bucket instead of one per logical row count — and the new
                # columns mark their pad tail invalid
                try:
                    ev = TpuEvaluator(self, header, parameters)
                    ev.n = phys
                    out = dict(self._cols)
                    pad = phys - self._nrows
                    new_cols = []
                    for expr, col in items:
                        c = ev.eval(expr)
                        if c.kind == OBJ:
                            raise TpuUnsupportedExpr(
                                "host column at physical size"
                            )
                        new_cols.append((col, c))
                    for col, c in new_cols:
                        live = J.row_tail_mask(c.data, self._nrows)
                        valid = live if c.valid is None else c.valid & live
                        out[col] = Column(
                            c.kind, c.data, valid, c.vocab,
                            int_flag=c.int_flag, pad=pad,
                            pad_synth=c.valid is None,
                        )
                    return TpuTable(out, self._nrows)
                except TpuUnsupportedExpr:
                    pass  # host fallback below needs the exact rows anyway
            t = self._depad()
            return t.with_columns(items, header, parameters)
        out = dict(self._cols)
        try:
            ev = TpuEvaluator(self, header, parameters)
            for expr, col in items:
                out[col] = ev.eval(expr)
            return TpuTable(out, self._nrows)
        except TpuUnsupportedExpr:
            lt = self._to_local('with_columns:expr').with_columns(items, header, parameters)
            return self._from_local(lt)

    def project(self, pairs) -> "TpuTable":
        return TpuTable({new: self._cols[old] for old, new in pairs}, self._nrows)

    def with_row_index(self, col: str) -> "TpuTable":
        t = self._depad()
        if t is not self:
            return t.with_row_index(col)
        out = dict(self._cols)
        out[col] = Column(I64, jnp.arange(self._nrows, dtype=jnp.int64), None)
        return TpuTable(out, self._nrows)

    def explode(self, expr, col: str, header, parameters) -> "TpuTable":
        t = self._depad()
        if t is not self:
            return t.explode(expr, col, header, parameters)
        """UNWIND: only the LIST column itself is host-decoded (lists are
        host objects by definition); every other column stays on device and
        is flattened with one device gather over the repeat index."""
        lists = TpuEvaluator(self, header, parameters).eval(expr).to_values()
        idx: List[int] = []
        values: List[Any] = []
        for i, lst in enumerate(lists):
            if lst is None:
                continue  # UNWIND null produces no rows
            if not isinstance(lst, (list, tuple)):
                idx.append(i)
                values.append(lst)
                continue
            for v in lst:
                idx.append(i)
                values.append(v)
        take = jnp.asarray(np.array(idx, dtype=np.int64))
        out = {c: c_.take(take) for c, c_ in self._cols.items()}
        out[col] = Column.from_values(values)
        return TpuTable(out, len(idx))

    def __repr__(self) -> str:
        return f"TpuTable({self._nrows} rows, cols={self.physical_columns})"

    # -- planner capability hooks (fused CSR expand path) -------------------

    @staticmethod
    def plan_expand_fastpath(planner, op, lhs, rhs, classic):
        from .expand_op import plan_expand_fastpath

        return plan_expand_fastpath(planner, op, lhs, rhs, classic)

    @staticmethod
    def plan_expand_into_fastpath(planner, op, in_plan, classic):
        from .expand_op import plan_expand_into_fastpath

        return plan_expand_into_fastpath(planner, op, in_plan, classic)

    @staticmethod
    def plan_var_expand_fastpath(planner, op, lhs, rhs, classic):
        from .expand_op import plan_var_expand_fastpath

        return plan_var_expand_fastpath(planner, op, lhs, rhs, classic)

    @staticmethod
    def plan_optional_expand_fastpath(planner, op, lhs, rhs, classic):
        from .expand_op import plan_optional_expand_fastpath

        return plan_optional_expand_fastpath(planner, op, lhs, rhs, classic)

    @staticmethod
    def plan_multiway_intersect_fastpath(planner, op, in_plan, classic):
        from .wcoj import plan_multiway_intersect_fastpath

        return plan_multiway_intersect_fastpath(planner, op, in_plan, classic)

    @staticmethod
    def plan_filter_fastpath(planner, op, child):
        from .expand_op import plan_filter_fastpath

        return plan_filter_fastpath(planner, op, child)

    @staticmethod
    def run_procedure(proc, graph, ctx, table, id_col, out_col, args) -> "TpuTable":
        """A procedure call's values (``relational/procedures.py``): one
        device program (``procedures.run``)."""
        return _procedures.run(
            proc, graph, ctx, ensure_flat(table), id_col, out_col, args
        )


def _float_as_exact_int(c: Column) -> Column:
    """An F64 key column recast for EXACT equality against int64 keys:
    rows where the float is integral and inside the int64 range become that
    integer; all other rows (fractional, NaN, inf, out of range) become
    invalid and so never match."""
    f = c.data
    integral = (
        (f == jnp.floor(f)) & (f >= -(2.0**63)) & (f < 2.0**63) & ~jnp.isnan(f)
    )
    data = jnp.where(integral, f, 0.0).astype(jnp.int64)
    valid = c.valid_mask() & integral
    return Column(I64, data, valid)
