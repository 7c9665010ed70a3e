"""Expr -> JAX compiler: typed expressions over device columns.

The TPU analog of the reference's SQL expression mappers
(``FlinkSQLExprMapper.scala:48`` / ``SparkSQLExprMapper.scala``): each Expr
becomes vectorized jnp ops over ``Column``s with (data, valid) null masks and
Kleene three-valued logic on booleans.

String functions run in VOCAB SPACE: columns are dictionary-encoded with an
order-preserving vocabulary, so an elementwise string function is O(|vocab|)
host work producing a lookup table, then one device gather remaps the codes
— row count never touches the host.

Expressions with no device representation (list values, paths, exotic
functions) evaluate as narrow HOST ISLANDS: only the columns the expression
actually references are decoded, the local-oracle evaluator computes the one
output column, and everything else stays on device. ``TpuUnsupportedExpr``
escapes only when even the island cannot run."""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional

import numpy as np

import jax.numpy as jnp

from ...api import types as T
from ...errors import reraise_if_device as _reraise_if_device
from ...ir import expr as E
from ...obs import trace as _obs_trace
from .column import (
    BOOL,
    DATE,
    DUR,
    F64,
    I64,
    LDT,
    LT,
    OBJ,
    STR,
    ZDT,
    ZT,
    Column,
    InexactPromotionError,
    TpuBackendError,
    _NULL_CODE,
    constant_column,
)


class TpuUnsupportedExpr(TpuBackendError):
    pass


def _temporal_range_gate(out, mid, lo, hi, vm, mid_scale=1, extra_bad=None):
    """Python datetimes span years [1, 9999]; device temporal arithmetic
    beyond that must raise the oracle's typed range error, not silently
    hold a proleptic value. The oracle raises at the MONTH step, so the
    month-shifted intermediate (``mid``, in days — scaled when ``out`` is
    in micros) is probed too. ONE any() sync; a violation routes the
    expression to the host island where the oracle raises."""
    if not out.shape[0]:
        return
    probe = jnp.where(vm, out, lo)
    probe_mid = jnp.where(vm, mid, lo // mid_scale)
    bad = (
        (probe < lo)
        | (probe > hi)
        | (probe_mid < lo // mid_scale)
        | (probe_mid > hi // mid_scale)
    )
    if extra_bad is not None:
        bad = bad | extra_bad
    # tpulint: allow[host-sync] reason=eligibility probe whose failure mode IS the host-island fallback; a device fault here degrades identically
    if bool(jnp.any(bad)):
        raise TpuUnsupportedExpr("temporal arithmetic needs the host island")


# functions that must evaluate per row (never const-fold / vocab-map)
_NONDETERMINISTIC = frozenset({"rand", "randomuuid"})


# ---------------------------------------------------------------------------
# jitted-evaluation cache
#
# Eager evaluation dispatches (and on first sight compiles) one program per
# primitive — see jit_ops — so a WHERE predicate of 20 primitives was
# dispatch-bound. The whole expression evaluation is instead TRACED into one
# cached jitted program keyed by (expression, header mapping, column
# layouts, params, row count). Tracing reuses ``_eval_device`` verbatim —
# identical semantics by construction; anything that needs host data during
# evaluation (object columns, data-dependent probes, nondeterministic
# functions) raises at trace time and the key is marked failed, so those
# expressions permanently take the eager/host-island path.
# ---------------------------------------------------------------------------

_EVAL_JIT_CACHE: Dict[Any, Any] = {}
_EVAL_JIT_FAILED = object()
_EVAL_JIT_CACHE_MAX = 4096
# vocab contents are part of the trace (string literals resolve to codes,
# vocab maps bake LUT constants), so they must be part of the key — bounded
# to keep key hashing O(small)
_EVAL_JIT_MAX_VOCAB = 1024

# warn when a host island runs over at least this many rows (0 disables)
from ...utils.config import ISLAND_WARN_ROWS


class _ShimTable:
    """Minimal table stand-in holding traced Columns during jit tracing.
    Deliberately EXCLUDES object columns: any access raises KeyError at
    trace time, failing the cache entry (their host content would
    otherwise be baked into the program as a stale constant)."""

    __slots__ = ("_cols", "size")

    def __init__(self, cols, size):
        self._cols = cols
        self.size = size


class TpuEvaluator:
    def __init__(self, table, header, parameters: Dict[str, Any]):
        self.table = table
        self.header = header
        self.params = parameters or {}
        self.n = table.size

    # ------------------------------------------------------------------

    def eval(self, expr: E.Expr) -> Column:
        if isinstance(self.table, _ShimTable):
            # inside a trace: no nested jit, no host islands — any failure
            # must escape so the cache entry is marked failed and the
            # expression re-runs on the real eager path
            return self._eval_device(expr)
        got = self._eval_jitted(expr)
        if got is not None:
            return got
        try:
            return self._eval_device(expr)
        except (TpuUnsupportedExpr, InexactPromotionError):
            return self._host_island(expr)

    # -- jit cache -----------------------------------------------------

    def _jit_cache_key(self, expr: E.Expr):
        """(key, device column dict, referenced params) or Nones when not
        cacheable."""
        if isinstance(self.table, _ShimTable):
            return None, None, None  # already tracing
        param_names: List[str] = []
        sub_vars: List[E.Expr] = []
        subs: List[E.Expr] = []

        # a loop, not a closure that calls itself: such a closure is a
        # reference cycle, and what it holds (here the evaluator, so its
        # table's device columns) then outlives the call until the cyclic
        # collector happens to run
        stack = [expr]
        while stack:  # pre-order, children left to right
            e = stack.pop()
            subs.append(e)
            if isinstance(e, E.Param):
                param_names.append(e.name)
            if isinstance(e, E.Var):
                sub_vars.append(e)
            stack.extend(reversed(getattr(e, "children", ()) or ()))
        # only the REFERENCED params feed the key and the closure (a cached
        # entry must not pin an unrelated 100MB parameter for the process
        # lifetime)
        used_params = {}
        pkey = []
        for name in sorted(set(param_names)):
            v = self.params.get(name)
            try:
                hash(v)
            except TypeError:
                return None, None, None  # unhashable param: stay eager
            # type tag: 1 == True == 1.0 under Python equality, but the
            # traced constant bakes the Cypher value's type (same reason
            # Lit has a custom __eq__/__hash__)
            pkey.append((name, type(v).__name__, v))
            used_params[name] = v
        # only the expression's dependency columns feed the trace: unrelated
        # columns changing layout must not recompile it, and their vocabs
        # must not be hashed per eval. A dependency the walk missed shows up
        # as a KeyError at trace time -> entry marked failed -> eager path.
        deps = set(self._dependency_columns(expr))
        dep_cols = {
            c: col
            for c, col in self.table._cols.items()
            if c in deps and col.kind != OBJ
        }
        ckey = []
        for c, col in sorted(dep_cols.items()):
            if col.vocab is not None and len(col.vocab) > _EVAL_JIT_MAX_VOCAB:
                return None, None, None
            ckey.append(
                (
                    c,
                    col.kind,
                    str(col.data.dtype),
                    tuple(col.data.shape),
                    col.valid is None,
                    col.int_flag is None,
                    tuple(col.vocab) if col.vocab is not None else None,
                )
            )
        # header slice relevant to THIS expression: its subexpressions plus
        # every header expr of any mentioned variable (the same closure
        # _dependency_columns uses — covers derived probes like id(v)).
        # Unrelated header growth must not miss the cache.
        hset = set()
        if self.header is not None:
            for s in subs:
                col = self.header.get(s)
                if col is not None:
                    hset.add((s, col))
            for v in sub_vars:
                try:
                    for e in self.header.expressions_for(v):
                        c = self.header.get(e)
                        if c is not None:
                            hset.add((e, c))
                except Exception:  # fault-ok: host-side header walk, no
                    # device work can fault here.
                    # An unresolvable variable must DISABLE caching, not
                    # silently narrow the key (a narrower key could replay
                    # a program traced under a different header mapping)
                    return None, None, None
        key = (expr, self.n, tuple(ckey), tuple(pkey), frozenset(hset))
        try:
            hash(key)
        except TypeError:  # pragma: no cover - unhashable literal payloads
            return None, None, None
        return key, dep_cols, used_params

    def _eval_jitted(self, expr: E.Expr) -> Optional[Column]:
        key, dep_cols, used_params = self._jit_cache_key(expr)
        if key is None:
            return None
        entry = _EVAL_JIT_CACHE.get(key)
        if entry is _EVAL_JIT_FAILED:
            return None
        cols_in = {
            c: (col.data, col.valid, col.int_flag)
            for c, col in dep_cols.items()
        }
        if entry is None:
            import jax

            kinds = {c: (col.kind, col.vocab) for c, col in dep_cols.items()}
            header, params, n = self.header, used_params, self.n
            meta: Dict[str, Any] = {}

            def fn(ci):
                cols = {
                    c: Column(
                        kinds[c][0], d, v, kinds[c][1], int_flag=i
                    )
                    for c, (d, v, i) in ci.items()
                }
                ev = TpuEvaluator(_ShimTable(cols, n), header, params)
                out = ev._eval_device(expr)
                meta["kind"] = out.kind
                meta["vocab"] = out.vocab
                return out.data, out.valid, out.int_flag

            # a name of its own in device traces: jit_eval_<expression>
            fn.__name__ = f"eval_{type(expr).__name__.lower()}"
            fn = _obs_trace.program(jax.jit(fn))
            if len(_EVAL_JIT_CACHE) >= _EVAL_JIT_CACHE_MAX:
                _EVAL_JIT_CACHE.clear()
            try:
                data, valid, iflag = fn(cols_in)
            except Exception as exc:  # fault-ok: trace failures fall back
                # to the eager path — but a genuine device fault (OOM,
                # device lost) must surface typed, not vanish into a
                # silently-slower evaluation
                _reraise_if_device(exc, site="eval")
                _EVAL_JIT_CACHE[key] = _EVAL_JIT_FAILED
                return None
            _EVAL_JIT_CACHE[key] = (fn, meta)
            return Column(meta["kind"], data, valid, meta["vocab"], int_flag=iflag)
        fn, meta = entry
        try:
            data, valid, iflag = fn(cols_in)
        except Exception as exc:  # fault-ok: late trace failure falls back
            _reraise_if_device(exc, site="eval")
            _EVAL_JIT_CACHE[key] = _EVAL_JIT_FAILED
            return None
        return Column(meta["kind"], data, valid, meta["vocab"], int_flag=iflag)

    def _host_island(self, expr: E.Expr) -> Column:
        """Evaluate ONE expression via the local oracle over only its
        dependency columns; the rest of the table stays device-resident
        (vs the old wholesale table fallback). Islands over large tables
        make the whole query host-bound, so crossing
        ``TPU_CYPHER_ISLAND_WARN_ROWS`` emits a one-line warning naming the
        expression — visible in logs long before a profile is taken."""
        from ..local.eval import Evaluator as LocalEvaluator
        from ..local.table import LocalTable
        from .table import FALLBACK_COUNTER

        FALLBACK_COUNTER.record(f"island:{type(expr).__name__}")
        warn_rows = ISLAND_WARN_ROWS.get()
        if warn_rows and self.n >= warn_rows:
            import warnings

            warnings.warn(
                f"host-island evaluation of {type(expr).__name__} over "
                f"{self.n} rows — this expression has no device "
                f"implementation and will bound query throughput "
                f"(TPU_CYPHER_ISLAND_WARN_ROWS={warn_rows})",
                RuntimeWarning,
                stacklevel=2,
            )
        deps = self._dependency_columns(expr)
        cols = {c: self.table._cols[c].to_values() for c in deps}
        lt = LocalTable(cols, self.n)
        vals = LocalEvaluator(lt, self.header, self.params).evaluate(expr)
        col = Column.from_values(vals)
        if col.data is not None and int(col.data.shape[0]) > self.n:
            # pad-invariant: ``from_values`` bucket-pads its ingest, but an
            # island column re-enters a table whose physical row count is
            # authoritative — a longer column would desync from row-aligned
            # device state built at table size (e.g. the group segment
            # index). Pads are always tail rows, so a slice restores it.
            col = col.slice(0, self.n)
        return col

    def _dependency_columns(self, expr: E.Expr) -> List[str]:
        """Physical columns a host island must decode: header-mapped
        subexpressions, plus every column owned by any entity/path variable
        mentioned (element materialization reads them all)."""
        out: Dict[str, None] = {}
        tcols = self.table._cols

        stack = [expr]  # a loop for ``_jit_cache_key``'s reason
        while stack:  # pre-order, children left to right
            e = stack.pop()
            col = self.header.get(e) if self.header is not None else None
            if col is not None and col in tcols:
                out[col] = None
                if not isinstance(e, E.Var):
                    continue  # mapped non-var: children irrelevant
            if isinstance(e, E.Var) and self.header is not None:
                if self.header.has_path(e.name):
                    # path materialization walks entity columns; decode all
                    for c in tcols:
                        out[c] = None
                    continue
                for sub in self.header.expressions_for(e):
                    c = self.header.get(sub)
                    if c is not None and c in tcols:
                        out[c] = None
            stack.extend(reversed(getattr(e, "children", ()) or ()))
        return list(out)

    def _eval_device(self, expr: E.Expr) -> Column:
        col = self.header.get(expr) if self.header is not None else None
        if col is not None and col in self.table._cols:
            return self.table._cols[col]

        if isinstance(expr, E.Lit):
            return constant_column(expr.value, self.n)
        if isinstance(expr, E.Param):
            return constant_column(self.params.get(expr.name), self.n)
        if isinstance(expr, E.PrefixId):
            inner = self.eval(expr.expr)
            if inner.kind != I64:
                raise TpuUnsupportedExpr("prefix on non-id column")
            return Column(I64, inner.data | (jnp.int64(expr.tag) << 54), inner.valid)
        if isinstance(expr, E.IsNull):
            inner = self.eval(expr.expr)
            return Column(BOOL, ~inner.valid_mask(), None)
        if isinstance(expr, E.IsNotNull):
            inner = self.eval(expr.expr)
            return Column(BOOL, inner.valid_mask(), None)
        if isinstance(expr, E.Not):
            inner = self._as_bool(self.eval(expr.expr))
            return Column(BOOL, ~inner.data, inner.valid)
        if isinstance(expr, E.Ands):
            return self._connective(expr.exprs, is_and=True)
        if isinstance(expr, E.Ors):
            return self._connective(expr.exprs, is_and=False)
        if isinstance(expr, E.Xor):
            l = self._as_bool(self.eval(expr.lhs))
            r = self._as_bool(self.eval(expr.rhs))
            valid = _and_valid(l, r)
            return Column(BOOL, l.data ^ r.data, valid)
        if isinstance(expr, (E.Equals, E.Neq)):
            return self._equality(expr)
        if isinstance(
            expr, (E.LessThan, E.LessThanOrEqual, E.GreaterThan, E.GreaterThanOrEqual)
        ):
            return self._comparison(expr)
        if isinstance(expr, E.In):
            return self._in(expr)
        if isinstance(expr, E.Neg):
            inner = self.eval(expr.expr)
            if inner.kind == DUR:
                return Column(DUR, -inner.data, inner.valid)
            if inner.kind not in (I64, F64):
                raise TpuUnsupportedExpr("negate non-numeric")
            return Column(inner.kind, -inner.data, inner.valid)
        if isinstance(expr, E.ArithmeticExpr):
            return self._arith(expr)
        if isinstance(expr, E.CaseExpr):
            return self._case(expr)
        if isinstance(expr, E.FunctionCall):
            return self._function(expr)
        if isinstance(expr, (E.StartsWith, E.EndsWith, E.Contains, E.RegexMatch)):
            return self._string_predicate(expr)
        if isinstance(expr, E.Property):
            # dynamic property access reaching here is an accessor on a
            # computed value; temporal columns answer on device (the
            # reference's TemporalUdfs run these on executors)
            return self._temporal_accessor(self.eval(expr.expr), expr.key)
        raise TpuUnsupportedExpr(type(expr).__name__)

    def _device_truncate(self, fn_name: str, unit: str, arg: E.Expr) -> Column:
        from .temporal import US_PER_DAY, truncate_days, truncate_ldt_micros

        inner = self.eval(arg)
        to_date = fn_name == "date.truncate"
        if inner.kind == DATE:
            if not to_date and unit not in (
                "day", "week", "month", "quarter", "year",
            ):
                raise TpuUnsupportedExpr("ldt truncate of a date (host path)")
            out = truncate_days(unit, inner.data)
            if out is None:
                raise TpuUnsupportedExpr(f"truncate unit {unit}")
            if to_date:
                return Column(DATE, out.astype(jnp.int32), inner.valid)
            return Column(LDT, out * US_PER_DAY, inner.valid)
        if inner.kind == LDT:
            if to_date:
                days = truncate_days(
                    unit if unit != "day" else "day",
                    jnp.floor_divide(inner.data.astype(jnp.int64), US_PER_DAY),
                )
                if days is None or unit in ("hour", "minute", "second",
                                            "millisecond", "microsecond"):
                    raise TpuUnsupportedExpr(f"truncate unit {unit}")
                return Column(DATE, days.astype(jnp.int32), inner.valid)
            out = truncate_ldt_micros(unit, inner.data)
            if out is None:
                raise TpuUnsupportedExpr(f"truncate unit {unit}")
            return Column(LDT, out, inner.valid)
        raise TpuUnsupportedExpr(f"truncate over {inner.kind}")

    def _temporal_accessor(self, inner: Column, key: str) -> Column:
        """Calendar-field accessors over device temporal columns: branch-free
        civil-calendar math on the VPU (``backend.tpu.temporal``)."""
        from .temporal import date_accessor, split_ldt, time_accessor

        k = key.lower()
        if inner.kind == DATE:
            out = date_accessor(k, inner.data)
            if out is None:
                raise TpuUnsupportedExpr(f"date accessor {key!r}")
            return Column(I64, out, inner.valid)
        if inner.kind == LDT:
            days, tod = split_ldt(inner.data)
            out = date_accessor(k, days)
            if out is None:
                out = time_accessor(k, tod)
            if out is None:
                raise TpuUnsupportedExpr(f"datetime accessor {key!r}")
            return Column(I64, out, inner.valid)
        if inner.kind in (ZDT, ZT, LT):
            from .temporal import US_PER_SECOND, parse_offset_str

            off = parse_offset_str((inner.vocab or ["+00:00"])[0])
            if inner.kind != LT and k in ("timezone", "offset"):
                # column-level offset: one constant dictionary code
                return Column(
                    STR,
                    jnp.zeros(self.n, jnp.int32),
                    inner.valid,
                    [(inner.vocab or ["+00:00"])[0]],
                )
            if inner.kind != LT and k == "offsetminutes":
                return Column(
                    I64, jnp.full(self.n, off // 60, jnp.int64), inner.valid
                )
            if inner.kind != LT and k == "offsetseconds":
                return Column(
                    I64, jnp.full(self.n, off, jnp.int64), inner.valid
                )
            if inner.kind == ZDT and k == "epochseconds":
                return Column(
                    I64,
                    jnp.floor_divide(inner.data, US_PER_SECOND),
                    inner.valid,
                )
            if inner.kind == ZDT and k == "epochmillis":
                return Column(
                    I64, jnp.floor_divide(inner.data, 1000), inner.valid
                )
            # civil fields read the LOCAL clock: shift the UTC lane by the
            # column offset
            local = inner.data + (0 if inner.kind == LT else off * US_PER_SECOND)
            if inner.kind == ZDT:
                days, tod = split_ldt(local)
                out = date_accessor(k, days)
                if out is None:
                    out = time_accessor(k, tod)
            else:
                from .temporal import US_PER_DAY

                out = time_accessor(k, local % US_PER_DAY)
            if out is None:
                raise TpuUnsupportedExpr(f"temporal accessor {key!r}")
            return Column(I64, out, inner.valid)
        if inner.kind == DUR:
            # integer component functions of (months, days, total micros) —
            # the device mirror of ir.functions.DURATION_ACCESSORS
            m, d, us = inner.data[:, 0], inner.data[:, 1], inner.data[:, 2]
            acc = {
                "years": lambda: m // 12,
                "months": lambda: m,
                "monthsofyear": lambda: m % 12,
                "weeks": lambda: d // 7,
                "days": lambda: d,
                "hours": lambda: us // (3_600 * 1_000_000),
                "minutes": lambda: us // (60 * 1_000_000),
                "seconds": lambda: us // 1_000_000,
                "milliseconds": lambda: us // 1_000,
                "microseconds": lambda: us,
            }.get(k)
            if acc is None:
                raise TpuUnsupportedExpr(f"duration accessor {key!r}")
            return Column(I64, acc().astype(jnp.int64), inner.valid)
        raise TpuUnsupportedExpr(f"property access on {inner.kind}")

    # -- vocab-space string ops -----------------------------------------
    #
    # STR columns are dictionary codes over an order-preserving vocab, so an
    # elementwise string function = transform the (small) vocab on host,
    # then ONE device gather remaps codes. O(|vocab|) host, O(n) device.

    def _vocab_outs_str(self, col: Column, outs: List[Optional[str]]) -> Column:
        vocab = col.vocab or []
        new_vocab = sorted({o for o in outs if o is not None})
        index = {s: i for i, s in enumerate(new_vocab)}
        lut = np.array(
            [index[o] if o is not None else _NULL_CODE for o in outs]
            + [_NULL_CODE],
            dtype=np.int32,
        )
        safe = jnp.where(col.data >= 0, col.data, len(vocab))
        codes = jnp.take(jnp.asarray(lut), safe)
        valid = col.valid_mask() & (codes != _NULL_CODE)
        if col.valid is None and _NULL_CODE not in lut[:-1]:
            valid = None
        return Column(STR, codes, valid, new_vocab)

    def _vocab_map_scalar(self, col: Column, fn, kind: str) -> Column:
        return self._vocab_outs_scalar(col, [fn(s) for s in (col.vocab or [])], kind)

    def _vocab_outs_scalar(self, col: Column, outs: List[Any], kind: str) -> Column:
        """outs: one int/float/bool/None per vocab entry (None = null)."""
        vocab = col.vocab or []
        dtype = {I64: np.int64, F64: np.float64, BOOL: np.bool_}[kind]
        ok = np.array([o is not None for o in outs] + [False], dtype=bool)
        vals = np.array(
            [o if o is not None else 0 for o in outs] + [0], dtype=dtype
        )
        safe = jnp.where(col.data >= 0, col.data, len(vocab))
        data = jnp.take(jnp.asarray(vals), safe)
        valid = col.valid_mask() & jnp.take(jnp.asarray(ok), safe)
        return Column(kind, data, valid)

    def _string_predicate(self, expr) -> Column:
        pat = self._const_value(expr.rhs)
        l = self.eval(expr.lhs)
        if pat is None:
            # null pattern: null everywhere
            return Column(BOOL, jnp.zeros(self.n, bool), jnp.zeros(self.n, bool))
        if pat is self._NOT_CONST or not isinstance(pat, str):
            raise TpuUnsupportedExpr("non-constant string pattern")
        if l.kind != STR:
            if l.is_all_null():
                return Column(BOOL, jnp.zeros(self.n, bool), jnp.zeros(self.n, bool))
            raise TpuUnsupportedExpr(f"string predicate over {l.kind}")
        if isinstance(expr, E.StartsWith):
            fn = lambda s: s.startswith(pat)
        elif isinstance(expr, E.EndsWith):
            fn = lambda s: s.endswith(pat)
        elif isinstance(expr, E.Contains):
            fn = lambda s: pat in s
        else:
            rx = re.compile(pat)
            fn = lambda s: rx.fullmatch(s) is not None
        return self._vocab_map_scalar(l, fn, BOOL)

    # ------------------------------------------------------------------

    def _as_bool(self, c: Column) -> Column:
        if c.kind != BOOL:
            raise TpuUnsupportedExpr(f"expected boolean, got {c.kind}")
        return c

    def _connective(self, exprs, is_and: bool) -> Column:
        cols = [self._as_bool(self.eval(e)) for e in exprs]
        vals = [c.data for c in cols]
        valids = [c.valid_mask() for c in cols]
        if is_and:
            # false if any (valid & ~val); true if all (valid & val)
            any_false = jnp.zeros(self.n, bool)
            all_true = jnp.ones(self.n, bool)
            for v, m in zip(vals, valids):
                any_false = any_false | (m & ~v)
                all_true = all_true & (m & v)
            return Column(BOOL, all_true, any_false | all_true)
        any_true = jnp.zeros(self.n, bool)
        all_false = jnp.ones(self.n, bool)
        for v, m in zip(vals, valids):
            any_true = any_true | (m & v)
            all_false = all_false & (m & ~v)
        return Column(BOOL, any_true, any_true | all_false)

    def _coerce_pair(self, l: Column, r: Column):
        if l.kind == r.kind:
            if l.kind == STR:
                from .column import _unify_vocab

                return _unify_vocab(l, r)
            return l, r
        if {l.kind, r.kind} == {I64, F64}:
            return l.cast_f64(), r.cast_f64()
        raise TpuUnsupportedExpr(f"compare {l.kind} vs {r.kind}")

    def _element_ids(self, e: E.Expr) -> Optional[Column]:
        """The id column of a node or relationship variable (an element is
        its id: two variables bind the same element where their ids are
        equal), or None for any other expression."""
        if not isinstance(e, E.Var) or self.header is None:
            return None
        try:
            v = self.header.var(e.name)
        except (KeyError, ValueError):
            return None
        m = v.cypher_type.material if v.cypher_type is not None else None
        if not isinstance(m, (T.CTNodeType, T.CTRelationshipType)):
            return None
        if self.header.has_path(e.name):
            return None
        col = self.header.get(self.header.id_expr(v))
        return self.table._cols.get(col) if col is not None else None

    def _equality(self, expr) -> Column:
        ids = self._element_ids(expr.lhs), self._element_ids(expr.rhs)
        if None not in ids:
            l, r = ids
        else:
            l, r = self.eval(expr.lhs), self.eval(expr.rhs)
        if OBJ in (l.kind, r.kind):
            raise TpuUnsupportedExpr("equality on object columns")
        if l.kind == DUR and r.kind == DUR:
            # component-wise (normalized storage makes this Duration.__eq__)
            eq = jnp.all(l.data == r.data, axis=1)
            valid = _and_valid(l, r)
            return Column(BOOL, ~eq if isinstance(expr, E.Neq) else eq, valid)
        if DUR in (l.kind, r.kind):
            eq = jnp.zeros(self.n, bool)  # cross-kind equality is False
            valid = _and_valid(l, r)
            return Column(BOOL, ~eq if isinstance(expr, E.Neq) else eq, valid)
        try:
            l, r = self._coerce_pair(l, r)
            eq = l.data == r.data
        except TpuUnsupportedExpr:
            # cross-kind equality (e.g. string vs int) is False, not error
            eq = jnp.zeros(self.n, bool)
        valid = _and_valid(l, r)
        if isinstance(expr, E.Neq):
            eq = ~eq
        return Column(BOOL, eq, valid)

    def _comparison(self, expr) -> Column:
        l, r = self.eval(expr.lhs), self.eval(expr.rhs)
        if OBJ in (l.kind, r.kind):
            raise TpuUnsupportedExpr("comparison on object columns")
        if l.kind == BOOL and r.kind == BOOL:
            # false < true
            l = Column(I64, l.data.astype(jnp.int64), l.valid)
            r = Column(I64, r.data.astype(jnp.int64), r.valid)
        try:
            l, r = self._coerce_pair(l, r)
        except TpuUnsupportedExpr:
            # cross-kind ordering (1 < 'a') is NULL in openCypher
            return Column(BOOL, jnp.zeros(self.n, bool), jnp.zeros(self.n, bool))
        if isinstance(expr, E.LessThan):
            v = l.data < r.data
        elif isinstance(expr, E.LessThanOrEqual):
            v = l.data <= r.data
        elif isinstance(expr, E.GreaterThan):
            v = l.data > r.data
        else:
            v = l.data >= r.data
        valid = _and_valid(l, r)
        if l.kind == F64:
            nan = jnp.isnan(l.data) | jnp.isnan(r.data)
            v = jnp.where(nan, False, v)
        return Column(BOOL, v, valid)

    def _in(self, expr) -> Column:
        if not isinstance(expr.rhs, E.ListLit) or not all(
            isinstance(i, E.Lit) for i in expr.rhs.items
        ):
            raise TpuUnsupportedExpr("IN on non-literal list")
        values = [i.value for i in expr.rhs.items]
        if not values:
            # x IN [] is the empty disjunction: false for EVERY x, null
            # included (the null-propagation below must not see this case)
            return Column(BOOL, jnp.zeros(self.n, bool), None)
        l = self.eval(expr.lhs)
        if l.kind == I64 and any(isinstance(v, float) for v in values):
            # cross-type numeric equality: 23 IN [23.0] is true
            l = l.cast_f64()
        if l.kind == I64:
            cand = [v for v in values if isinstance(v, int) and not isinstance(v, bool)]
            arr = jnp.asarray(np.array(cand, dtype=np.int64)) if cand else None
        elif l.kind == F64:
            cand = [
                float(v)
                for v in values
                if isinstance(v, (int, float)) and not isinstance(v, bool)
            ]
            arr = jnp.asarray(np.array(cand, dtype=np.float64)) if cand else None
        elif l.kind == STR:
            vocab = l.vocab or []
            idx = {s: i for i, s in enumerate(vocab)}
            cand = [idx[v] for v in values if isinstance(v, str) and v in idx]
            arr = jnp.asarray(np.array(cand, dtype=np.int32)) if cand else None
        else:
            raise TpuUnsupportedExpr(f"IN over {l.kind}")
        has_null_item = any(v is None for v in values)
        if arr is None:
            hit = jnp.zeros(self.n, bool)
        else:
            hit = jnp.isin(l.data, arr)
        valid = l.valid_mask()
        if has_null_item:
            # null list element: non-hits become unknown
            valid = valid & hit
        return Column(BOOL, hit & valid, valid)

    def _temporal_dur_operands(self, expr, l, r, kinds):
        """Shared preamble of the temporal +/- duration device paths: match
        the (temporal, duration) operand shape for the given temporal
        ``kinds``, force eager evaluation (the bound checks below raise
        data-dependently, which a traced program cannot), split operands,
        and negate the duration for Subtract. None = not this shape."""
        is_t_dur = l.kind in kinds and r.kind == DUR
        is_dur_t = (
            isinstance(expr, E.Add) and l.kind == DUR and r.kind in kinds
        )
        if not isinstance(expr, (E.Add, E.Subtract)) or not (
            is_t_dur or is_dur_t
        ):
            return None
        if isinstance(self.table, _ShimTable):
            raise TpuUnsupportedExpr("temporal arithmetic is eager")
        t, dur = (l, r) if is_t_dur else (r, l)
        months = dur.data[:, 0]
        ddays = dur.data[:, 1]
        dmic = dur.data[:, 2]
        if isinstance(expr, E.Subtract):
            months, ddays, dmic = -months, -ddays, -dmic
        return t, months, ddays, dmic, _and_valid(l, r)

    def _arith(self, expr) -> Column:
        l, r = self.eval(expr.lhs), self.eval(expr.rhs)
        if l.kind == DUR and r.kind == DUR:
            # duration +/- duration: component-wise (reference
            # CalendarInterval.add; the micros column renormalizes at
            # decode via Duration.__init__)
            if isinstance(expr, (E.Add, E.Subtract)):
                out = (
                    l.data + r.data
                    if isinstance(expr, E.Add)
                    else l.data - r.data
                )
                return Column(DUR, out, _and_valid(l, r))
            raise TpuUnsupportedExpr(f"{type(expr).__name__} on durations")
        # temporal +/- duration on device (oracle: eval._add_duration —
        # months with day clamp, then days, then the time remainder).
        # DATE stays a host island: its result type is data-dependent
        # (a sub-day remainder demotes to a datetime per row).
        got = self._temporal_dur_operands(expr, l, r, (DATE, ZT, LT))
        if got is not None:
            from .temporal import (
                US_PER_DAY,
                add_duration_micros,
                encode_date,
            )
            import datetime as _dt

            t, months, ddays, dmic, valid = got
            if t.kind in (ZT, LT):
                # time/localtime: only sub-day components apply, the clock
                # wraps modulo 24h, the offset is unchanged (the oracle's
                # _add_duration_time; months/days are whole days = 0 mod
                # 24h). The ZT lane is signed UNWRAPPED local-minus-offset
                # micros: wrap on the LOCAL clock, then re-subtract the
                # offset ((data + off + dmic) mod day - off)
                off_us = 0
                if t.kind == ZT:
                    from .temporal import US_PER_SECOND, parse_offset_str

                    off_us = (
                        parse_offset_str((t.vocab or ["+00:00"])[0])
                        * US_PER_SECOND
                    )
                out = (t.data + off_us + dmic) % US_PER_DAY - off_us
                return Column(t.kind, out, valid, t.vocab)
            # DATE + duration: the oracle demotes to a datetime when a
            # sub-day remainder survives — a data-dependent result TYPE the
            # column model cannot hold, so only whole-day durations stay on
            # device (one any() sync; the host island handles the rest)
            out_us, mid_days = add_duration_micros(
                t.data.astype(jnp.int64) * US_PER_DAY, months, ddays, dmic
            )
            days = out_us // US_PER_DAY
            lo_d = encode_date(_dt.date(1, 1, 1))
            hi_d = encode_date(_dt.date(9999, 12, 31))
            # sub-day remainders on VALID rows: the oracle demotes those to
            # datetimes — a result type the column cannot hold — so they
            # join the out-of-range probes in ONE fused island-routing sync
            vm = (
                valid
                if valid is not None
                else jnp.ones(days.shape[0], bool)
            )
            subday = jnp.where(vm, dmic, 0) % US_PER_DAY != 0
            _temporal_range_gate(
                days, mid_days, lo_d, hi_d, vm, extra_bad=subday
            )
            return Column(DATE, days.astype(jnp.int32), valid)
        got = self._temporal_dur_operands(expr, l, r, (LDT, ZDT))
        if got is not None:
            from .temporal import (
                US_PER_DAY,
                US_PER_SECOND,
                add_duration_micros,
                encode_ldt,
                parse_offset_str,
            )
            import datetime as _dt

            t, months, ddays, dmic, valid = got
            off = 0
            local = t.data
            if t.kind == ZDT:
                # the arithmetic runs on the LOCAL clock (Python aware
                # datetime + timedelta semantics); the offset is unchanged
                off = parse_offset_str((t.vocab or ["+00:00"])[0])
                local = t.data + off * US_PER_SECOND
            out, mid_days = add_duration_micros(local, months, ddays, dmic)
            vm = (
                valid
                if valid is not None
                else jnp.ones(out.shape[0], bool)
            )
            lo_us = encode_ldt(_dt.datetime(1, 1, 1))
            hi_us = encode_ldt(_dt.datetime(9999, 12, 31, 23, 59, 59, 999999))
            _temporal_range_gate(
                out, mid_days, lo_us, hi_us, vm, mid_scale=US_PER_DAY
            )
            if t.kind == LDT:
                return Column(LDT, out, valid)
            return Column(ZDT, out - off * US_PER_SECOND, valid, t.vocab)
        if l.kind not in (I64, F64) or r.kind not in (I64, F64):
            raise TpuUnsupportedExpr(f"arithmetic on {l.kind}/{r.kind}")
        valid = _and_valid(l, r)
        both_int = l.kind == I64 and r.kind == I64
        if isinstance(expr, E.Add):
            if both_int:
                return Column(I64, l.data + r.data, valid)
            return Column(F64, l.cast_f64().data + r.cast_f64().data, valid)
        if isinstance(expr, E.Subtract):
            if both_int:
                return Column(I64, l.data - r.data, valid)
            return Column(F64, l.cast_f64().data - r.cast_f64().data, valid)
        if isinstance(expr, E.Multiply):
            if both_int:
                return Column(I64, l.data * r.data, valid)
            return Column(F64, l.cast_f64().data * r.cast_f64().data, valid)
        if isinstance(expr, E.Divide):
            if both_int:
                rr = jnp.where(r.data == 0, 1, r.data)
                q = jnp.sign(l.data) * jnp.sign(r.data) * (jnp.abs(l.data) // jnp.abs(rr))
                return Column(I64, q, _mask_and(valid, r.data != 0))
            return Column(F64, l.cast_f64().data / r.cast_f64().data, valid)
        if isinstance(expr, E.Modulo):
            if both_int:
                rr = jnp.where(r.data == 0, 1, r.data)
                m = jnp.sign(l.data) * (jnp.abs(l.data) % jnp.abs(rr))
                return Column(I64, m, _mask_and(valid, r.data != 0))
            ld, rd = l.cast_f64().data, r.cast_f64().data
            m = jnp.sign(ld) * (jnp.abs(ld) % jnp.abs(rd))
            return Column(F64, m, valid)
        if isinstance(expr, E.Pow):
            return Column(F64, l.cast_f64().data ** r.cast_f64().data, valid)
        raise TpuUnsupportedExpr(type(expr).__name__)

    def _case(self, expr: E.CaseExpr) -> Column:
        if expr.operand is not None:
            conds = [
                self._equality(E.Equals(expr.operand, w)) for w in expr.whens
            ]
        else:
            conds = [self._as_bool(self.eval(w)) for w in expr.whens]
        thens = [self.eval(t) for t in expr.thens]
        default = (
            self.eval(expr.default)
            if expr.default is not None
            else constant_column(None, self.n)
        )
        kinds = {c.kind for c in thens} | {default.kind}
        if kinds <= {I64, F64} and len(kinds) > 1:
            thens = [c.as_f64_keeping_intness() for c in thens]
            if default.kind in (I64, F64):
                default = default.as_f64_keeping_intness()
            kinds = {F64}
        if len(kinds - {default.kind}) > 0 and len(kinds) > 1:
            raise TpuUnsupportedExpr("heterogeneous CASE branches")
        if kinds == {STR}:
            # remap every branch onto one merged dictionary so codes blend
            from .column import _remap

            merged = sorted({s for c in thens + [default] for s in (c.vocab or [])})
            thens = [_remap(c, merged) for c in thens]
            default = _remap(default, merged)
        out = default
        # evaluate from last WHEN to first so earlier WHENs win
        for cond, then in zip(reversed(conds), reversed(thens)):
            take = cond.data & cond.valid_mask()
            data = jnp.where(take, then.data, out.data)
            valid = jnp.where(take, then.valid_mask(), out.valid_mask())
            out = Column(
                then.kind, data, valid, then.vocab,
                int_flag=_merge_int_flag(take, then, out),
            )
        return out

    def _function(self, expr: E.FunctionCall) -> Column:
        from ...ir.functions import lookup as lookup_function

        name = expr.name
        if name in _NONDETERMINISTIC:
            # must run per row — const-folding would broadcast one sample
            raise TpuUnsupportedExpr(f"nondeterministic function {name}")
        try:
            f = lookup_function(name)
        except Exception:
            raise TpuUnsupportedExpr(f"unknown function {name}")
        consts = [self._const_value(a) for a in expr.args]
        if all(c is not self._NOT_CONST for c in consts):
            # fold fully-constant (incl. zero-arg: pi(), e()) calls before
            # any device allocation
            if f.null_prop and any(c is None for c in consts):
                return constant_column(None, self.n)
            return constant_column(f.fn(*consts), self.n)
        if (
            name in ("date.truncate", "localdatetime.truncate")
            and len(expr.args) == 2
            and isinstance(consts[0], str)
        ):
            # constant unit over a temporal device column: branch-free
            # calendar truncation on the VPU (the reference's biggest
            # temporal UDF family, TemporalUdfs.scala truncate variants)
            return self._device_truncate(name, consts[0].lower(), expr.args[1])
        args = [self.eval(a) for a in expr.args]
        if name == "abs" and args[0].kind in (I64, F64):
            return Column(args[0].kind, jnp.abs(args[0].data), args[0].valid)
        if name == "sign" and args[0].kind in (I64, F64):
            return Column(I64, jnp.sign(args[0].data).astype(jnp.int64), args[0].valid)
        if name in ("ceil", "floor", "round", "sqrt", "exp", "log", "log10", "sin", "cos", "tan") and args[0].kind in (I64, F64):
            x = args[0].cast_f64().data
            fn = {
                "ceil": jnp.ceil,
                "floor": jnp.floor,
                "round": lambda v: jnp.where(v >= 0, jnp.floor(v + 0.5), jnp.ceil(v - 0.5)),
                "sqrt": jnp.sqrt,
                "exp": jnp.exp,
                "log": jnp.log,
                "log10": jnp.log10,
                "sin": jnp.sin,
                "cos": jnp.cos,
                "tan": jnp.tan,
            }[name]
            return Column(F64, fn(x), args[0].valid)
        if name == "tofloat" and args[0].kind in (I64, F64):
            return args[0].cast_f64()
        if name == "tointeger" and args[0].kind in (I64, F64):
            return Column(I64, args[0].data.astype(jnp.int64), args[0].valid)
        if name == "coalesce":
            kinds = {a.kind for a in args}

            def obj_blend(blend_args):
                # host-side blend: OBJ columns (lists/elements) are numpy
                # object arrays, null encoded as None
                import numpy as np

                out_vals = list(blend_args[-1].data)
                for a in reversed(blend_args[:-1]):
                    out_vals = [
                        v if v is not None else o
                        for v, o in zip(list(a.data), out_vals)
                    ]
                arr = np.empty(len(out_vals), dtype=object)
                for i, v in enumerate(out_vals):
                    arr[i] = v
                return Column(OBJ, arr, None)

            if kinds <= {I64, F64} and len(kinds) > 1:
                args = [a.as_f64_keeping_intness() for a in args]
            elif kinds == {STR}:
                # blend on one merged dictionary or codes are meaningless
                from .column import _remap

                merged = sorted({s for a in args for s in (a.vocab or [])})
                args = [_remap(a, merged) for a in args]
            elif kinds == {OBJ}:
                return obj_blend(args)
            elif kinds in ({ZDT}, {ZT}) and len(
                {tuple(a.vocab or ()) for a in args}
            ) > 1:
                # DIFFERENT column zone offsets: the vocab carries one
                # offset for the whole result, so blending device lanes
                # would silently re-zone rows taken from the other
                # arguments — the exact zone loss ``Column._concat``
                # guards against. Blend host-exact instead.
                return obj_blend([a.to_obj() for a in args])
            elif len(kinds) > 1:
                raise TpuUnsupportedExpr("heterogeneous coalesce")
            out = args[-1]
            for a in reversed(args[:-1]):
                take = a.valid_mask()
                out = Column(
                    a.kind,
                    jnp.where(take, a.data, out.data),
                    jnp.where(take, True, out.valid_mask()),
                    a.vocab,
                    int_flag=_merge_int_flag(take, a, out),
                )
            return out
        return self._generic_function(expr, args, f, consts)

    _NOT_CONST = object()

    def _const_value(self, e: E.Expr):
        if isinstance(e, E.Lit):
            return e.value
        if isinstance(e, E.Param):
            return self.params.get(e.name)
        return self._NOT_CONST

    def _generic_function(
        self, expr: E.FunctionCall, args: List[Column], f, consts
    ) -> Column:
        """Registry-driven device evaluation with EXACT oracle parity: the
        same scalar ``fn`` the local evaluator uses (``ir/functions.py``)
        runs once per constant set or once per vocab entry — never per row.

        * all args constant -> compute once, broadcast
        * one STR column + constants -> vocab map (string library: toUpper,
          trim, replace, substring, size, toInteger, ... for free)
        * BOOL column tostring -> two-entry vocab
        """
        name = expr.name
        str_pos = [
            i
            for i, (c, a) in enumerate(zip(consts, args))
            if c is self._NOT_CONST and a.kind == STR
        ]
        if len(str_pos) == 1 and all(
            c is not self._NOT_CONST
            for i, c in enumerate(consts)
            if i != str_pos[0]
        ):
            pos = str_pos[0]
            col = args[pos]
            if f.null_prop and any(
                c is None for i, c in enumerate(consts) if i != pos
            ):
                return constant_column(None, self.n)

            def per_entry(s, _c=consts, _p=pos, _f=f.fn):
                a = list(_c)
                a[_p] = s
                return _f(*a)

            res = self._vocab_apply(col, per_entry)
            if not f.null_prop and res.kind in (I64, F64, BOOL):
                # e.g. exists(): fn(None) is a real value, not null
                try:
                    nv = per_entry(None)
                except Exception:  # fault-ok: host-side fn probe (fn(None)
                    # may legitimately raise); no device work here
                    nv = None
                if nv is not None:
                    const = constant_column(nv, self.n)
                    if const.kind == res.kind:
                        base = col.valid_mask()
                        data = jnp.where(base, res.data, const.data)
                        valid = jnp.where(base, res.valid_mask(), True)
                        res = Column(res.kind, data, valid)
            return res
        if name == "tostring" and len(args) == 1 and args[0].kind == BOOL:
            # two-entry vocab; 'false' < 'true' so code == bool value
            return Column(
                STR, args[0].data.astype(jnp.int32), args[0].valid, ["false", "true"]
            )
        raise TpuUnsupportedExpr(f"function {name}")

    def _vocab_apply(self, col: Column, fn) -> Column:
        """Apply a scalar function per vocab entry; infer the result kind
        from the outputs and build the matching device column."""
        outs = [fn(s) for s in (col.vocab or [])]
        non_null = [o for o in outs if o is not None]
        if all(isinstance(o, str) for o in non_null):
            return self._vocab_outs_str(col, outs)
        if all(isinstance(o, bool) for o in non_null):
            return self._vocab_outs_scalar(col, outs, BOOL)
        if all(isinstance(o, int) and not isinstance(o, bool) for o in non_null):
            return self._vocab_outs_scalar(col, outs, I64)
        if all(
            isinstance(o, (int, float)) and not isinstance(o, bool)
            for o in non_null
        ):
            outs = [float(o) if o is not None else None for o in outs]
            return self._vocab_outs_scalar(col, outs, F64)
        raise TpuUnsupportedExpr("non-scalar vocab function result")


def _merge_int_flag(take, a: Column, b: Column):
    """int_flag of where(take, a, b) — None when neither side tracks it."""
    if a.int_flag is None and b.int_flag is None:
        return None
    n = len(a)
    ai = a.int_flag if a.int_flag is not None else jnp.zeros(n, bool)
    bi = b.int_flag if b.int_flag is not None else jnp.zeros(n, bool)
    return jnp.where(take, ai, bi)


def _mask_and(valid, cond):
    return cond if valid is None else (valid & cond)


def _and_valid(l: Column, r: Column):
    lv, rv = l.valid, r.valid
    if lv is None and rv is None:
        return None
    return l.valid_mask() & r.valid_mask()
