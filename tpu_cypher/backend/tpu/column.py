"""Device columns: typed JAX arrays + validity masks.

The TPU-native data layout replacing the reference backends' engine columns
(Spark ``Column`` / Flink ``Expression``): every column is a fixed-width
device array plus an optional validity mask (Cypher null != padding; the
table-level row mask lives in ``TpuTable``). Strings are dictionary-encoded
with an ORDER-PRESERVING vocabulary (sorted), so <,<=,ORDER BY work on codes
without touching host strings. Ids are int64 (graph tag in high bits — see
``ir.expr.PrefixId``)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

import jax

# int64 element ids are load-bearing (graph tags live in bits 54+); the
# backend cannot run in 32-bit mode
jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp

from ...api import types as T
from ...api.types import CypherType
from ...obs import trace as _obs_trace
from ...parallel.mesh import padded_to_mesh

def to_host(arr) -> np.ndarray:
    """Device -> host pull that works across PROCESS boundaries: on a
    multi-process runtime (``jax.distributed``), a row-sharded global array
    is not fully addressable locally, so the full value is assembled with a
    collective allgather — the engine-level analog of the reference's
    collect-to-driver. Every process must reach this call symmetrically
    (they run the same SPMD query program, so they do). Single-process:
    plain ``np.asarray``."""
    if isinstance(arr, np.ndarray):
        return arr
    if (
        jax.process_count() > 1
        and hasattr(arr, "is_fully_addressable")
        and not arr.is_fully_addressable
    ):
        from jax.experimental import multihost_utils

        arr = multihost_utils.process_allgather(arr, tiled=True)
    return np.asarray(arr)


# column kinds
I64 = "i64"
F64 = "f64"
BOOL = "bool"
STR = "str"  # dictionary-encoded int32 codes
DATE = "date"  # int32 days since 1970-01-01 (ref TemporalUdfs.scala:40-160)
LDT = "ldt"  # int64 microseconds since 1970-01-01T00:00 (local, no zone)
ZDT = "zdt"  # int64 UTC microseconds; vocab = ['+HH:MM'] column zone offset
ZT = "zt"  # int64 UTC-adjusted micros of day; vocab = ['+HH:MM'] offset
LT = "lt"  # int64 microseconds since midnight (local time, no zone)
DUR = "dur"  # int64 (n, 3): months / days / total micros (seconds*1e6+us) —
#              the reference's (months, days, seconds, nanos) Duration model
#              (okapi-api Duration.scala) with the normalized sub-day pair
#              collapsed into one microsecond count (bijective: 0 <= us < 1e6)
OBJ = "obj"  # host-side Python objects (lists, elements) — not device resident

# duration ORDER/min/max keys use average-length microseconds (month =
# 30.4375 days, the reference's CalendarInterval comparison basis); ties
# keep first occurrence on BOTH backends (stable sorts / first-match
# selection). One definition: api.values (the oracle's order key), consumed
# on device by jit_ops._dur_order_key.

# temporal kinds share the integer device machinery (sort keys, joins,
# distinct/group packing, min/max) — they differ only in decode + typing
TEMPORAL_KINDS = (DATE, LDT, ZDT, ZT, LT)
# zoned kinds key on their single UTC-instant lane, so every packed
# sort/group/distinct path treats them as plain integers (openCypher
# datetime equality/order IS instant equality/order)
INTEGRAL_KINDS = (I64, BOOL, STR, DATE, LDT, ZDT, ZT, LT)

_NULL_CODE = np.int32(-1)


def device_padded(host_arr, fill):
    """Host array -> device array tail-padded with ``fill`` to the shape
    bucket (``bucketing.round_size``, identity when ``TPU_CYPHER_BUCKET`` is
    off) and then to a mesh-shard multiple. Returns ``(device array, total
    pad)``. THE ingest-side sizing discipline: bucketed ingestion makes two
    graphs/tables whose row counts share a bucket hit the same compiled
    programs downstream (pad rows are always marked/treated invalid)."""
    from .bucketing import bucket_pad_host

    arr, bpad = bucket_pad_host(np.asarray(host_arr), fill)
    dev, mpad = padded_to_mesh(arr, fill)
    # per-shard lattice invariant: with bucketing on under a mesh,
    # round_size already returned a shard-divisible size (it rounds the
    # LOCAL extent and scales back up), so the mesh pass only lays out —
    # the two pads are mutually exclusive
    assert not (bpad and mpad), (
        f"per-shard lattice failed to absorb the shard pad "
        f"(bucket pad {bpad}, mesh pad {mpad})"
    )
    return dev, bpad + mpad


def _obj_array(vals) -> np.ndarray:
    """ALWAYS-1-D object array (np.array() on equal-length list values
    silently builds 2-D, breaking concat and row gathers)."""
    arr = np.empty(len(vals), dtype=object)
    for i, v in enumerate(vals):
        arr[i] = v
    return arr


class TpuBackendError(Exception):
    pass


def _decode_host(kind, data, valid, iflag, vocab) -> List[Any]:
    """Per-kind host-array -> Python-value decode shared by ``to_values``
    and the chunked ``to_values_range`` (``data``/``valid``/``iflag`` are
    ALREADY-SLICED host numpy arrays)."""
    if kind == I64:
        return [
            int(v) if (valid is None or valid[i]) else None
            for i, v in enumerate(data)
        ]
    if kind == F64:
        return [
            (
                (int(v) if (iflag is not None and iflag[i]) else float(v))
                if (valid is None or valid[i])
                else None
            )
            for i, v in enumerate(data)
        ]
    if kind == BOOL:
        return [
            bool(v) if (valid is None or valid[i]) else None
            for i, v in enumerate(data)
        ]
    if kind == STR:
        vb = vocab or []
        return [
            (vb[v] if v >= 0 else None)
            if (valid is None or valid[i])
            else None
            for i, v in enumerate(data)
        ]
    if kind == DATE:
        from .temporal import decode_date

        return [
            decode_date(v) if (valid is None or valid[i]) else None
            for i, v in enumerate(data)
        ]
    if kind == LDT:
        from .temporal import decode_ldt

        return [
            decode_ldt(v) if (valid is None or valid[i]) else None
            for i, v in enumerate(data)
        ]
    if kind in (ZDT, ZT):
        from .temporal import decode_zdt, decode_zt, parse_offset_str

        off = parse_offset_str((vocab or ["+00:00"])[0])
        dec = decode_zdt if kind == ZDT else decode_zt
        return [
            dec(v, off) if (valid is None or valid[i]) else None
            for i, v in enumerate(data)
        ]
    if kind == LT:
        from .temporal import decode_lt

        return [
            decode_lt(v) if (valid is None or valid[i]) else None
            for i, v in enumerate(data)
        ]
    if kind == DUR:
        from ...api.values import Duration

        return [
            Duration(months=int(r[0]), days=int(r[1]), microseconds=int(r[2]))
            if (valid is None or valid[i])
            else None
            for i, r in enumerate(data)
        ]
    raise TpuBackendError(kind)  # pragma: no cover


class InexactPromotionError(TpuBackendError):
    """An I64->F64 promotion would round integers beyond 2**53; the caller
    must use a host-exact representation (OBJ / local oracle) instead."""



@dataclass
class Column:
    kind: str
    data: Any  # jnp array (device) or np object array for OBJ
    valid: Optional[Any]  # jnp bool array or None (= all valid)
    vocab: Optional[List[str]] = None  # sorted, for STR
    _obj_type: Optional[CypherType] = None  # cached OBJ value type (metadata)
    # F64 only: bool device array marking rows whose Cypher value is an
    # INTEGER (mixed int/float columns are stored as f64 payloads; Cypher
    # distinguishes 1 from 1.0 as *values* even though 1 = 1.0 compares
    # true, so decode must restore intness). None = no integer rows.
    int_flag: Optional[Any] = None
    # I64 only: cached 'has valid values beyond 2**53' probe (None = not yet
    # computed); computed at most once per column instance so f64-promotion
    # guards don't sync repeatedly
    _beyond_f64: Optional[bool] = None
    # host mirrors of ``data``/``valid`` when the column was BUILT from
    # host data (``from_numpy``/``from_values``): decoding such a column
    # costs zero device-to-host fetches. Mirrors hold the LOGICAL rows only
    # (no padding).
    _np_cache: Optional[np.ndarray] = None
    _np_valid: Optional[np.ndarray] = None
    # lazily-fetched (data, valid, int_flag) host tuple for the decode
    # paths (``to_values`` / ``to_values_range``): ONE D2H per array per
    # column lifetime, then chunk decodes slice host-side. Columns are
    # immutable after construction, so the fetch can never go stale.
    _host_fetch: Optional[tuple] = None
    # sharding padding (``parallel.mesh.padded_to_mesh``): the trailing
    # ``pad`` device rows are phantom rows added so the array shards evenly
    # over the active mesh. They are ALWAYS marked invalid in ``valid``, so
    # the fused expand/count paths (which gate on the id column's validity,
    # ``jit_ops.compact_lookup``) skip them with no extra machinery; eager
    # relational ops slice them off first (``TpuTable._depad``).
    pad: int = 0
    # True when ``valid`` exists ONLY for the padding (the logical column
    # has no nulls) — type metadata stays non-nullable and depad restores
    # ``valid=None``.
    pad_synth: bool = False

    def ints_beyond_f64(self) -> bool:
        """True when a VALID int64 payload exceeds f64 exactness (2**53)."""
        if self.kind != I64:
            return False
        if self._beyond_f64 is None:
            big = self.valid_mask() & (jnp.abs(self.data) > 2**53)
            # tpulint: allow[host-sync] reason=one cached scalar probe per column at compare time; runs inside the ladder's per-attempt fault boundary
            self._beyond_f64 = bool(jnp.any(big))
        return self._beyond_f64

    def __len__(self) -> int:
        return int(self.data.shape[0]) if self.kind != OBJ else len(self.data)

    @property
    def logical_len(self) -> int:
        """Row count excluding sharding pad rows."""
        return len(self) - self.pad

    def depad(self) -> "Column":
        """Slice off the sharding pad rows (and drop a synthesized-only
        validity mask). The result is a plain unpadded column; host mirrors
        carry over (they never include padding)."""
        if self.pad == 0:
            return self
        n = self.logical_len
        valid = None if self.pad_synth else (
            self.valid[:n] if self.valid is not None else None
        )
        return Column(
            self.kind,
            self.data[:n],
            valid,
            self.vocab,
            int_flag=self.int_flag[:n] if self.int_flag is not None else None,
            _np_cache=self._np_cache,
            _np_valid=self._np_valid,
        )

    # -- conversion --------------------------------------------------------

    @staticmethod
    def _ingest(data_np: np.ndarray, valid_np: Optional[np.ndarray], fill):
        """Host arrays -> (device data, device valid, pad, pad_synth) with
        shape-bucket + mesh-sharding padding: pad rows are ALWAYS invalid
        (the valid mask is synthesized when the logical column has none)."""
        data, pad = device_padded(data_np, fill)
        if valid_np is not None:
            v, _ = device_padded(valid_np, False)
            return data, v, pad, False
        if pad:
            v, _ = device_padded(np.ones(len(data_np), bool), False)
            return data, v, pad, True
        return data, None, pad, False

    @staticmethod
    def from_values(values: Sequence[Any]) -> "Column":
        """Infer kind from Python values (None = null)."""
        non_null = [v for v in values if v is not None]
        n = len(values)
        valid_np = np.array([v is not None for v in values], dtype=bool)
        has_null = not valid_np.all()
        hv = valid_np if has_null else None

        def build(kind, data_np, fill, vocab=None, iflag_np=None):
            data, v, pad, ps = Column._ingest(data_np, hv, fill)
            iflag = None
            if iflag_np is not None and iflag_np.any():
                iflag = device_padded(iflag_np, False)[0]
            return Column(
                kind, data, v, vocab, int_flag=iflag,
                _np_cache=data_np, _np_valid=hv, pad=pad, pad_synth=ps,
            )

        if not non_null:
            data, v, pad, _ = Column._ingest(
                np.zeros(n, np.int64), valid_np, 0
            )
            return Column(
                I64, data, v, _np_cache=np.zeros(n, np.int64),
                _np_valid=valid_np, pad=pad,
            )
        _BOOLK = (bool, np.bool_)
        _INTK = (int, np.integer)
        _NUMK = (int, float, np.integer, np.floating)
        if all(isinstance(v, _BOOLK) for v in non_null):
            data = np.array([bool(v) if v is not None else False for v in values])
            return build(BOOL, data, False)
        if all(isinstance(v, _INTK) and not isinstance(v, _BOOLK) for v in non_null):
            data = np.array(
                [int(v) if v is not None else 0 for v in values], dtype=np.int64
            )
            return build(I64, data, 0)
        if all(isinstance(v, _NUMK) and not isinstance(v, _BOOLK) for v in non_null):
            ints = [
                v
                for v in non_null
                if isinstance(v, _INTK) and not isinstance(v, _BOOLK)
            ]
            if any(abs(int(v)) > 2**53 for v in ints):
                # mixed int/float with ints beyond f64 exactness: the f64
                # payload would silently round (2**53+1 -> 2**53) — keep the
                # column host-exact instead
                return Column(OBJ, _obj_array(values), None)
            data = np.array(
                [float(v) if v is not None else 0.0 for v in values], dtype=np.float64
            )
            iflag = np.array(
                [isinstance(v, _INTK) and not isinstance(v, _BOOLK) for v in values],
                dtype=bool,
            )
            return build(F64, data, 0.0, iflag_np=iflag)
        if all(isinstance(v, str) for v in non_null):
            vocab = sorted(set(non_null))
            index = {s: i for i, s in enumerate(vocab)}
            codes = np.array(
                [index[v] if v is not None else _NULL_CODE for v in values],
                dtype=np.int32,
            )
            return build(STR, codes, _NULL_CODE, vocab=vocab)
        import datetime as _dt

        from .temporal import encode_date, encode_ldt

        # naive local datetimes -> int64 micros; pure dates -> int32 days
        # (datetime IS a date subclass — check it first; zoned datetimes and
        # mixed date/datetime columns stay host-exact OBJ)
        if all(
            isinstance(v, _dt.datetime) and v.tzinfo is None for v in non_null
        ):
            data = np.array(
                [encode_ldt(v) if v is not None else 0 for v in values],
                dtype=np.int64,
            )
            return build(LDT, data, 0)
        if all(
            isinstance(v, _dt.date) and not isinstance(v, _dt.datetime)
            for v in non_null
        ):
            data = np.array(
                [encode_date(v) if v is not None else 0 for v in values],
                dtype=np.int32,
            )
            return build(DATE, data, 0)
        from .temporal import (
            encode_time_of_day,
            encode_zdt,
            encode_zt,
            offset_seconds_of,
            offset_str,
        )

        # zoned datetimes/times with ONE fixed offset across the column:
        # the UTC instant is the device lane, the offset rides as column
        # metadata (vocab). Per-row MIXED offsets (e.g. a DST-crossing
        # zoneinfo column) stay host-exact OBJ — the reference's
        # TemporalUdfs warn on timezone loss; we lose nothing, we fall back.
        if all(
            isinstance(v, _dt.datetime) and isinstance(v.tzinfo, _dt.timezone)
            for v in non_null
        ):
            # fixed-offset zones only: region-NAMED zones (zoneinfo) keep
            # their name host-exact; a device round-trip would degrade
            # 'Europe/Berlin' to '+02:00' (the reference's TemporalUdfs
            # warn on exactly this loss — we avoid it instead)
            offs = {offset_seconds_of(v) for v in non_null}
            if len(offs) == 1:
                off = offs.pop()
                data = np.array(
                    [encode_zdt(v) if v is not None else 0 for v in values],
                    dtype=np.int64,
                )
                return build(ZDT, data, 0, vocab=[offset_str(off)])
            return Column(OBJ, _obj_array(values), None)
        if all(isinstance(v, _dt.time) for v in non_null):
            if all(isinstance(v.tzinfo, _dt.timezone) for v in non_null):
                offs = {offset_seconds_of(v) for v in non_null}
                if len(offs) == 1:
                    off = offs.pop()
                    data = np.array(
                        [encode_zt(v) if v is not None else 0 for v in values],
                        dtype=np.int64,
                    )
                    return build(ZT, data, 0, vocab=[offset_str(off)])
                return Column(OBJ, _obj_array(values), None)
            if all(v.tzinfo is None for v in non_null):
                data = np.array(
                    [
                        encode_time_of_day(v) if v is not None else 0
                        for v in values
                    ],
                    dtype=np.int64,
                )
                return build(LT, data, 0)
            return Column(OBJ, _obj_array(values), None)
        from ...api.values import Duration

        if all(isinstance(v, Duration) for v in non_null):
            data = np.zeros((n, 3), dtype=np.int64)
            for i, v in enumerate(values):
                if v is not None:
                    data[i] = (
                        v.months,
                        v.days,
                        v.seconds * 1_000_000 + v.microseconds,
                    )
            return build(DUR, data, 0)
        # fallback: host objects
        return Column(OBJ, _obj_array(values), None)

    @staticmethod
    def from_numpy(arr: np.ndarray, valid: Optional[np.ndarray] = None) -> "Column":
        """Zero-copy-ish bulk construction from a numpy array (the IO/bench
        fast path — ``from_values`` walks Python objects, O(n) interpreter
        work; this is one H2D transfer)."""
        arr = np.asarray(arr)
        hv = np.asarray(valid, dtype=bool).copy() if valid is not None else None
        if arr.dtype == np.bool_:
            host = arr.copy()
            kind = BOOL
            fill = False
        elif np.issubdtype(arr.dtype, np.integer):
            host = arr.astype(np.int64, copy=True)
            kind = I64
            fill = 0
        elif np.issubdtype(arr.dtype, np.floating):
            host = arr.astype(np.float64, copy=True)
            kind = F64
            fill = 0.0
        else:
            raise TpuBackendError(f"from_numpy: unsupported dtype {arr.dtype}")
        data, v, pad, ps = Column._ingest(host, hv, fill)
        return Column(
            kind, data, v,
            _np_cache=host, _np_valid=hv, pad=pad, pad_synth=ps,
        )

    def _host_arrays(self):
        """Host mirrors of (data, valid, int_flag), fetched AT MOST ONCE
        per column instance and cached — the cursor-streaming decode path
        slices these host-side per chunk, so a streamed result pays one
        D2H transfer per column regardless of how many chunks it spans
        (and never compiles a per-bounds device slice program)."""
        if self._host_fetch is None:
            def fetch(arr):
                if isinstance(arr, np.ndarray):
                    return arr
                # the read that waits for whatever program fills ``arr``
                with _obs_trace.sync("to_host"):
                    return to_host(arr)

            data = (
                self._np_cache if self._np_cache is not None
                else fetch(self.data)
            )
            if self.valid is None:
                valid = None
            elif self._np_valid is not None:
                valid = self._np_valid
            else:
                valid = fetch(self.valid)
            iflag = (
                fetch(self.int_flag) if self.int_flag is not None else None
            )
            self._host_fetch = (data, valid, iflag)
        return self._host_fetch

    def to_values(self, row_mask: Optional[np.ndarray] = None) -> List[Any]:
        """Decode to Python values (respecting validity)."""
        if self.kind == OBJ:
            vals = list(self.data)
        else:
            data, valid, iflag = self._host_arrays()
            vals = _decode_host(self.kind, data, valid, iflag, self.vocab)
        if row_mask is not None:
            vals = [v for v, keep in zip(vals, row_mask) if keep]
        return vals

    def to_values_range(self, lo: int, hi: int) -> List[Any]:
        """Decode rows ``[lo, hi)`` only — the chunked-materialize step of
        cursor streaming (``TpuTable.rows_chunked``). Host arrays are
        cached by ``_host_arrays``, so per-chunk cost is the decode of
        ``hi - lo`` rows and nothing else."""
        if self.kind == OBJ:
            return list(self.data[lo:hi])
        data, valid, iflag = self._host_arrays()
        return _decode_host(
            self.kind,
            data[lo:hi],
            valid[lo:hi] if valid is not None else None,
            iflag[lo:hi] if iflag is not None else None,
            self.vocab,
        )

    # -- ops ---------------------------------------------------------------

    def take(self, idx) -> "Column":
        """Gather rows by index array (ONE jitted dispatch for data +
        masks, not one eager gather per array — see ``jit_ops``)."""
        if self.kind == OBJ:
            return Column(OBJ, self.data[np.asarray(idx)], None)
        from .jit_ops import cols_take

        d, v, i = cols_take({"c": (self.data, self.valid, self.int_flag)}, idx)["c"]
        return Column(self.kind, d, v, self.vocab, int_flag=i)

    def take_or_null(self, idx, in_bounds) -> "Column":
        """Gather; rows where ``in_bounds`` is False become null (outer joins)."""
        n = int(idx.shape[0]) if hasattr(idx, "shape") else len(idx)
        if len(self) == 0:
            # empty build side: every row is an outer-join null
            if self.kind == OBJ:
                out = np.empty(n, dtype=object)
                return Column(OBJ, out, None)
            dtype = self.data.dtype
            return Column(
                self.kind,
                jnp.zeros((n,) + self.data.shape[1:], dtype),
                jnp.zeros(n, bool),
                self.vocab,
            )
        if self.kind == OBJ:
            out = np.empty(len(idx), dtype=object)
            idx_np = np.asarray(idx)
            ib = np.asarray(in_bounds)
            for i in range(len(idx_np)):
                out[i] = self.data[idx_np[i]] if ib[i] else None
            return Column(OBJ, out, None)
        from .jit_ops import cols_take_or_null

        d, v, i = cols_take_or_null(
            {"c": (self.data, self.valid, self.int_flag)}, idx, in_bounds
        )["c"]
        return Column(self.kind, d, v, self.vocab, int_flag=i)

    def concat(self, other: "Column") -> "Column":
        a, b = self, other
        if a.kind != b.kind:
            # an all-null side carries no payload: adopt the other's kind
            # (scan alignment fills absent properties with null constants,
            # which default to I64 — without this, unioning them with a
            # STR/BOOL column would degrade the whole column to OBJ)
            if a.kind != OBJ and b.is_all_null():
                b = a.null_like(len(b))
            elif b.kind != OBJ and a.is_all_null():
                a = b.null_like(len(a))
        if a.kind != b.kind:
            # unify: promote numerics (keeping Cypher intness), else objects
            if {a.kind, b.kind} == {I64, F64}:
                iside = a if a.kind == I64 else b
                if iside.ints_beyond_f64():
                    a = a.to_obj()
                    b = b.to_obj()
                else:
                    a = a.as_f64_keeping_intness()
                    b = b.as_f64_keeping_intness()
            else:
                a = a.to_obj()
                b = b.to_obj()
        if a.kind == OBJ:
            return Column(OBJ, np.concatenate([a.data, b.data]), None)
        if a.kind == STR:
            a, b = _unify_vocab(a, b)
        if a.kind in (ZDT, ZT) and a.vocab != b.vocab:
            # DIFFERENT column offsets: the vocab carries one offset for
            # the whole column, so a blind concat would silently re-zone
            # one side's rows — keep the union host-exact instead (same
            # policy as mixed-offset ingest)
            a = a.to_obj()
            b = b.to_obj()
            return Column(OBJ, np.concatenate([a.data, b.data]), None)
        data = jnp.concatenate([a.data, b.data])
        if a.valid is None and b.valid is None:
            valid = None
        else:
            av = a.valid if a.valid is not None else jnp.ones(len(a), bool)
            bv = b.valid if b.valid is not None else jnp.ones(len(b), bool)
            valid = jnp.concatenate([av, bv])
        if a.int_flag is None and b.int_flag is None:
            iflag = None
        else:
            ai = a.int_flag if a.int_flag is not None else jnp.zeros(len(a), bool)
            bi = b.int_flag if b.int_flag is not None else jnp.zeros(len(b), bool)
            iflag = jnp.concatenate([ai, bi])
        return Column(a.kind, data, valid, a.vocab, int_flag=iflag)

    def is_all_null(self) -> bool:
        if self.kind == OBJ:
            return all(v is None for v in self.data)
        # tpulint: allow[host-sync] reason=one scalar nullness probe at decode/compare time; runs inside the ladder's per-attempt fault boundary
        return self.valid is not None and not bool(jnp.any(self.valid))

    def null_like(self, n: int) -> "Column":
        """n all-null rows with this column's kind/vocab."""
        if self.kind == OBJ:
            return Column(OBJ, np.array([None] * n, dtype=object), None)
        if self.kind == STR:
            data = jnp.full(n, _NULL_CODE, jnp.int32)
        else:
            data = jnp.zeros((n,) + self.data.shape[1:], self.data.dtype)
        return Column(self.kind, data, jnp.zeros(n, bool), self.vocab)

    def cast_f64(self) -> "Column":
        """Pure float cast (arithmetic contexts — intness deliberately
        dropped: the result of float arithmetic IS a float)."""
        if self.kind == F64:
            if self.int_flag is not None:
                return Column(F64, self.data, self.valid)
            return self
        if self.kind == I64:
            return Column(F64, self.data.astype(jnp.float64), self.valid)
        raise TpuBackendError(f"Cannot cast {self.kind} to f64")

    def as_f64_keeping_intness(self) -> "Column":
        """Value-union contexts (UNION ALL, scan alignment): an I64 column
        becomes f64 payloads with every valid row flagged as a Cypher
        INTEGER, so decode restores 1 (not 1.0). Precision caveat: mixed
        columns join/compare on f64 payloads, exact only below 2**53."""
        if self.kind == F64:
            return self
        if self.kind == I64:
            if self.ints_beyond_f64():
                raise InexactPromotionError(
                    "int64 values beyond 2**53 cannot promote to f64 exactly"
                )
            return Column(
                F64,
                self.data.astype(jnp.float64),
                self.valid,
                int_flag=self.valid_mask(),
            )
        raise TpuBackendError(f"Cannot cast {self.kind} to f64")

    def to_obj(self) -> "Column":
        return Column(OBJ, _obj_array(self.to_values()), None)

    def valid_mask(self) -> Any:
        if self.kind == OBJ:
            return jnp.asarray(np.array([v is not None for v in self.data], bool))
        if self.valid is None:
            return jnp.ones(len(self), bool)
        return self.valid

    def slice(self, lo: int, hi: int) -> "Column":
        """Contiguous row slice (device slice — no gather)."""
        if self.kind == OBJ:
            return Column(OBJ, self.data[lo:hi], None)
        data = self.data[lo:hi]
        valid = self.valid[lo:hi] if self.valid is not None else None
        iflag = self.int_flag[lo:hi] if self.int_flag is not None else None
        return Column(self.kind, data, valid, self.vocab, int_flag=iflag)

    # NOTE: Cypher-equivalence sort keys (null canonical 0, NaN its own
    # class, -0.0 == 0.0) are built inside the jitted factorization —
    # ``jit_ops._equivalence_keys_traced`` — shared by distinct and group.
    # Join keys deliberately implement ``=`` semantics instead (NaN never
    # matches), so they must not use those keys.

    def cypher_type(self) -> CypherType:
        base = {
            I64: T.CTInteger,
            F64: T.CTFloat,
            BOOL: T.CTBoolean,
            STR: T.CTString,
            DATE: T.CTDate,
            LDT: T.CTLocalDateTime,
            ZDT: T.CTDateTime,
            ZT: T.CTTime,
            LT: T.CTLocalTime,
            DUR: T.CTDuration,
            OBJ: T.CTAny,
        }[self.kind]
        if self.kind == F64 and self.int_flag is not None:
            base = T.join_types([T.CTInteger, T.CTFloat])
        # a validity mask synthesized only for sharding padding does not
        # make the column nullable
        has_null = (
            self.valid is not None and not self.pad_synth
        ) or self.kind == OBJ
        return base.nullable if has_null else base


def _unify_vocab(a: Column, b: Column) -> Tuple[Column, Column]:
    if a.vocab == b.vocab:
        return a, b
    merged = sorted(set(a.vocab or []) | set(b.vocab or []))
    return _remap(a, merged), _remap(b, merged)


def _remap(c: Column, merged: List[str]) -> Column:
    old = c.vocab or []
    index = {s: i for i, s in enumerate(merged)}  # O(V), not list.index O(V^2)
    lut = np.array(
        [index[s] for s in old] + [0], dtype=np.int32
    )  # extra slot for null code indexing
    codes = np.asarray(c.data)
    new_codes = np.where(codes >= 0, lut[np.clip(codes, 0, len(old) - 1 if old else 0)], _NULL_CODE)
    return Column(STR, jnp.asarray(new_codes.astype(np.int32)), c.valid, merged)


def mask_to_idx(mask) -> Tuple[Any, int]:
    """Boolean device mask -> (index array, count) with ONE scalar sync —
    the shared compaction idiom of the table ops and the fused expand path.
    Both phases are cached jitted programs (``jit_ops.mask_to_idx``)."""
    from .jit_ops import mask_to_idx as _jit_mask_to_idx

    return _jit_mask_to_idx(mask)


def mask_to_idx_bucketed(mask) -> Tuple[Any, int]:
    """``mask_to_idx`` with the index array padded to the shape bucket:
    returns (index array of ``round_size(count)`` lanes, true count). Pad
    lanes hold index 0 (duplicates of a real row) — consumers mark lanes at
    or past ``count`` invalid (``jit_ops.cols_take_counted``), keeping the
    tail-pad invariant. One scalar sync, same as the exact form."""
    from .bucketing import round_size
    from .jit_ops import mask_count, mask_nonzero

    count = mask_count(mask)
    return mask_nonzero(mask, size=round_size(count)), count


def constant_column(value: Any, n: int) -> Column:
    import datetime as _dt

    if value is None:
        return Column(I64, jnp.zeros(n, jnp.int64), jnp.zeros(n, bool))
    if isinstance(value, bool):
        return Column(BOOL, jnp.full(n, value, dtype=bool), None)
    if isinstance(value, int):
        return Column(I64, jnp.full(n, value, dtype=jnp.int64), None)
    if isinstance(value, float):
        return Column(F64, jnp.full(n, value, dtype=jnp.float64), None)
    if isinstance(value, str):
        return Column(STR, jnp.zeros(n, jnp.int32), None, [value])
    if isinstance(value, _dt.datetime):
        if value.tzinfo is None:
            from .temporal import encode_ldt

            return Column(LDT, jnp.full(n, encode_ldt(value), jnp.int64), None)
        if not isinstance(value.tzinfo, _dt.timezone):
            # region-named zone: keep the name host-exact (see from_values)
            return Column(OBJ, _obj_array([value] * n), None)
        from .temporal import encode_zdt, offset_seconds_of, offset_str

        return Column(
            ZDT,
            jnp.full(n, encode_zdt(value), jnp.int64),
            None,
            [offset_str(offset_seconds_of(value))],
        )
    if isinstance(value, _dt.date):
        from .temporal import encode_date

        return Column(DATE, jnp.full(n, encode_date(value), jnp.int32), None)
    if isinstance(value, _dt.time):
        from .temporal import (
            encode_time_of_day,
            encode_zt,
            offset_seconds_of,
            offset_str,
        )

        if value.tzinfo is None:
            return Column(
                LT, jnp.full(n, encode_time_of_day(value), jnp.int64), None
            )
        if not isinstance(value.tzinfo, _dt.timezone):
            return Column(OBJ, _obj_array([value] * n), None)
        return Column(
            ZT,
            jnp.full(n, encode_zt(value), jnp.int64),
            None,
            [offset_str(offset_seconds_of(value))],
        )
    from ...api.values import Duration

    if isinstance(value, Duration):
        row = jnp.asarray(
            [
                value.months,
                value.days,
                value.seconds * 1_000_000 + value.microseconds,
            ],
            jnp.int64,
        )
        return Column(DUR, jnp.broadcast_to(row, (n, 3)), None)
    return Column(OBJ, _obj_array([value] * n), None)
