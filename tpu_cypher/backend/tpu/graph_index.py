"""Per-graph device-resident CSR index for the fused expand path.

The reference executes every ``Expand`` as relationship-scan + 2 hash joins
on the engine's shuffle machinery (``RelationalPlanner.scala:130-165``).
The TPU-native replacement keeps a compacted CSR of each relationship-type
set resident in HBM, built ONCE per graph and reused by every query
(``GraphIndex.of(graph)`` hangs the cache off the graph object, the analog
of the engines' cached/partitioned relationship tables):

* ``node_ids``  — sorted unique int64 element ids; position = compact id
* per (types, orientation): ``row_ptr``/``col_idx`` int32 CSR plus
  ``edge_orig`` mapping CSR edge position -> row of the canonical
  relationship scan (so any rel property is one gather away)
* per label set: the canonical node scan plus ``row_map`` taking a compact
  id to its row in that scan (-1 = node lacks the labels — the fused label
  filter)
* per (types, orientation): sorted ``edge_keys`` (src*N + dst forward,
  dst*N + src reverse) for ExpandInto and WCOJ intersection probes

Scans are cached under canonical variable names; operators re-key their
header expressions onto the canonical var (structural equality ignores
types), so one cache serves every query variable name.
"""

from __future__ import annotations

import contextlib
import contextvars
import time
from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from ...api import types as T
from ...ir import expr as E
from ...obs import trace as _obs_trace
from ...obs.metrics import REGISTRY as _REGISTRY
from ...runtime.faults import fault_point
from .bucketing import ID_SENTINEL, bucket_pad_host, round_size
from .column import Column, TpuBackendError, device_padded

# canonical scan variable names (reserved: queries cannot produce '$' vars)
CANON_NODE = "$gi_n"
CANON_REL = "$gi_r"


INDEX_BUILD_SECONDS = _REGISTRY.counter(
    "tpu_cypher_index_build_seconds_total",
    "host seconds building a graph's lazy indexes (each build's own time: "
    "a build made inside another counts once, under its own name)",
    labels=("index",),
)

# seconds of the builds nested in the one open in this context
_NESTED: contextvars.ContextVar[Optional[List[float]]] = contextvars.ContextVar(
    "tpu_cypher_index_build_nested", default=None
)


@contextlib.contextmanager
def _build(what: str, **attrs) -> Iterator[Any]:
    """One lazy index build: a span ``index:<what>`` (kind ``build``) and
    its own seconds — less those of the builds it made on the way — in
    ``tpu_cypher_index_build_seconds_total{index=<what>}``. A graph's first
    queries are made of these; a warm index never comes here."""
    nested = [0.0]
    token = _NESTED.set(nested)
    t0 = time.perf_counter()
    try:
        with _obs_trace.span(f"index:{what}", kind="build", **attrs) as sp:
            yield sp
    finally:
        _NESTED.reset(token)
        seconds = time.perf_counter() - t0
        INDEX_BUILD_SECONDS.inc(max(seconds - nested[0], 0.0), index=what)
        outer = _NESTED.get()
        if outer is not None:
            outer[0] += seconds


class GraphIndexError(TpuBackendError):
    """The graph cannot be CSR-indexed (e.g. dangling endpoints)."""


def _host_logical(col: Column, size: int) -> np.ndarray:
    """Host int64 copy of a scan column's LOGICAL rows: the ingest-time
    host mirror when present (no device-to-host fetch), else one device
    fetch sliced past any sharding pad."""
    if col._np_cache is not None:
        return np.asarray(col._np_cache[:size], dtype=np.int64)
    return np.asarray(col.data, dtype=np.int64)[:size]


def rekey_element_expr(e: E.Expr, canon: E.Var) -> Optional[E.Expr]:
    """Rebuild an element sub-expression onto the canonical scan variable.

    Header expressions for a var v are Var/Id/StartNode/EndNode/HasLabel/
    HasType/Property over v; structural equality ignores the attached type,
    so the rebuilt expr indexes the canonical scan's header directly."""
    if isinstance(e, E.Var):
        return canon
    if isinstance(e, E.Id):
        return E.Id(canon)
    if isinstance(e, E.StartNode):
        return E.StartNode(canon)
    if isinstance(e, E.EndNode):
        return E.EndNode(canon)
    if isinstance(e, E.HasLabel):
        return E.HasLabel(canon, e.label)
    if isinstance(e, E.HasType):
        return E.HasType(canon, e.rel_type)
    if isinstance(e, E.Property):
        return E.Property(canon, e.key)
    return None


@dataclass(frozen=True)
class RowSpan:
    """The rows one CSR holds, and the window of the node space a program
    reads them through (``GraphIndex.csr_row_span``): ``start <= lo`` and
    ``hi <= start + L <= num_nodes``, ``L`` on the bucket lattice."""

    lo: int  # the first row with an edge
    hi: int  # one past the last; lo == hi: the type has no edge
    start: int  # the row the window starts at
    row_ptr: Any  # int32[L + 1] device: row_ptr[start : start + L + 1]

    @property
    def window(self) -> Tuple[Any, np.int32]:
        """``(row_ptr slice, start)`` as ``jit_ops._csr_spmv`` takes a span:
        the slice's shape is static, where it starts is traced."""
        return self.row_ptr, np.int32(self.start)


class GraphIndex:
    """CSR + canonical-scan cache for one RelationalCypherGraph."""

    # sorted-adjacency contract: every CSR row's col_idx is NONDECREASING
    # (``np.lexsort((b, a))`` orders edges by (row, neighbor); the build
    # asserts it rather than trusts it). The WCOJ sorted-intersection
    # executor (``wcoj.py``, ``jit_ops.range_count``) binary-searches row
    # slices and is only correct against it.
    csr_sorted: bool = True

    @staticmethod
    def of(graph) -> "GraphIndex":
        gi = getattr(graph, "_tpu_graph_index", None)
        if gi is None:
            gi = GraphIndex(graph)
            try:
                graph._tpu_graph_index = gi
            except AttributeError:  # exotic graph impl without __dict__
                pass
        return gi

    def __init__(self, graph):
        self.graph = graph
        self._node_ids: Optional[Tuple[Any, np.ndarray]] = None
        # labels_key -> (cols, header, row_map)
        self._node_scans: Dict[Tuple[str, ...], Tuple[Dict, Any, Any]] = {}
        # types_key -> (cols, header); logical row counts in _rel_sizes
        self._rel_scans: Dict[Tuple[str, ...], Tuple[Dict, Any]] = {}
        self._rel_sizes: Dict[Tuple[str, ...], int] = {}
        # (types_key, reverse) -> (row_ptr, col_idx, edge_orig) device arrays
        self._csr: Dict[Tuple[Tuple[str, ...], bool], Tuple[Any, Any, Any]] = {}
        # types_key -> both-orientation CSR (undirected var-length walks:
        # each relationship appears once per endpoint, self-loops once)
        self._csr_und: Dict[Tuple[str, ...], Tuple[Any, Any, Any]] = {}
        # (types_key, reverse) -> host max out-degree (Pallas eligibility
        # probe — computed once at build, never synced per query)
        self._csr_max_deg: Dict[Tuple[Tuple[str, ...], bool], int] = {}
        self._csr_lanes: Dict[Tuple[Tuple[str, ...], bool], int] = {}
        # (types_key, reverse) -> the rows that CSR holds and their window
        self._csr_span: Dict[Tuple[Tuple[str, ...], bool], RowSpan] = {}
        # per CSR orientation, host bool[num_nodes]: the nodes its edges end
        # in; per (orientation, labels): do all of those carry the labels
        self._csr_far_nodes: Dict[Tuple[Tuple[str, ...], bool], np.ndarray] = {}
        self._hop_label_proven: Dict[Tuple, bool] = {}
        # (types_key, reverse) -> sorted edge keys, device int64: forward
        # keys are (src*N + dst), reverse keys (dst*N + src) — each sorted
        # because its CSR orientation lexsorts by that pair
        self._edge_keys: Dict[Tuple[Tuple[str, ...], bool], Any] = {}
        # types_key -> int64[num_rels] (src*N + dst) key per canonical
        # rel-scan row (relationship-uniqueness probe subtraction)
        self._keys_by_orig: Dict[Tuple[str, ...], Any] = {}
        # (types_key, reverse) -> Optional (Npad, Npad) bf16 dense adjacency
        # with edge MULTIPLICITY entries (MXU matmul tier; Npad = block pad)
        self._dense_adj: Dict[Tuple[Tuple[str, ...], bool], Optional[Any]] = {}
        # types_key -> device int64[num_nodes] self-loop counts (undirected
        # count chains subtract the double-counted loop contribution)
        self._loop_count: Dict[Tuple[str, ...], Any] = {}
        # labels_key -> device bool[num_nodes] (node carries the labels) or
        # None where every logical node does (the unrestricted set among them)
        self._label_mask: Dict[Tuple[str, ...], Optional[Any]] = {}
        # labels_key -> host row_map copy (mask building without a D2H sync)
        self._row_map_np: Dict[Tuple[str, ...], np.ndarray] = {}
        # labels_key -> logical row count of that node scan
        self._scan_rows: Dict[Tuple[str, ...], int] = {}
        # types_key -> (sorted global ids, scan-row perm) device arrays:
        # global-rel-id -> canonical scan row (isomorphism forbid masks)
        self._rel_id_index: Dict[Tuple[str, ...], Tuple[Any, Any]] = {}
        # (types_key, reverse) -> int32 row (node) of each lane of that CSR
        self._csr_rows: Dict[Tuple[Tuple[str, ...], bool], Any] = {}
        # (types_key, reverse) -> the most parallel lanes any (row, col) pair has
        self._longest_run: Dict[Tuple[Tuple[str, ...], bool], int] = {}
        # (CSR key, CSR key) -> int32 per lane a -> b of the first CSR: the
        # lanes b -> a of the second (constrained count chains)
        self._back_counts: Dict[Tuple[Any, Any], Any] = {}
        # types_key -> (int32 per node: its number among the nodes an edge of
        # the types touches, -1 = none; int32 per number: the node)
        self._wedge_ranks: Dict[Tuple[str, ...], Tuple[Any, Any]] = {}
        # (CSR key, the types whose rank numbers the columns) -> its bit rows
        self._wedge_adj: Dict[Any, Optional[Tuple[Any, ...]]] = {}
        # (closing CSR key, first hop's types, second hop's types) -> the
        # two bit rows each closing lane intersects (int32 per lane, -1 none)
        self._closing_rows: Dict[Any, Tuple[Any, Any]] = {}

    # -- nodes -------------------------------------------------------------

    def node_ids(self, ctx) -> Tuple[Any, np.ndarray]:
        """(device sorted unique int64 ids, host copy)."""
        if self._node_ids is None:
            self.node_scan((), ctx)
        return self._node_ids

    @property
    def num_nodes(self) -> int:
        """Size of the DEVICE compact-id space: the logical node count
        rounded up to the shape bucket when bucketing is on (the device
        ``node_ids`` array is tail-padded with an above-every-id sentinel).
        Pad ids exist only on device — degree 0, row_map -1, label masks
        False — so every kernel treats them as absent nodes; keeping the
        static ``num_nodes`` argument on the bucket lattice is what lets
        two graphs of different logical size share compiled programs."""
        if self._node_ids is None:
            raise GraphIndexError("node ids not built yet")
        return int(self._node_ids[0].shape[0])

    @property
    def num_logical_nodes(self) -> int:
        """The graph's own node count: ``num_nodes`` less the bucket's pad
        ids."""
        if self._node_ids is None:
            raise GraphIndexError("node ids not built yet")
        return len(self._node_ids[1])

    def node_scan(self, labels: Tuple[str, ...], ctx):
        """Canonical node scan for a label set: (columns, header, row_map).

        ``row_map[compact_id]`` = row index into the scan's columns, or -1
        when the node does not carry the labels (fused label filtering)."""
        key = tuple(sorted(labels))
        got = self._node_scans.get(key)
        if got is not None:
            return got
        with _build("node_scan", labels=":".join(key)) as sp:
            return self._build_node_scan(key, ctx, sp)

    def _build_node_scan(self, key: Tuple[str, ...], ctx, sp):
        op = self.graph.scan_operator(
            CANON_NODE, T.CTNodeType(frozenset(key)), ctx
        )
        table = op.table
        sp.note("rows", table.size)
        header = op.header
        id_col = table._cols[header.column(E.Id(E.Var(CANON_NODE)))]
        ids_np = _host_logical(id_col, table.size)
        if self._node_ids is None:
            if key != ():
                # the unrestricted scan defines the compact id space
                self.node_scan((), ctx)
            else:
                sorted_ids = np.sort(ids_np)
                if len(sorted_ids) and (sorted_ids[1:] == sorted_ids[:-1]).any():
                    raise GraphIndexError("duplicate node ids")
                # device id array tail-padded to the shape bucket with an
                # above-every-id sentinel (searchsorted stays correct; no
                # query id can equal 2^62); the HOST copy stays logical
                dev_ids = bucket_pad_host(sorted_ids, ID_SENTINEL)[0]
                self._node_ids = (jnp.asarray(dev_ids), sorted_ids)
        _, all_ids = self._node_ids
        n = len(all_ids)
        pos = np.searchsorted(all_ids, ids_np)
        pos = np.clip(pos, 0, max(n - 1, 0))
        if len(ids_np) and not (all_ids[pos] == ids_np).all():
            raise GraphIndexError("node scan id outside the graph id space")
        # device-space length (pad ids map to no scan row)
        row_map = np.full(self.num_nodes, -1, dtype=np.int64)
        row_map[pos] = np.arange(len(ids_np), dtype=np.int64)
        self._row_map_np[key] = row_map
        self._scan_rows[key] = len(ids_np)
        out = (table._cols, header, jnp.asarray(row_map))
        self._node_scans[key] = out
        return out

    def label_mask(self, labels: Tuple[str, ...], ctx) -> Optional[Any]:
        """Device bool[num_nodes]: node carries the label set. ``None``
        when every LOGICAL node does — the empty set, or a label the whole
        graph carries (decided on the host copy of the row map, no device
        read): every node qualifies, so callers skip the mask multiply and
        the count chain knows its weights are still constant. Exact under
        bucketing: a pad node has degree 0, no edge points at it and no
        frontier holds it, so what weight it carries is never read."""
        key = tuple(sorted(labels))
        if not key:
            return None
        if key not in self._label_mask:
            with _build("label_mask", labels=":".join(key)):
                self.node_scan(key, ctx)
                carries = self._row_map_np[key] >= 0
                every = bool(carries[: self.num_logical_nodes].all())
                self._label_mask[key] = None if every else jnp.asarray(carries)
        return self._label_mask[key]

    def hop_mask(
        self, types_key: Tuple[str, ...], reverse: bool,
        labels: Tuple[str, ...], ctx,
    ) -> Optional[Any]:
        """``label_mask(labels)`` as ONE hop needs it: None also where every
        node an edge of this CSR orientation points at carries the labels
        (every LIKES source is a Person) — a fact of the index build, on
        the host, decided once per hop and label set. A hop reads its far
        node's mask through its edges alone, so the mask would pass every
        lane it is read on."""
        mask = self.label_mask(labels, ctx)
        if mask is None:
            return None
        key = (types_key, reverse, tuple(sorted(labels)))
        if key not in self._hop_label_proven:
            self.csr(types_key, reverse, ctx)
            far = self._csr_far_nodes[(types_key, reverse)]
            carries = self._row_map_np[key[2]] >= 0
            self._hop_label_proven[key] = not bool((far & ~carries).any())
        return None if self._hop_label_proven[key] else mask

    def scan_is_whole(self, labels: Tuple[str, ...], ctx) -> bool:
        """True when the node scan of ``labels`` holds every logical node
        exactly once: as many rows as the graph has nodes, and every node
        mapped to one of them (host facts of the build, no device read)."""
        key = tuple(sorted(labels))
        self.node_scan(key, ctx)
        return (
            self._scan_rows[key] == self.num_logical_nodes
            and self.label_mask(key, ctx) is None
        )

    # -- relationships -----------------------------------------------------

    @staticmethod
    def types_key(types) -> Tuple[str, ...]:
        return tuple(sorted(types)) if types else ()

    def rel_scan(self, types_key: Tuple[str, ...], ctx):
        """Canonical relationship scan: (columns, header)."""
        got = self._rel_scans.get(types_key)
        if got is not None:
            return got
        with _build("rel_scan", types=":".join(types_key)) as sp:
            op = self.graph.scan_operator(
                CANON_REL, T.CTRelationshipType(frozenset(types_key)), ctx
            )
            out = (op.table._cols, op.header)
            self._rel_scans[types_key] = out
            self._rel_sizes[types_key] = op.table.size
            sp.note("rows", op.table.size)
        return out

    def rel_row_index(self, types_key: Tuple[str, ...], ctx):
        """(sorted int64 global ids, int64 canonical-scan-row perm) device
        arrays: binary-search bridge from relationship element ids to the
        rows that ``csr``'s ``edge_orig`` walks carry — how a fixed rel
        bound in the input becomes a forbidden edge inside a fused
        var-length walk (reference ``VarLengthExpandPlanner.scala:96``
        filters var-length steps against in-scope rel elements)."""
        got = self._rel_id_index.get(types_key)
        if got is None:
            cols, header = self.rel_scan(types_key, ctx)
            n = self._rel_sizes[types_key]
            id_col = cols[header.column(header.id_expr(header.var(CANON_REL)))]
            ids = _host_logical(id_col, n)
            order = np.argsort(ids, kind="stable").astype(np.int64)
            got = (
                jnp.asarray(bucket_pad_host(ids[order], ID_SENTINEL)[0]),
                jnp.asarray(bucket_pad_host(order, 0)[0]),
            )
            self._rel_id_index[types_key] = got
        return got

    def _edge_endpoints(self, types_key: Tuple[str, ...], ctx):
        """Resolve one type set's relationships to compact endpoint
        positions: (src_pos int64, dst_pos int64, num_nodes) — the shared
        front half of every CSR build (validates endpoints). Not kept:
        each CSR orientation, and the planner's loop-free probe, resolve
        the endpoints anew (a ``searchsorted`` per edge and end)."""
        cols, header = self.rel_scan(types_key, ctx)
        nrel = self._rel_sizes[types_key]
        with _build("edge_endpoints", rows=nrel):
            return self._resolve_endpoints(cols, header, nrel, ctx)

    def _resolve_endpoints(self, cols, header, nrel: int, ctx):
        rel = E.Var(CANON_REL)
        start = cols[header.column(E.StartNode(rel))]
        end = cols[header.column(E.EndNode(rel))]
        _, all_ids = self.node_ids(ctx)
        n_log = len(all_ids)
        s_ids = _host_logical(start, nrel)
        d_ids = _host_logical(end, nrel)
        s = np.clip(np.searchsorted(all_ids, s_ids), 0, max(n_log - 1, 0)).astype(np.int64)
        d = np.clip(np.searchsorted(all_ids, d_ids), 0, max(n_log - 1, 0)).astype(np.int64)
        if len(s_ids) and (
            not (all_ids[s] == s_ids).all() or not (all_ids[d] == d_ids).all()
        ):
            raise GraphIndexError("relationship endpoint not a graph node")
        # the returned node-space size is the DEVICE (bucketed) one: CSR
        # row_ptrs, probe keys (src*N + dst), bitmaps and dense forms must
        # all agree with the kernels' static num_nodes
        return s, d, self.num_nodes

    @staticmethod
    def _sorted_csr(a: np.ndarray, b: np.ndarray, n: int):
        """Lexsort edges by (a, b) and build the row_ptr — the shared back
        half of every CSR build. Returns host (row_ptr, order, a_sorted);
        callers gather their per-edge payloads (col ids, edge origins)
        through ``order``. Asserts the ``csr_sorted`` contract: within
        every row the neighbor column is nondecreasing."""
        order = np.lexsort((b, a))
        a_sorted = a[order]
        if len(order) > 1:
            b_sorted = b[order]
            in_row_order = (b_sorted[1:] >= b_sorted[:-1]) | (
                a_sorted[1:] != a_sorted[:-1]
            )
            if not in_row_order.all():
                raise GraphIndexError(
                    "CSR build violated the sorted-by-neighbor contract"
                )
        row_ptr = np.searchsorted(a_sorted, np.arange(n + 1)).astype(np.int32)
        return row_ptr, order, a_sorted

    def csr(self, types_key: Tuple[str, ...], reverse: bool, ctx):
        """(row_ptr, col_idx, edge_orig) int32/int32/int64 device arrays for
        one orientation of one relationship-type set."""
        got = self._csr.get((types_key, reverse))
        if got is not None:
            return got
        orientation = "reverse" if reverse else "forward"
        with _build("csr", orientation=orientation) as sp:
            return self._build_csr(types_key, reverse, ctx, sp)

    def _build_csr(self, types_key: Tuple[str, ...], reverse: bool, ctx, sp):
        s, d, n = self._edge_endpoints(types_key, ctx)
        sp.note("rows", len(s))
        a, b = (d, s) if reverse else (s, d)
        row_ptr, order, a_sorted = self._sorted_csr(a, b, n)
        degs = row_ptr[1:] - row_ptr[:-1]
        self._csr_max_deg[(types_key, reverse)] = int(degs.max()) if n else 0
        self._csr_lanes[(types_key, reverse)] = len(s)
        pointed_at = np.zeros(n, dtype=bool)
        pointed_at[b] = True  # the nodes this orientation's edges end in
        self._csr_far_nodes[(types_key, reverse)] = pointed_at
        out = (
            # row_ptr is node-dim (replicated); the edge-dim arrays pad to
            # the shape bucket and shard over the active mesh (padded to a
            # shard multiple) — the hash-partitioned-relationship-table
            # analog (SURVEY §2.3). Pad safety: every consumer reads edges
            # through row_ptr ranges (all < the logical edge count) or
            # clips gathers, so the -1 col_idx / 0 edge_orig tail is never
            # observed.
            jnp.asarray(row_ptr),
            device_padded(b[order].astype(np.int32), -1)[0],
            device_padded(order.astype(np.int64), 0)[0],
        )
        self._csr[(types_key, reverse)] = out
        self._csr_span[(types_key, reverse)] = self._row_span(row_ptr, out[0])
        if (types_key, reverse) not in self._edge_keys:
            # this CSR orientation is lexsorted by (a, b) => a*N + b keys
            # sorted (forward: src*N + dst; reverse: dst*N + src); the pad
            # sentinel sorts past every real key so binary-search probes
            # are unaffected. Under a mesh, device_padded leaves the length
            # shard-divisible and row-sharded, so each shard holds a
            # CONTIGUOUS sorted run — the sharded WCOJ count tier
            # (mesh.sharded_range_count) rests on range counts being
            # additive over exactly such partitions, with sentinel lanes
            # never entering a counted range
            keys = a_sorted.astype(np.int64) * n + b[order].astype(np.int64)
            self._edge_keys[(types_key, reverse)] = device_padded(
                keys, (1 << 62)
            )[0]
        if not reverse and types_key not in self._loop_count:
            loops = s[s == d]
            self._loop_count[types_key] = jnp.asarray(
                np.bincount(loops, minlength=n).astype(np.int64)
            )
        return out

    @staticmethod
    def _row_span(row_ptr: np.ndarray, row_ptr_dev) -> RowSpan:
        """The run of rows with an edge, from the host ``row_ptr`` (it
        never falls: two binary searches), and a window over it whose
        length follows the lattice the node space itself follows — so two
        graphs of one deployment share a program — clamped into the node
        space. A type without an edge gets the smallest window; a window
        as long as the node space is ``row_ptr`` itself, not a copy."""
        n = len(row_ptr) - 1
        edges = int(row_ptr[-1])
        lo = int(np.searchsorted(row_ptr, 0, side="right")) - 1 if edges else 0
        hi = int(np.searchsorted(row_ptr, edges, side="left")) if edges else 0
        length = min(round_size(max(hi - lo, 1)), n)
        start = min(lo, n - length)
        window = (
            row_ptr_dev if length == n
            else jnp.asarray(row_ptr[start:start + length + 1])
        )
        return RowSpan(lo, hi, start, window)

    def csr_row_span(
        self, types_key: Tuple[str, ...], reverse: bool, ctx
    ) -> RowSpan:
        """The rows one CSR orientation holds (a host fact of its build):
        a label's nodes are one run of the sorted id space, and a typed CSR
        has rows only inside the run of the labels its edges start from."""
        if (types_key, reverse) not in self._csr_span:
            self.csr(types_key, reverse, ctx)
        return self._csr_span[(types_key, reverse)]

    def csr_undirected(self, types_key: Tuple[str, ...], ctx):
        """(row_ptr, col_idx, edge_orig) for the BOTH-ORIENTATION graph of
        one type set: every relationship contributes an edge from each
        endpoint (self-loops once), with ``edge_orig`` carrying the SAME
        canonical scan row for both orientations — so the var-length
        frontier loop's walked-edge masks (``orig != prev``) implement
        relationship uniqueness across directions for free. One index
        build replaces the classic planner's per-step union of four scan
        orientations (reference ``VarLengthExpandPlanner.scala:264-310``)."""
        got = self._csr_und.get(types_key)
        if got is not None:
            return got
        with _build("csr", orientation="undirected") as sp:
            return self._build_csr_undirected(types_key, ctx, sp)

    def _build_csr_undirected(self, types_key: Tuple[str, ...], ctx, sp):
        s, d, n = self._edge_endpoints(types_key, ctx)
        nrel = len(s)
        sp.note("rows", nrel)
        nonloop = s != d
        a = np.concatenate([s, d[nonloop]])
        b = np.concatenate([d, s[nonloop]])
        eo = np.concatenate(
            [np.arange(nrel, dtype=np.int64), np.arange(nrel, dtype=np.int64)[nonloop]]
        )
        row_ptr, order, _ = self._sorted_csr(a, b, n)
        out = (
            jnp.asarray(row_ptr),
            device_padded(b[order].astype(np.int32), -1)[0],
            device_padded(eo[order], 0)[0],
        )
        self._csr_und[types_key] = out
        return out

    def loop_count(self, types_key: Tuple[str, ...], ctx):
        """Device int64[num_nodes]: self-loop edges per node for one type
        set (built host-side once with the forward CSR)."""
        if types_key not in self._loop_count:
            self.csr(types_key, False, ctx)
        return self._loop_count[types_key]

    def edge_keys(
        self, types_key: Tuple[str, ...], ctx, reverse: bool = False
    ):
        """Sorted int64 device keys for ExpandInto/WCOJ range probes:
        (src*N + dst) forward, (dst*N + src) with ``reverse=True`` (close
        constraints against INCOMING adjacency probe the reverse keys)."""
        if (types_key, reverse) not in self._edge_keys:
            self.csr(types_key, reverse, ctx)
        return self._edge_keys[(types_key, reverse)]

    def edge_keys_by_orig(self, types_key: Tuple[str, ...], ctx):
        """int64[num_rels] device array: the (src*N + dst) probe key of each
        canonical rel-scan row. ``into_close_count_unique`` subtracts a
        carried chain edge from a probe range exactly when its key equals
        the probe key (same key <=> same endpoints; the range covers every
        edge of the type set, so the carried edge is in it iff keys match)."""
        got = self._keys_by_orig.get(types_key)
        if got is None:
            s, d, n = self._edge_endpoints(types_key, ctx)
            got = self._keys_by_orig[types_key] = jnp.asarray(
                bucket_pad_host(
                    s.astype(np.int64) * n + d.astype(np.int64), ID_SENTINEL
                )[0]
            )
        return got

    DENSE_BLOCK = 256  # MXU tile-friendly row-block / pad quantum

    def dense_adj(
        self, types_key: Tuple[str, ...], reverse: bool, ctx,
        max_nodes: Optional[int] = None,
    ) -> Optional[Tuple[Any, int, int]]:
        """Dense bf16[(Npad, Npad)] adjacency with edge-MULTIPLICITY
        entries for the MXU matmul tier (``jit_ops.mxu_close_count`` /
        ``mxu_distinct_pairs``): path counting as blocked ``A @ A`` on the
        systolic array — where the TPU's FLOPs actually are — instead of
        gather/searchsorted streams. Returns ``(matrix, max_entry,
        max_row_sum)`` (the exactness metadata callers use to bound the
        f32 accumulator), or None when the graph is too large for the
        dense form (Npad^2 bf16 per matrix) or a multiplicity exceeds
        bf16's exact-integer range (256). Rows/cols past N are zero.
        ``max_nodes=None`` resolves through the cost model
        (``optimizer.cost.mxu_dense_node_cap``), which honors a
        ``TPU_CYPHER_MXU_DENSE_MAX`` pin verbatim."""
        if max_nodes is None:
            from ...optimizer.cost import mxu_dense_node_cap

            max_nodes = mxu_dense_node_cap()
        key = (types_key, reverse, max_nodes)
        if key not in self._dense_adj:
            self.node_ids(ctx)
            n = self.num_nodes
            if not 0 < n <= max_nodes:
                # cheap size gate BEFORE resolving per-edge endpoints
                self._dense_adj[key] = None
                return None
            s, d, _ = self._edge_endpoints(types_key, ctx)
            out = None
            b = self.DENSE_BLOCK
            npad = -(-n // b) * b
            a, bb = (d, s) if reverse else (s, d)
            dense = np.zeros((npad, npad), dtype=np.int32)
            np.add.at(dense, (a, bb), 1)
            max_entry = int(dense.max()) if len(s) else 0
            if max_entry <= 256:
                # int32 -> bf16 on DEVICE (entries <= 256 are bf16-exact);
                # a host f32 staging copy would double peak host memory
                out = (
                    jnp.asarray(dense).astype(jnp.bfloat16),
                    max_entry,
                    int(dense.sum(axis=1).max()) if len(s) else 0,
                )
            self._dense_adj[key] = out
        return self._dense_adj[key]

    # -- constrained count chains (expand_op.chain_constraint_count) -------

    def csr_rows(self, types_key: Tuple[str, ...], reverse: bool, ctx):
        """Device int32 per lane of ``csr(types_key, reverse)``: its row."""
        key = (types_key, reverse)
        if key not in self._csr_rows:
            from . import jit_ops as J

            rp, ci, _ = self.csr(types_key, reverse, ctx)
            self._csr_rows[key] = J.csr_lane_rows(rp, ci)
        return self._csr_rows[key]

    def longest_run(self, key, ctx) -> int:
        """The most parallel lanes any (row, col) pair of the CSR ``key``
        has."""
        if key not in self._longest_run:
            from . import jit_ops as J

            fault_point("expand")  # the scalar read below
            rp, ci, _ = self.csr(*key, ctx)
            longest = J.csr_longest_run(rp, ci, self.csr_rows(*key, ctx))
            with _obs_trace.sync("expand"):
                self._longest_run[key] = int(longest)
        return self._longest_run[key]

    def back_counts(self, first, second, ctx):
        """Device int32 per lane ``a -> b`` of the CSR ``first`` (a
        ``(types_key, reverse)``): how many lanes ``b -> a`` the CSR
        ``second`` holds. A fact of the graph, probed once (two binary
        searches a lane over ``second``'s sorted keys) and kept."""
        got = self._back_counts.get((first, second))
        if got is None:
            from . import jit_ops as J

            with _build("back_counts"):
                rp, ci, _ = self.csr(*first, ctx)
                got = J.csr_back_counts(
                    rp, ci, self.csr_rows(*first, ctx),
                    self.edge_keys(second[0], ctx, reverse=second[1]),
                    num_nodes=self.num_nodes,
                )
            self._back_counts[(first, second)] = got
        return got

    # bytes of bit rows a constrained count may hold on the device: 6 GiB of
    # a chip's 16 GB, about 155,000 nodes a side without parallel edges (a
    # closing term holds two sets of n * n / 8 bytes a plane)
    WEDGE_MAX_BYTES = 6 << 30
    # the multiple a set of bit rows' number of rows is padded to, so that two
    # graphs of one deployment share their programs (the persons with a friend
    # at SNB SF10: 65,570 to 65,589 over seven seeds, 66,048 rows each)
    WEDGE_ROWS = 512
    # closing lanes ``jit_ops.wedge_close_sum`` intersects at a time (my chip
    # run, PR 37, call M6, SNB SF10's KNOWS, rows of 2,176 words: 0.318 s a
    # pass at 256, 0.257 at 512, 0.297 at 1,024, 0.291 at 2,048, 0.329 at
    # 4,096, 0.439 at 8,192, 0.499 at 16,384)
    WEDGE_CHUNK = 1 << 9

    def wedge_rank(self, types_key: Tuple[str, ...], ctx) -> Tuple[Any, Any]:
        """Of the nodes an edge of the types touches: (device int32 per
        node: its number among them, -1 = it is none; device int32 per
        number: the node, padded with node 0 to a multiple of
        ``WEDGE_ROWS``)."""
        if types_key not in self._wedge_ranks:
            fault_point("expand")  # the count is read back
            with _build("wedge_rank") as sp:
                rp_out, _, _ = self.csr(types_key, False, ctx)
                rp_in, _, _ = self.csr(types_key, True, ctx)
                touched = (rp_out[1:] > rp_out[:-1]) | (rp_in[1:] > rp_in[:-1])
                rank = jnp.where(
                    touched, jnp.cumsum(touched, dtype=jnp.int32) - 1, -1
                )
                with _obs_trace.sync("expand"):
                    count = int(jnp.sum(touched))
                sp.note("nodes", count)
                size = max(-(-count // self.WEDGE_ROWS), 1) * self.WEDGE_ROWS
                # tpulint: allow[pad-invariant] reason=the bit rows' own lattice (multiples of WEDGE_ROWS), not the row buckets': a bucket's pad would be squared
                nodes = jnp.nonzero(touched, size=size, fill_value=0)[0]
                self._wedge_ranks[types_key] = (rank, nodes.astype(jnp.int32))
        return self._wedge_ranks[types_key]

    def wedge_adjacency(self, key, cols, ctx) -> Optional[Tuple[Any, ...]]:
        """The CSR ``key`` as bit rows (``jit_ops.bit_adjacency``) — a row
        per node its types touch, a bit per node the types ``cols`` touch
        (``wedge_rank`` numbers both), one uint32[rows, words] plane per
        binary digit of the most parallel lanes a pair has — or None: two
        such sets (a closing term's) would pass ``WEDGE_MAX_BYTES``."""
        if (key, cols) not in self._wedge_adj:
            from . import jit_ops as J

            with _build("wedge_adjacency") as sp:
                rp, ci, _ = self.csr(*key, ctx)
                row_rank, row_nodes = self.wedge_rank(key[0], ctx)
                col_rank, col_nodes = self.wedge_rank(cols, ctx)
                planes = max(self.longest_run(key, ctx).bit_length(), 1)
                # words in whole lanes of 128: the layout in which the chip
                # keeps a node's row contiguous (with 2,064 words a row it
                # chose column-major and copied both sets on every call)
                size = int(row_nodes.shape[0])
                words = -(-int(col_nodes.shape[0]) // 4096) * 128
                sp.note("planes", planes)
                out = None
                if 2 * planes * size * words * 4 <= self.WEDGE_MAX_BYTES:
                    out = J.bit_adjacency(
                        rp, ci, self.csr_rows(*key, ctx), row_rank, col_rank,
                        size=size, words=words, planes=planes,
                    )
                self._wedge_adj[(key, cols)] = out
        return self._wedge_adj[(key, cols)]

    def closing_pair_rows(self, closing, first_types, second_types, ctx):
        """Device (int32, int32) per lane ``a -> c`` of the CSR ``closing``:
        the rows of ``wedge_adjacency``'s bit rows a closing term intersects
        for the pair — ``a``'s among the first hop's nodes, ``c``'s among
        the second hop's — on the first lane of each pair, else -1. A fact
        of the graph, gathered once and kept."""
        key = (closing, first_types, second_types)
        if key not in self._closing_rows:
            from . import jit_ops as J

            with _build("closing_pair_rows"):
                rp, ci, _ = self.csr(*closing, ctx)
                self._closing_rows[key] = J.closing_pair_rows(
                    rp, ci, self.csr_rows(*closing, ctx),
                    self.wedge_rank(first_types, ctx)[0],
                    self.wedge_rank(second_types, ctx)[0],
                )
        return self._closing_rows[key]

    def csr_lane_count(self, types_key: Tuple[str, ...], reverse: bool, ctx) -> int:
        """Host-cached number of edges one CSR orientation holds (its real
        lanes; computed at build)."""
        if (types_key, reverse) not in self._csr_lanes:
            self.csr(types_key, reverse, ctx)
        return self._csr_lanes[(types_key, reverse)]

    def csr_max_degree(self, types_key: Tuple[str, ...], reverse: bool, ctx) -> int:
        """Host-cached max degree of one CSR orientation (computed at
        build)."""
        if (types_key, reverse) not in self._csr_max_deg:
            self.csr(types_key, reverse, ctx)
        return self._csr_max_deg[(types_key, reverse)]

    # -- id -> compact mapping --------------------------------------------

    def compact_of(self, id_col: Column, ctx) -> Tuple[Any, Any]:
        """Map an int64 element-id column to (compact ids, present mask)."""
        from . import jit_ops as J

        dev_ids, _ = self.node_ids(ctx)
        ids = id_col.data
        if self.num_nodes == 0:
            z = jnp.zeros(ids.shape[0], jnp.int64)
            return z, jnp.zeros(ids.shape[0], bool)
        return J.compact_lookup(dev_ids, ids, id_col.valid)

