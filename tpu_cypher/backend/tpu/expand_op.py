"""Fused CSR expand operators: the TPU-native physical Expand/ExpandInto.

The reference plans every ``Expand`` as relationship-scan + 2 hash joins and
``ExpandInto`` as a 2-key join (``RelationalPlanner.scala:130-189``); on
Spark/Flink those joins ride the engines' shuffle. Here the physical planner
swaps in these operators when the backend is CSR-capable: one fused
repeat+gather over the HBM-resident CSR per hop (``GraphIndex``), with the
classic join cascade kept as a same-header shadow plan for graphs that
cannot be indexed (dangling endpoints, duplicate ids).

Semantics are bag-identical to the classic cascade by construction:

* multiplicity: one output row per (input row, matching edge) — exactly the
  rel-scan join; the far-end node-scan join becomes a compact-id row-map
  gather (``row_map`` = -1 filters nodes lacking the target labels);
* undirected expands mirror the classic scan ∪ swapped-scan union: a
  primary CSR half (loops included) plus the opposite-orientation half with
  self-loops excluded and Start/End reported swapped;
* headers: the operator REUSES the classic plan's RecordHeader, so every
  downstream operator sees identical columns either way.
"""

from __future__ import annotations

import contextlib

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...ir import expr as E
from ...obs import trace as _obs_trace
from ...parallel.mesh import (
    current_mesh,
    mesh_size,
    note_decline,
    note_exchange,
)
from ...runtime.faults import fault_point
from ...relational.header import RecordHeader
from ...relational.ops import RelationalOperator
from . import bucketing
from . import jit_ops as J
from .column import (
    OBJ,
    Column,
    TpuBackendError,
    mask_to_idx as _mask_to_idx,
    mask_to_idx_bucketed as _mask_to_idx_bucketed,
)
from .graph_index import CANON_NODE, CANON_REL, GraphIndex, GraphIndexError, rekey_element_expr


def _flat_in(t):
    """Coerce a (possibly factorized) input table to its flat form before
    positional ``_cols`` access — identity for plain ``TpuTable`` inputs,
    an admission-guarded decompress for ``FactorizedTable`` ones."""
    from .table import ensure_flat

    return ensure_flat(t)


@jax.jit
def _csr_run_bounds(rp, pos, present, nvalid):
    """Per-lane adjacency run bounds straight off the CSR row pointers:
    ``(lo, cnt, total)`` where lane ``i``'s suffix run is
    ``ci[lo[i]:lo[i]+cnt[i]]``. Dead lanes (absent frontier ids, tail
    pads past ``nvalid``) carry ``cnt = 0`` so they contribute no flat
    rows; the clip keeps the row-pointer gather in-bounds for them (an
    OOB gather under jit fills with int64 min)."""
    live = present & (jnp.arange(pos.shape[0], dtype=jnp.int64) < nvalid)
    p = jnp.clip(pos, 0, rp.shape[0] - 2)
    lo = jnp.where(live, jnp.take(rp, p), 0)
    cnt = jnp.where(live, jnp.take(rp, p + 1) - jnp.take(rp, p), 0)
    cnt = jnp.maximum(cnt, 0)
    return lo, cnt, jnp.sum(cnt)


def _mxu_dense_mode() -> bool:
    """Route 2-hop counts through the MXU dense tier (blocked bf16 A @ A,
    ``jit_ops.mxu_close_count``/``mxu_distinct_pairs``)? Defaults to ON for
    accelerator backends (matmuls are where the TPU's FLOPs live) and OFF
    for CPU (dense N^3 does not win there).
    ``TPU_CYPHER_MXU_DENSE=force`` enables it anywhere (correctness tests),
    ``=0`` disables."""
    from ...utils.config import MXU_DENSE

    mode = MXU_DENSE.get()
    if mode == "0":
        return False
    if mode in ("1", "force"):
        return True
    return jax.default_backend() != "cpu"


# how many dense-eligible counts the MXU tier answered. Served by the
# unified obs registry; the view keeps the dict-shaped read path.
from ...obs.metrics import REGISTRY as _OBS_REGISTRY  # noqa: E402
from ...obs.metrics import CounterView  # noqa: E402

MXU_TIER_COUNTS = CounterView(
    _OBS_REGISTRY.counter(
        "tpu_cypher_mxu_tier_total",
        "dense-eligible counts answered per MXU tier",
        labels=("tier",),
    ),
    "tier",
    ("dense",),
)

_MESH_EXPAND_TOTAL = _OBS_REGISTRY.counter(
    "tpu_cypher_mesh_expand_total",
    "fused count chains executed as the explicit shard_map program over "
    "the row-sharded CSR",
)


def _note_chain_forms(forms) -> None:
    """Count a fused count chain's (or tree's) hops by the form each takes
    (``jit_ops.chain_forms`` / ``tree_forms``): in the registry, and as
    ``chain_hops`` on the operator's span."""
    counts = {f: forms.count(f) for f in ("degree", "reduce", "scan")}
    for form, n in counts.items():  # a 0 seeds the series: it exports
        _obs_trace.COUNT_CHAIN_HOPS.inc(n, form=form)
    _obs_trace.note("chain_hops", counts)


_TREE_LANES_TOTAL = _OBS_REGISTRY.counter(
    "tpu_cypher_tree_count_lanes_total",
    "edge lanes (a bucket's pad included) the programs of tree counts read: "
    "every lane of a reduce or scan hop's CSR, both orientations of an "
    "undirected one; a degree hop reads none",
)


_SCAN_NODE_LANES_TOTAL = _OBS_REGISTRY.counter(
    "tpu_cypher_count_scan_node_lanes_total",
    "row-pointer lanes the scan hops of counts gather prefix sums at: the "
    "window of rows the hop's CSR holds and one (jit_ops._csr_spmv), both "
    "orientations of an undirected hop; a degree or reduce hop gathers none",
)
_SCAN_NODE_LANES_TOTAL.inc(0)  # exported from the start


def _note_tree_lanes(forms, hop_data) -> None:
    """The lanes a count's hops read (``hop_data`` as ``path_count_chain``
    takes it, ``forms`` beside it), on the counters and on the open span:
    ``edge_lanes``, every lane of a ``reduce`` or ``scan`` hop's CSR, and
    ``node_lanes``, the row pointers a ``scan`` hop gathers at — its
    window's, or all of them where the hop carries no span."""
    edge_lanes = node_lanes = 0
    for h, form in zip(hop_data, forms):
        rp_a, ci_a, rp_b, ci_b, _, _, span_a, span_b = J.hop_parts(h)
        for rp, ci, span in ((rp_a, ci_a, span_a), (rp_b, ci_b, span_b)):
            if rp is None or form == "degree":
                continue
            edge_lanes += int(ci.shape[0])
            if form == "scan":
                node_lanes += int((rp if span is None else span[0]).shape[0])
    _TREE_LANES_TOTAL.inc(edge_lanes)
    _SCAN_NODE_LANES_TOTAL.inc(node_lanes)
    _obs_trace.note("edge_lanes", edge_lanes)
    _obs_trace.note("node_lanes", node_lanes)


def _hop_arrays(gi: GraphIndex, hop, ctx):
    """One hop as ``jit_ops.path_count_chain`` / ``tree_count`` take it:
    ``(rp_a, ci_a, rp_b, ci_b, loop_cnt, mask, span_a, span_b)`` — the CSR
    whose rows are the hop's near node, for an undirected hop the opposite
    orientation and the self-loop counts beside it, the far node's label
    mask, and per orientation the window of rows its CSR holds
    (``GraphIndex.csr_row_span``: what bounds a ``scan`` hop's node side).
    The mask is None where the index build proves the labels of every node
    the hop's edges reach (every LIKES source is a Person;
    ``GraphIndex.hop_mask``), in both orientations for an undirected hop.
    The one place that builds a hop of a count, and so the one that decides
    whether it needs a mask."""
    rp, ci, _ = gi.csr(hop.types_key, hop.backwards, ctx)
    span = gi.csr_row_span(hop.types_key, hop.backwards, ctx).window
    mask = gi.hop_mask(hop.types_key, hop.backwards, hop.far_labels, ctx)
    if not getattr(hop, "undirected", False):
        return rp, ci, None, None, None, mask, span, None
    rp_b, ci_b, _ = gi.csr(hop.types_key, not hop.backwards, ctx)
    span_b = gi.csr_row_span(hop.types_key, not hop.backwards, ctx).window
    if mask is None:
        mask = gi.hop_mask(hop.types_key, not hop.backwards, hop.far_labels, ctx)
    return rp, ci, rp_b, ci_b, gi.loop_count(hop.types_key, ctx), mask, span, span_b


def _pad_mask(mask, npad: int):
    """Optional bool[num_nodes] label mask -> bf16 0/1[(npad,)] or None."""
    if mask is None:
        return None
    return jnp.pad(
        mask.astype(jnp.bfloat16), (0, npad - mask.shape[0])
    )


def _owner_name(e: E.Expr) -> Optional[str]:
    if isinstance(e, E.Var):
        return e.name
    inner = getattr(e, "expr", None)
    if isinstance(inner, E.Var):
        return inner.name
    return None


def _fused_chain_walk(
    gi: GraphIndex, ctx, hops, id_col: Column, final,
    carry_rels=frozenset(), mask_pairs=None,
):
    """Walk a stacked expand chain carrying only (base endpoint key, current
    position, liveness) per partial path — the shared spine of the fused
    DISTINCT-endpoints count and the fused ExpandInto close count. Middle
    hops run ``distinct_hop_materialize``; at the OUTERMOST hop (``hops[0]``)
    ``final(rp, ci, eo, pos, deg, akey, mask, prevs, order, mask_idx,
    total)`` fuses the terminal computation. Returns final's int, or 0 when
    any hop empties.

    Relationship uniqueness (openCypher isomorphism — the reference's
    per-pair ``id(r_i) <> id(r_j)`` filters, Neo4j ``AddUniquenessPredicates``)
    is enforced inside the walk: ``carry_rels`` names hops whose edge scan
    rows ride along per partial path, and ``mask_pairs[late_rel]`` lists the
    carried rels that hop's edge must differ from (violating paths die, as
    in ``varlen_hop``). ``final`` receives the carried arrays (``prevs``,
    name-sorted per ``order``) plus its own ``mask_idx``."""
    gi.node_ids(ctx)
    if gi.num_nodes == 0:
        return 0
    pos, present = gi.compact_of(id_col, ctx)
    akey = pos  # base endpoint key = its compact position
    mask_pairs = mask_pairs or {}
    carried: Dict[str, Any] = {}
    last = hops[0]
    bucketed = bucketing.enabled()
    for hop in reversed(hops):
        rp, ci, eo = gi.csr(hop.types_key, hop.backwards, ctx)
        mask = gi.label_mask(hop.far_labels, ctx)
        deg, t_dev = J.expand_degrees_total(rp, pos, present)
        with _obs_trace.sync("expand"):
            total = int(t_dev)
        if total == 0:
            return 0
        # bucketed: the static materialize size rounds up to the lattice;
        # the true count rides as a traced operand (``nvalid``) and pad
        # lanes come out dead (present=False / excluded from the final sum)
        size = bucketing.round_size(total)
        # always pass the traced count when bucketing (even on an exact
        # bucket hit) so each bucket size compiles exactly ONE program
        nvalid = t_dev if bucketed else None
        order = tuple(sorted(carried))
        prevs = tuple(carried[r] for r in order)
        midx = tuple(order.index(r) for r in mask_pairs.get(hop.rel_fld, ()))
        if hop is last:
            return final(
                rp, ci, eo, pos, deg, akey, mask, prevs, order, midx, size,
                nvalid,
            )
        if order or hop.rel_fld in carry_rels:
            akey, pos, orig, prevs_out, present = J.unique_hop_materialize(
                rp, ci, eo, pos, deg, akey, mask, prevs,
                total=size, mask_idx=midx, nvalid=nvalid,
            )
            carried = dict(zip(order, prevs_out))
            if hop.rel_fld in carry_rels:
                carried[hop.rel_fld] = orig
        else:
            akey, pos, present = J.distinct_hop_materialize(
                rp, ci, pos, deg, akey, mask, total=size, nvalid=nvalid
            )
    raise AssertionError("unreachable: loop always hits hops[0]")


def _chain_enforcement_spec(hops, pairs, close_rel=None, close_types=None):
    """Compile a set of rel-uniqueness pairs into walk enforcement:
    ``(carry_rels, mask_pairs, close_partners)``, or None when any pair
    cannot be enforced in the fused walk (undirected hops, duplicate rel
    bindings, rels outside the subtree, or DIFFERENT type sets — carried
    edge scan rows are only comparable within one canonical rel scan).

    ``close_partners`` lists chain rels that must differ from the closing
    relationship (``into_close_count_unique`` subtracts them from the probe
    range); chain-chain pairs become a mask at the later-executed hop."""
    if any(h.undirected for h in hops):
        return None
    exec_rels = [h.rel_fld for h in reversed(hops)]  # execution order
    if len(set(exec_rels)) != len(exec_rels):
        return None
    types_of = {h.rel_fld: h.types_key for h in hops}
    if close_rel is not None:
        if close_rel in types_of:
            return None
        types_of[close_rel] = close_types
    pos_of = {r: i for i, r in enumerate(exec_rels)}
    carry = set()
    mask_pairs: Dict[str, Tuple[str, ...]] = {}
    close_partners = []
    for ra, rb in pairs:
        if ra == rb or ra not in types_of or rb not in types_of:
            return None
        if types_of[ra] != types_of[rb]:
            return None
        if close_rel is not None and close_rel in (ra, rb):
            other = rb if ra == close_rel else ra
            if other not in pos_of:
                return None
            if other not in close_partners:
                close_partners.append(other)
            if other != exec_rels[-1]:
                carry.add(other)
            continue
        if ra not in pos_of or rb not in pos_of:
            return None
        early, late = (ra, rb) if pos_of[ra] < pos_of[rb] else (rb, ra)
        if early not in mask_pairs.get(late, ()):
            mask_pairs[late] = mask_pairs.get(late, ()) + (early,)
        carry.add(early)
    return frozenset(carry), mask_pairs, tuple(close_partners)


def _collected_pairs(hops, extra=()):
    """Deduplicated uniqueness pairs attached anywhere on a fused subtree."""
    seen = []
    for op in list(hops) + list(extra):
        for p in getattr(op, "enforced_pairs", ()):
            if p not in seen:
                seen.append(p)
    return tuple(seen)


_WEDGE_LANES_TOTAL = _OBS_REGISTRY.counter(
    "tpu_cypher_chain_constraint_wedge_lanes_total",
    "candidate (a, c) pairs the closing program of constrained count chains "
    "went over: the closing lanes of the chunks it walked",
)


# (equality, closing edge) -> (is the whole chain's count a term, the sign
# of each other term): inclusion and exclusion over ``same`` (wedges with
# a = c), ``closed`` (wedges an a-c edge closes) and ``same_closed`` (both)
_CONSTRAINED_TERMS = {
    ("neq", None): (True, {"same": -1}),
    ("eq", None): (False, {"same": 1}),
    (None, "closed"): (False, {"closed": 1}),
    (None, "open"): (True, {"closed": -1}),
    ("neq", "closed"): (False, {"closed": 1, "same_closed": -1}),
    ("neq", "open"): (True, {"same": -1, "closed": -1, "same_closed": 1}),
    ("eq", "closed"): (False, {"same_closed": 1}),
    ("eq", "open"): (False, {"same": 1, "same_closed": -1}),
}


def _constrained_pair(names, constraints):
    """The constraints of a constrained chain count read against the
    nodes of the pattern's path (``CsrExpandOp._pattern_path``): ``(i,
    equality, edge)`` — the pair is ``names[i]`` / ``names[i + 2]``,
    ``equality`` None, ``"neq"`` or ``"eq"``, ``edge`` None or ``(types_key,
    from_first, negated)`` with ``from_first`` saying whether the closing
    edge leaves ``names[i]``. None: the constraints are not all on one pair
    two hops apart, or hold two of a kind."""
    at = {name: i for i, name in enumerate(names)}
    pair, equality, edge = None, None, None
    for kind, x, y, *rest in constraints:
        if x not in at or y not in at or abs(at[x] - at[y]) != 2:
            return None
        if pair not in (None, {x, y}):
            return None
        pair = {x, y}
        if kind == "edge":
            if edge is not None:
                return None
            types_key, negated = rest
            edge = (types_key, at[x] < at[y], negated)
        else:
            if equality is not None:
                return None
            equality = kind
    return min(at[n] for n in pair), equality, edge


class _FusedExpandBase(RelationalOperator):
    """Shared machinery: header delegation + fallback + column assembly."""

    def __init__(
        self, in_plan: RelationalOperator, classic: RelationalOperator, graph_obj
    ):
        super().__init__(in_plan, classic)
        self._graph_obj = graph_obj

    def _with_pair(self, pair, predicate) -> "RelationalOperator":
        """Clone with one relationship-uniqueness pair enforced INSIDE the
        operator (``plan_filter_fastpath`` drops the filter). The classic
        shadow keeps the dropped predicate as a real FilterOp, so every
        fallback path stays bag-identical to the generic plan."""
        from ...relational.ops import FilterOp

        kw = self._ctor_kwargs()
        kw["enforced_pairs"] = self.enforced_pairs + (tuple(sorted(pair)),)
        return type(self)(
            self.children[0],
            FilterOp(self.children[1], predicate),
            self._graph_obj,
            **kw,
        )

    def _enforce_pair_ids(self, gi: GraphIndex, ctx, row, orig):
        """Row-keep mask for the materializing path: for each enforced
        pair, compare element ids — this op's own relationship reads the
        canonical rel-scan id column at ``orig``; any other rel reads its
        input-table id column at ``row`` (element ids are global, so the
        comparison is sound across type sets and fallback paths)."""
        in_op = self.children[0]
        in_t = _flat_in(in_op.table)
        rel_cols, rel_header = gi.rel_scan(self.types_key, ctx)
        canon_id = rel_header.id_expr(rel_header.var(CANON_REL))
        own_ids = None

        def ids_of(r):
            nonlocal own_ids
            if r == self.rel_fld:
                if own_ids is None:
                    own_ids = jnp.take(
                        rel_cols[rel_header.column(canon_id)].data, orig
                    )
                return own_ids
            h = in_op.header
            try:
                col = in_t._cols[h.column(h.id_expr(h.var(r)))]
            except (KeyError, ValueError) as exc:
                raise GraphIndexError(f"uniqueness rel {r!r} unmapped") from exc
            return jnp.take(col.data, row)

        keep = None
        for ra, rb in self.enforced_pairs:
            k = ids_of(ra) != ids_of(rb)
            keep = k if keep is None else keep & k
        return keep

    def _apply_enforced_pairs(self, gi, ctx, row, orig, extras, n_out):
        """Materializing-path enforcement: mask rows violating any enforced
        pair and compact (``extras``: whatever arrays ride along — far
        rows, swapped flags). Shared by the expand and expand-into
        materializers so the keep/compact discipline cannot diverge. Under
        bucketing the arrays may carry pad lanes past ``n_out`` (masked
        dead) and the compaction itself is bucket-sized."""
        if not self.enforced_pairs or not n_out:
            return row, orig, extras, n_out
        # the enforcement compact syncs a count on both branches (the
        # bucketed one inside _mask_to_idx_bucketed): same site as every
        # other mask compaction
        fault_point("compact")
        keep = self._enforce_pair_ids(gi, ctx, row, orig)
        if bucketing.enabled():
            if int(row.shape[0]) != n_out:
                keep = keep & J.row_tail_mask(row, n_out)
            idx, n2 = _mask_to_idx_bucketed(keep)
            taken = J.tree_take((row, orig) + tuple(extras), idx)
            return taken[0], taken[1], tuple(taken[2:]), n2
        n_dev = J.mask_sum(keep)
        with _obs_trace.sync("compact"):
            n2 = int(n_dev)
        if n2 != n_out:
            # tpulint: allow[pad-invariant] reason=bucketing-off branch only (the enabled branch above routes through _mask_to_idx_bucketed); exact size is the contract here
            idx = J.mask_nonzero(keep, size=n2)
            taken = J.tree_take((row, orig) + tuple(extras), idx)
            row, orig, extras = taken[0], taken[1], tuple(taken[2:])
            n_out = n2
        return row, orig, extras, n_out

    def _compute_header(self) -> RecordHeader:
        full = self.children[1].header
        req = getattr(self, "required_exprs", None)
        if req is None:
            return full
        # column pruning (relational/prune.py): emit only mentioned exprs
        m = {e: full.column(e) for e in full.expressions if e in req}
        return RecordHeader(m, full.paths)

    @property
    def graph(self):
        return self._graph_obj

    def _compute_table(self):
        try:
            return self._fused_table()
        except (GraphIndexError, TpuBackendError):
            # shadow plan: identical header, so identical columns
            return self.children[1].table

    # -- column assembly ---------------------------------------------------

    def _gather_plan(
        self,
        plan: Dict[str, Tuple[Column, str]],
        idx_by_tag: Dict[str, Any],
        null_mask_by_tag: Optional[Dict[str, Any]] = None,
        count: Optional[int] = None,
    ) -> Dict[str, Column]:
        """Execute a tagged gather plan: ONE jitted dispatch per index
        source for all device columns, host path for OBJ columns. A tag
        with an entry in ``null_mask_by_tag`` gathers outer-join style:
        rows where the mask is False come out null. Empty source columns
        (zero-row scans) take the per-column path, whose empty-source
        branch emits all-null rows instead of a non-empty take from an
        empty axis. ``count``: bucketed true row count — index arrays
        longer than it carry pad lanes, gathered device rows past it come
        out invalid, OBJ columns gather the exact prefix."""
        masks = null_mask_by_tag or {}
        out: Dict[str, Column] = {}
        for tag, idx in idx_by_tag.items():
            group = {c: s for c, (s, t) in plan.items() if t == tag}
            if not group:
                continue
            mask = masks.get(tag)
            size = int(idx.shape[0])
            counted = count is not None and mask is None and size != count
            dev = {
                c: (s.data, s.valid, s.int_flag)
                for c, s in group.items()
                if s.kind != OBJ and not (mask is not None and len(s) == 0)
            }
            if dev:
                if counted:
                    taken = J.cols_take_counted(dev, idx, count)
                else:
                    taken = (
                        J.cols_take(dev, idx)
                        if mask is None
                        else J.cols_take_or_null(dev, idx, mask)
                    )
                for c, (d, v, i) in taken.items():
                    s = group[c]
                    if counted:
                        out[c] = Column(
                            s.kind, d, v, s.vocab, int_flag=i,
                            pad=size - count,
                            pad_synth=s.valid is None or s.pad_synth,
                        )
                    else:
                        out[c] = Column(s.kind, d, v, s.vocab, int_flag=i)
            idx_host = None
            for c, s in group.items():
                if c in out:
                    continue
                if counted:
                    if idx_host is None:
                        idx_host = np.asarray(idx)[:count]
                    out[c] = s.take(idx_host)
                    continue
                out[c] = s.take(idx) if mask is None else s.take_or_null(idx, mask)
        return out

    def _assemble(
        self,
        gi: GraphIndex,
        row,
        orig,
        swapped,
        far_rows,
        far_labels: Tuple[str, ...],
        rel_var: str,
        far_var: Optional[str],
        n_out: int,
    ):
        """Gather every output column for the fused result.

        ``row``: input-row index per output row; ``orig``: canonical
        rel-scan row per output row; ``swapped``: bool array (or None) —
        report Start/End swapped for those rows; ``far_rows``: row in the
        far-end canonical node scan (only when ``far_var`` is set)."""
        from .table import TpuTable

        ctx = self.context
        in_op = self.children[0]
        in_t = _flat_in(in_op.table)
        rel_cols, rel_header = gi.rel_scan(self.types_key, ctx)
        if far_var is not None:
            node_cols, node_header, _ = gi.node_scan(far_labels, ctx)
        header = self.header
        canon_rel = E.Var(CANON_REL)
        canon_node = E.Var(CANON_NODE)
        # gather plan: (source column, which index) per output column; the
        # actual gathers run as ONE jitted dispatch per index source
        plan: Dict[str, Tuple[Column, str]] = {}
        swap_plan: Dict[str, Tuple[Column, Column]] = {}
        for e in header.expressions:
            col = header.column(e)
            if col in plan or col in swap_plan:
                continue
            if e in in_op.header:
                plan[col] = (in_t._cols[in_op.header.column(e)], "row")
                continue
            owner = _owner_name(e)
            if owner == rel_var:
                key = rekey_element_expr(e, canon_rel)
                if swapped is not None and isinstance(e, (E.StartNode, E.EndNode)):
                    flipped = (
                        E.EndNode(canon_rel)
                        if isinstance(e, E.StartNode)
                        else E.StartNode(canon_rel)
                    )
                    swap_plan[col] = (
                        rel_cols[rel_header.column(key)],
                        rel_cols[rel_header.column(flipped)],
                    )
                    continue
                if key is None or key not in rel_header:
                    raise GraphIndexError(f"unmapped rel expr {e!r}")
                plan[col] = (rel_cols[rel_header.column(key)], "orig")
                continue
            if far_var is not None and owner == far_var:
                key = rekey_element_expr(e, canon_node)
                if key is None or key not in node_header:
                    raise GraphIndexError(f"unmapped node expr {e!r}")
                plan[col] = (node_cols[node_header.column(key)], "far")
                continue
            raise GraphIndexError(f"unmapped expr {e!r}")
        count = n_out if bucketing.enabled() else None
        out = self._gather_plan(
            plan, {"row": row, "orig": orig, "far": far_rows}, count=count
        )
        for c, (a, b) in swap_plan.items():
            data, valid = J.gather_swapped(
                a.data, b.data, a.valid, b.valid, orig, swapped
            )
            size = int(data.shape[0])
            if count is not None and size != count:
                live = J.row_tail_mask(data, count)
                valid = live if valid is None else valid & live
                out[c] = Column(
                    a.kind, data, valid, a.vocab, pad=size - count,
                    pad_synth=a.valid is None and b.valid is None,
                )
            else:
                out[c] = Column(a.kind, data, valid, a.vocab)
        return TpuTable(out, n_out)


class _TreeCounted:
    """What ``CsrExpandOp`` and ``CsrOptionalExpandOp`` share: the top of a
    stack of them answers ``count(*)`` for the whole stack."""

    def tree_count(self) -> Optional[int]:
        """count(*) of this operator's rows where it and the expands stacked
        under it are a tree of the pattern — a path, a star, OPTIONAL leaves —
        WITHOUT a row of it: one 64-bit multiplicity per node from the leaves
        to the root, vectors that meet in a node multiplied, ``max(branch, 1)``
        for an OPTIONAL branch (``jit_ops.tree_count``: one program, one
        blocking read). The linked chain is its case without a branch and keeps
        its own program (``_count_via_chain``). None — the reason on the span
        as ``tree_decline`` — and the caller builds the rows."""
        ops = _tree_ops(self)
        optional = sum(isinstance(op, CsrOptionalExpandOp) for op in ops)
        linked = not optional and all(
            upper.frontier_fld == lower.far_fld for upper, lower in zip(ops, ops[1:])
        )
        with _obs_trace.span(
            "tree_count", kind="kernel", hops=len(ops), optional_branches=optional
        ):
            try:
                gi = GraphIndex.of(self.graph)
                if linked:
                    _obs_trace.note("branches", 1)
                    return self._count_via_chain(gi, self.context)
                read = _read_tree(ops)
                if isinstance(read, str):
                    _obs_trace.note("tree_decline", read)
                    return None
                if current_mesh() is not None and mesh_size() > 1:
                    note_decline("expand", "tree_count")
                    _obs_trace.note("tree_decline", "mesh")
                    return None
                return _tree_count_program(gi, ops[-1], *read)
            except (GraphIndexError, TpuBackendError) as exc:
                _obs_trace.note("tree_decline", type(exc).__name__)
                return None


class CsrExpandOp(_TreeCounted, _FusedExpandBase):
    """Fused (frontier)-[rel]->(far) expansion over the graph CSR.

    Replaces the scan+2-joins cascade: frontier element ids map to compact
    ids (one searchsorted), per-row degrees come from ``row_ptr``, and the
    output is materialized with fixed-size repeat+gather — O(output) work,
    no per-hop sorting (the CSR was sorted once at index build)."""

    def __init__(
        self,
        in_plan: RelationalOperator,
        classic: RelationalOperator,
        graph_obj,
        *,
        frontier_fld: str,
        rel_fld: str,
        far_fld: str,
        types_key: Tuple[str, ...],
        undirected: bool,
        backwards: bool,
        far_labels: Tuple[str, ...],
        frontier_scan: Optional[Tuple[str, ...]] = None,
        enforced_pairs: Tuple[Tuple[str, str], ...] = (),
    ):
        super().__init__(in_plan, classic, graph_obj)
        self.frontier_fld = frontier_fld
        self.rel_fld = rel_fld
        self.far_fld = far_fld
        self.types_key = types_key
        self.undirected = undirected
        self.backwards = backwards
        self.far_labels = far_labels
        # the label set whose plain node scan of this graph IS the input
        # (nothing joined, filtered or unwound on the way), else None: what
        # lets the count chain know its frontier without reading it
        self.frontier_scan = frontier_scan
        self.enforced_pairs = enforced_pairs

    def _ctor_kwargs(self) -> Dict[str, Any]:
        return dict(
            frontier_fld=self.frontier_fld,
            rel_fld=self.rel_fld,
            far_fld=self.far_fld,
            types_key=self.types_key,
            undirected=self.undirected,
            backwards=self.backwards,
            far_labels=self.far_labels,
            frontier_scan=self.frontier_scan,
        )

    def _show_inner(self) -> str:
        arrow = "-" if self.undirected else ("<-" if self.backwards else "->")
        t = "|".join(self.types_key) or "*"
        uniq = (
            " uniq" + ",".join(f"({a}<>{b})" for a, b in self.enforced_pairs)
            if self.enforced_pairs
            else ""
        )
        return f"({self.frontier_fld}){arrow}[{self.rel_fld}:{t}]({self.far_fld}){uniq}"

    def _expand_half(self, gi: GraphIndex, pos, present, reverse: bool, drop_loops: bool):
        """One CSR expand half. Returns ``(row, nbr, orig, count)`` where
        ``count`` is the TRUE emission count; under bucketing the arrays
        are tail-padded past it (pad lanes sanitized to row 0)."""
        ctx = self.context
        rp, ci, eo = gi.csr(self.types_key, reverse, ctx)
        deg, t_dev = J.expand_degrees_total(rp, pos, present)
        with _obs_trace.sync("expand"):
            total = int(t_dev)
        # pre-flight: (row, nbr, orig) int64 lanes + every gathered output
        # column (8B data + 1B mask), padded on the bucket lattice
        bucketing.admit(
            total, 24 + 9 * max(len(self.header.expressions), 1), "expand"
        )
        if bucketing.enabled():
            size = bucketing.round_size(total)
            row, nbr, orig, live = J.expand_materialize_counted(
                rp, ci, eo, pos, deg, t_dev, size=size
            )
            if drop_loops and total:
                keep = J.drop_loops_mask(nbr, pos, row) & live
                idx, total = _mask_to_idx_bucketed(keep)
                row, nbr, orig = J.tree_take((row, nbr, orig), idx)
            return row, nbr, orig, total
        row, nbr, orig = J.expand_materialize(rp, ci, eo, pos, deg, total=total)
        if drop_loops and total:
            keep = J.drop_loops_mask(nbr, pos, row)
            idx, total = _mask_to_idx(keep)
            row, nbr, orig = J.tree_take((row, nbr, orig), idx)
        return row, nbr, orig, total

    def _stacked_expands(self, linked: bool) -> List["CsrExpandOp"]:
        """This op and the CsrExpandOps directly stacked under it over the
        same graph (cache wraps are identity), deepest last. ``linked``:
        only as far as each hop expands FROM its child's far node —
        branching patterns ((x)-->(y), (x)-->(z)) stack expands whose
        frontier is NOT the previous far end."""
        from ...relational.ops import CacheOp

        hops: List[CsrExpandOp] = [self]
        node = self
        while True:
            child = node.children[0]
            while isinstance(child, CacheOp):
                child = child.children[0]
            if (
                isinstance(child, CsrExpandOp)
                and child._graph_obj is self._graph_obj
                and (not linked or node.frontier_fld == child.far_fld)
            ):
                hops.append(child)
                node = child
                continue
            return hops

    def _chain_hops(self) -> List["CsrExpandOp"]:
        """Walk the input chain of directly-stacked, linked CsrExpandOps
        (deepest last): composing the SpMVs of hops that are not linked
        would count the wrong paths. Intermediate output columns are
        irrelevant for counting: each op's row MULTISET is exactly its
        child's multiset expanded, so a per-node multiplicity vector carries
        complete information down the chain."""
        return self._stacked_expands(linked=True)

    def _count_via_chain(self, gi: GraphIndex, ctx) -> int:
        """Whole-chain count as ONE jitted program (``path_count_chain``):
        the engine's replacement for the reference's 2k-join cascade on a
        count(*) query (``RelationalPlanner.scala:130-165``)."""
        hops = self._chain_hops()
        base = hops[-1]
        in_op = base.children[0]
        in_t = _flat_in(in_op.table)
        frontier_var = in_op.header.var(base.frontier_fld)
        id_col = in_t._cols[in_op.header.column(in_op.header.id_expr(frontier_var))]
        gi.node_ids(ctx)  # build the compact id space (validates the graph)
        if gi.num_nodes == 0:
            return 0
        # the fused count is an expand-class dispatch: its count syncs sit
        # behind the expand fault site (injection + deadline coverage)
        fault_point("expand")
        pairs = _collected_pairs(hops)
        if pairs:
            # rel-uniqueness enforced inside the count: the SpMV carries
            # only per-node multiplicities (no edge identity), so unique
            # chains count via the edge-carrying walk instead
            spec = _chain_enforcement_spec(hops, pairs)
            if spec is None:
                raise GraphIndexError(
                    "unenforceable uniqueness pairs: classic shadow counts"
                )
            carry, mask_pairs, _ = spec

            def final(rp, ci, eo, pos, deg, akey, mask, prevs, order, midx,
                      total, nvalid=None):
                return int(
                    J.chain_count_final_unique(
                        rp, ci, eo, pos, deg, mask, prevs,
                        total=total, mask_idx=midx, nvalid=nvalid,
                    )
                )

            return _fused_chain_walk(
                gi, ctx, hops, id_col, final, carry, mask_pairs
            )
        if len(hops) == 1 and not self.undirected and not self.far_labels:
            # single unrestricted hop: the O(frontier) degree sum beats
            # the chain's O(nodes) degree vector
            pos, present = gi.compact_of(id_col, ctx)
            rp, _, _ = gi.csr(self.types_key, self.backwards, ctx)
            n_dev = J.frontier_degree_sum(rp, pos, present)
            _note_chain_forms(("degree",))
            with _obs_trace.sync("expand"):
                return int(n_dev)
        # the frontier holds every node exactly once: the input is the
        # graph's own scan of a label set that every node carries, row for
        # row (facts of the plan and of the index build; nothing is read)
        whole = (
            base.frontier_scan is not None
            # no null id: no validity, or one that marks the pad tail alone
            and (id_col.valid is None or id_col.pad_synth)
            and in_t.size == id_col.logical_len == gi.num_logical_nodes
            and gi.scan_is_whole(base.frontier_scan, ctx)
        )
        hop_data = [  # deepest (first executed) hop first
            _hop_arrays(gi, hop, ctx) for hop in reversed(hops)
        ]
        dev_ids, _ = gi.node_ids(ctx)
        chain = J.path_count_chain
        mesh = current_mesh()
        if mesh is not None:
            # explicit shard_map SpMV over the row-sharded CSR (GSPMD's
            # automatic partitioning of the global cumsum degenerates);
            # requires every edge array padded to the mesh size — true for
            # CSRs built under the mesh, checked for safety
            size = mesh_size()
            axis = mesh.axis_names[0]
            divisible = all(
                (h[1].shape[0] % size == 0)
                and (h[3] is None or h[3].shape[0] % size == 0)
                for h in hop_data
            )
            if divisible and size > 1:
                chain = J.path_count_chain_on_mesh(mesh, axis)
            elif size > 1:
                note_decline("expand", "unpadded_edges")
        forms = J.chain_forms(
            [h[5] is not None for h in reversed(hop_data)], whole
        )
        _note_chain_forms(forms)
        _note_tree_lanes(forms, list(reversed(hop_data)))
        # a whole frontier is not read: none is handed to the program
        frontier = (None,) * 3 if whole else (dev_ids, id_col.data, id_col.valid)
        on_mesh = chain is not J.path_count_chain
        if on_mesh:
            _obs_trace.note("expand_shards", size)
        with (
            _obs_trace.span("mesh_expand", kind="mesh") if on_mesh
            else contextlib.nullcontext()
        ):
            n_dev = chain(
                *frontier, tuple(hop_data), num_nodes=gi.num_nodes, whole=whole
            )
        if on_mesh:
            # per edge pass (two for an undirected hop) every shard hands
            # the mesh one 64-bit scalar in the reduce form and one per row
            # of the CSR's window in the scan form; a degree hop reads no edge
            words = 0
            for h, form in zip(reversed(hop_data), forms):
                spans = [sp for sp in J.hop_parts(h)[6:] if sp is not None]
                if form == "reduce":
                    words += len(spans)
                elif form == "scan":
                    words += sum(int(sp[0].shape[0]) - 1 for sp in spans)
            note_exchange("expand", size * 8 * words)
            _MESH_EXPAND_TOTAL.inc()
        with _obs_trace.sync("expand"):  # the read that waits for the chain
            return int(n_dev)

    def _pattern_path(self):
        """The expands stacked under this one (same graph, caches looked
        through) read as a path of the PATTERN, whatever node the plan
        started from and in whatever order it grew: ``(names, steps,
        base)`` with ``steps[j] = (types_key, reverse, op)`` the CSR whose
        rows are ``names[j]`` and whose columns are ``names[j + 1]``, and
        ``base`` the deepest expand (its input is the rows everything
        starts from, bound to ``base.frontier_fld``). None: an undirected
        hop, a node bound twice, a pattern that branches."""
        ops = self._stacked_expands(linked=False)
        base = ops[-1]
        links: Dict[str, list] = {base.frontier_fld: []}
        for op in reversed(ops):  # the order executed
            if op.undirected or op.frontier_fld not in links or op.far_fld in links:
                return None
            links[op.frontier_fld].append((op.far_fld, op.backwards, op))
            links[op.far_fld] = [(op.frontier_fld, not op.backwards, op)]
        ends = [name for name, out in links.items() if len(out) == 1]
        if len(ends) != 2 or any(len(out) > 2 for out in links.values()):
            return None
        names, steps, came_by = [ends[0]], [], None
        while len(names) <= len(ops):
            far, reverse, op = next(
                link for link in links[names[-1]] if link[2] is not came_by
            )
            names.append(far)
            steps.append((op.types_key, reverse, op))
            came_by = op
        return names, steps, base

    def chain_constraint_count(self, constraints) -> Optional[int]:
        """count(*) of this chain's rows under ``constraints`` between two
        of its nodes two hops apart (``relational.ops._node_pair_constraint``:
        ``a <> c``, ``a = c``, ``[NOT] (a)-[:T]->(c)``, at most one of each
        kind), WITHOUT a row of the chain:

            count = sum over wedges (a -> b -> c) of left[a] * right[c] * [constraints]

        with ``left[a]`` the ways the pattern's hops before the pair reach
        ``a`` and ``right[c]`` the completions of the hops after it from
        ``c`` (``jit_ops.chain_node_weights``). By inclusion and exclusion
        over the whole chain's count, the wedges with ``a = c`` (one cached
        count per first-hop lane, ``GraphIndex.back_counts``) and the
        wedges an edge between ``a`` and ``c`` closes (EXISTS: parallel
        closing edges count once; ``jit_ops.wedge_close_sum``, two bit rows
        intersected per closing pair). None where the shape does not fit —
        an undirected hop, a branching pattern, a pair further apart,
        relationship uniqueness the chain enforces itself (self-loops under
        a type walked twice), bit rows too large for the device, a mesh —
        and the caller builds the rows."""
        try:
            path = self._pattern_path()
            if path is None:
                return None
            names, steps, base = path
            if _collected_pairs([op for _, _, op in steps]):
                return None
            read = _constrained_pair(names, constraints)
            if read is None:
                return None
            if current_mesh() is not None and mesh_size() > 1:
                note_decline("expand", "chain_constraint")
                return None
            gi = GraphIndex.of(self.graph)
            with _obs_trace.span(
                "chain_constraint", kind="kernel", constraints=len(constraints)
            ):
                return self._constrained_count(gi, names, steps, base, *read)
        except (GraphIndexError, TpuBackendError):
            return None

    def _constrained_count(self, gi, names, steps, base, i, equality, edge):
        ctx = self.context
        gi.node_ids(ctx)
        n = gi.num_nodes
        if n == 0:
            return 0
        fault_point("expand")
        # what each node of the pattern weighs: its label mask (None: every
        # node passes), and for the node the plan starts from the input's
        # rows — the label mask again where the input is a plain node scan,
        # else their number per node
        weight = {
            op.far_fld: gi.label_mask(op.far_labels, ctx) for _, _, op in steps
        }
        if base.frontier_scan is not None:
            weight[base.frontier_fld] = gi.label_mask(base.frontier_scan, ctx)
        elif base.frontier_fld == names[i + 1]:
            return None  # the middle of the wedges has to weigh 0 or 1
        else:
            in_op = base.children[0]
            in_t = _flat_in(in_op.table)
            h = in_op.header
            id_col = in_t._cols[h.column(h.id_expr(h.var(base.frontier_fld)))]
            pos, present = gi.compact_of(id_col, ctx)
            weight[base.frontier_fld] = J.frontier_multiplicity(pos, present, n=n)
        first, second = steps[i][:2], steps[i + 1][:2]
        whole, signs = _CONSTRAINED_TERMS[
            equality, edge and ("open" if edge[2] else "closed")
        ]
        if "same_closed" in signs and _graph_loop_free(self._graph_obj, edge[0], ctx):
            # no node closes on itself: that term is 0 (a host fact)
            signs = {name: sign for name, sign in signs.items() if name != "same_closed"}
        if "closed" in signs:
            types_key, from_first, _ = edge
            closing = (types_key, not from_first)
            # a's first-hop neighbours and the nodes with a second-hop lane
            # into c, as bit rows over one numbering of the middle nodes
            from_a = gi.wedge_adjacency(first, second[0], ctx)
            into_c = gi.wedge_adjacency((second[0], not second[1]), second[0], ctx)
            if from_a is None or into_c is None:
                return None
        # left: the pattern's first node carried to node i, each step over
        # the CSR whose rows are the node reached; right: its last node
        # carried back to node i + 2
        def step(types, reverse, node):
            return gi.csr(types, reverse, ctx)[:2] + (
                weight[node], gi.csr_row_span(types, reverse, ctx).window
            )

        left = J.chain_node_weights(
            weight[names[0]],
            tuple(
                step(types, not reverse, names[j + 1])
                for j, (types, reverse, _) in enumerate(steps[:i])
            ),
            num_nodes=n,
        )
        right = J.chain_node_weights(
            weight[names[-1]],
            tuple(
                step(types, reverse, names[j])
                for j, (types, reverse, _) in reversed(
                    list(enumerate(steps))[i + 2:]
                )
            ),
            num_nodes=n,
        )
        mid_mask = weight[names[i + 1]]
        rp1, ci1, _ = gi.csr(*first, ctx)
        rows1 = gi.csr_rows(*first, ctx)
        terms = {}
        if "same" in signs or "same_closed" in signs:
            back = gi.back_counts(first, second, ctx)
            both = left * right
            if "same_closed" in signs:  # a = c under a closing edge: a's loop
                looped = gi.loop_count(edge[0], ctx) > 0
                terms["same_closed"] = J.two_cycle_sum(
                    rp1, ci1, rows1, back, mid_mask, jnp.where(looped, both, 0)
                )
            if "same" in signs:
                terms["same"] = J.two_cycle_sum(
                    rp1, ci1, rows1, back, mid_mask, both
                )
        if "closed" in signs:
            rp_c, ci_c, _ = gi.csr(*closing, ctx)
            chunk = min(gi.WEDGE_CHUNK, int(ci_c.shape[0]))
            ra_c, kc_c = gi.closing_pair_rows(closing, first[0], second[0], ctx)
            terms["closed"] = J.wedge_close_sum(
                from_a, gi.wedge_rank(first[0], ctx)[1],
                into_c, gi.wedge_rank(second[0], ctx)[1], mid_mask,
                rp_c, ra_c, kc_c, left, right, chunk=chunk,
            )
            # the closing lanes of the chunks walked, the last one's pad too
            lanes = -(-gi.csr_lane_count(*closing, ctx) // chunk) * chunk if chunk else 0
            _WEDGE_LANES_TOTAL.inc(lanes)
            _obs_trace.note("form", "bits")
            _obs_trace.note("planes", f"{len(from_a)}x{len(into_c)}")
            _obs_trace.note("wedge_lanes", lanes)
        n_dev = sum(sign * terms[name] for name, sign in signs.items())
        # the whole chain's count is the chain's own program, as ever
        count = self._count_via_chain(gi, ctx) if whole else 0
        with _obs_trace.sync("expand"):
            return count + int(n_dev)

    def distinct_endpoints_count(self, fields) -> Optional[int]:
        """count(DISTINCT endpoints) over a fused expand chain WITHOUT
        materializing any row set: per hop one size sync + one (base-key,
        position) materialize program; the final hop fuses into a packed
        values-only sort count (``jit_ops.distinct_pairs_count_final``).
        Returns None when the pattern doesn't fit (fields beyond the chain
        endpoints, undirected hops, paths) — callers fall back to the
        materialized distinct. The relational pushdown hook is
        ``AggregateOp._compute_table``."""
        try:
            hops = self._chain_hops()
            base = hops[-1]
            want = set(fields)
            if not want or not want <= {base.frontier_fld, self.far_fld}:
                return None
            if base.frontier_fld == self.far_fld:
                return None  # ambiguous binding; keep the generic path
            if any(h.undirected for h in hops):
                return None
            # named paths make the var's identity more than its id column
            if any(self.header.has_path(f) for f in want):
                return None
            use_a = base.frontier_fld in want
            use_c = self.far_fld in want
            gi = GraphIndex.of(self.graph)
            ctx = self.context
            in_op = base.children[0]
            in_t = _flat_in(in_op.table)
            frontier_var = in_op.header.var(base.frontier_fld)
            id_col = in_t._cols[
                in_op.header.column(in_op.header.id_expr(frontier_var))
            ]
            gi.node_ids(ctx)
            if use_a and use_c and gi.num_nodes >= (1 << 30):
                return None  # pos*V+pos pair key must stay below the sentinel
            # eligible from here on: the distinct-count tiers below all
            # sync, so the expand fault site covers them
            fault_point("expand")
            pairs = _collected_pairs(hops)
            carry, mask_pairs = frozenset(), {}
            if pairs:
                spec = _chain_enforcement_spec(hops, pairs)
                if spec is None:
                    return None  # materialized path enforces via row masks
                carry, mask_pairs, _ = spec
            elif (
                len(hops) == 2 and current_mesh() is None
                and use_a and use_c and _mxu_dense_mode()
            ):
                # MXU tier: nonzero count of the blocked bf16 boolean
                # product — one matmul chain instead of 20M-row state
                got = self._mxu_distinct_pairs(gi, ctx, hops, id_col)
                if got is not None:
                    return got

            def final(rp, ci, eo, pos, deg, akey, mask, prevs, order, midx,
                      total, nvalid=None):
                # final hop: fused materialize + distinct count
                if midx:
                    return int(
                        J.distinct_pairs_count_final_unique(
                            rp, ci, eo, pos, deg, akey, mask, prevs,
                            total=total, use_a=use_a, use_c=use_c,
                            num_nodes=gi.num_nodes, mask_idx=midx,
                            nvalid=nvalid,
                        )
                    )
                return int(
                    J.distinct_pairs_count_final(
                        rp, ci, pos, deg, akey, mask,
                        total=total, use_a=use_a, use_c=use_c,
                        num_nodes=gi.num_nodes, nvalid=nvalid,
                    )
                )

            return _fused_chain_walk(
                gi, ctx, hops, id_col, final, carry, mask_pairs
            )
        except (GraphIndexError, TpuBackendError):
            return None

    def _mxu_distinct_pairs(self, gi, ctx, hops, id_col):
        """count(DISTINCT a, c) as the nonzero count of the blocked bf16
        boolean matmul chain (``jit_ops.mxu_distinct_pairs``); None when
        the dense tier doesn't apply."""
        base, final_hop = hops[1], hops[0]
        got1 = gi.dense_adj(base.types_key, base.backwards, ctx)
        got2 = gi.dense_adj(final_hop.types_key, final_hop.backwards, ctx)
        if got1 is None or got2 is None:
            return None
        a1, _, rowsum1 = got1
        a2, entry2, _ = got2
        if rowsum1 * entry2 > (1 << 24):
            return None  # >0.5 test needs the f32 cell to stay nonzero-exact
        pos, present = gi.compact_of(id_col, ctx)
        npad = int(a1.shape[0])
        pres = J.frontier_multiplicity(pos, present, n=npad) > 0
        m_b = _pad_mask(gi.label_mask(base.far_labels, ctx), npad)
        m_c = _pad_mask(gi.label_mask(final_hop.far_labels, ctx), npad)
        fault_point("expand")  # the dense-tier count sync below
        MXU_TIER_COUNTS.inc("dense")
        return int(
            J.mxu_distinct_pairs(
                a1, a2, pres, m_b, m_c, block=GraphIndex.DENSE_BLOCK
            )
        )

    def _factorized_expand(self, gi: GraphIndex, ctx, in_op, in_t, pos, present):
        """The expand output as a ``FactorizedTable`` — input rows are the
        lanes, each lane's suffix run is its CSR adjacency slice, and rel/
        far-node columns decode through ``(eo,)`` / ``(ci, row_map)``
        gather-map chains only at collect time. Eligible for directed,
        label-free, uniqueness-free expands whose routed flat estimate the
        factorized router rejects (``optimizer.cost.prefer_factorized``);
        returns None to keep the classic flat materialize."""
        from ...optimizer.cost import factorized_routing_enabled, prefer_factorized
        from .factorized import FactorizedTable, RunLevel, note_factorized
        from .table import TpuTable

        if (
            self.undirected
            or self.far_labels
            or self.enforced_pairs
            or gi.num_nodes == 0
            or not self.header.expressions
            # the pre-gate keeps the default configuration free: no
            # run-bounds program or row-total sync unless routing is live
            or not factorized_routing_enabled()
        ):
            return None
        rp, ci, eo = gi.csr(self.types_key, self.backwards, ctx)
        if int(ci.shape[0]) == 0:
            return None
        fault_point("expand")  # the run-total scalar sync below
        lo, cnt, t_dev = _csr_run_bounds(rp, pos, present, np.int64(in_t.size))
        with _obs_trace.sync("expand"):
            total = int(t_dev)
        nexprs = max(len(self.header.expressions), 1)
        if not prefer_factorized(total, 24 + 9 * nexprs):
            return None
        rel_cols, rel_header = gi.rel_scan(self.types_key, ctx)
        node_cols, node_header, row_map = gi.node_scan((), ctx)
        canon_rel = E.Var(CANON_REL)
        canon_node = E.Var(CANON_NODE)
        phys = int(pos.shape[0])
        pfx_cols: Dict[str, Column] = {}
        lvl_cols: Dict[str, Tuple[Column, Tuple[Any, ...]]] = {}
        for e in self.header.expressions:
            col = self.header.column(e)
            if col in pfx_cols or col in lvl_cols:
                continue
            if e in in_op.header:
                src = in_t._cols[in_op.header.column(e)]
                if src.kind != OBJ and len(src) != phys:
                    return None  # misaligned pass-through: flat path
                pfx_cols[col] = src
                continue
            owner = _owner_name(e)
            if owner == self.rel_fld:
                key = rekey_element_expr(e, canon_rel)
                if key is None or key not in rel_header:
                    raise GraphIndexError(f"unmapped rel expr {e!r}")
                src = rel_cols[rel_header.column(key)]
                if src.kind == OBJ or len(src) == 0:
                    return None  # host-gather columns cannot ride the decode
                lvl_cols[col] = (src, (eo,))
                continue
            if owner == self.far_fld:
                key = rekey_element_expr(e, canon_node)
                if key is None or key not in node_header:
                    raise GraphIndexError(f"unmapped node expr {e!r}")
                src = node_cols[node_header.column(key)]
                if src.kind == OBJ or len(src) == 0:
                    return None
                lvl_cols[col] = (src, (ci, row_map))
                continue
            raise GraphIndexError(f"unmapped expr {e!r}")
        # the compressed form pays admission for its two run-bound arrays
        # at the LANE extent — never the flat product
        bucketing.admit(in_t.size, 16, "factorized")
        prefix = TpuTable(pfx_cols, in_t.size)
        out = FactorizedTable(
            prefix, (RunLevel(lo, cnt, lvl_cols),), nrows=total
        )
        note_factorized(total, phys, in_t.size)
        return out

    def _fused_table(self):
        fault_point("expand")
        gi = GraphIndex.of(self.graph)
        ctx = self.context
        if not self.header.expressions:
            # pure-multiplicity consumer (a pruned count(*) plan): no rows
            # are materialized at all — the whole stacked-expand chain runs
            # as one fused device program
            from .table import TpuTable

            return TpuTable({}, self._count_via_chain(gi, ctx))
        in_op = self.children[0]
        in_t = _flat_in(in_op.table)
        frontier_var = in_op.header.var(self.frontier_fld)
        id_col = in_t._cols[in_op.header.column(in_op.header.id_expr(frontier_var))]
        pos, present = gi.compact_of(id_col, ctx)
        fact = self._factorized_expand(gi, ctx, in_op, in_t, pos, present)
        if fact is not None:
            return fact
        primary_reverse = self.backwards
        bucketed = bucketing.enabled()
        row, nbr, orig, n_live = self._expand_half(
            gi, pos, present, reverse=primary_reverse, drop_loops=False
        )
        swapped = None
        if self.undirected:
            row2, nbr2, orig2, n2 = self._expand_half(
                gi, pos, present, reverse=not primary_reverse, drop_loops=True
            )
            if bucketed:
                live = J.concat_pair(
                    J.row_tail_mask(row, n_live), J.row_tail_mask(row2, n2)
                )
            row, nbr, orig, swapped = J.concat_expand_halves(
                row, nbr, orig, row2, nbr2, orig2
            )
            n_live += n2
            if bucketed and int(row.shape[0]) != n_live:
                # the halves' tail pads land mid-array after the concat:
                # compact back to the tail-pad form (pad lanes duplicate
                # lane 0, dead past ``n_live``)
                idx = J.mask_nonzero(live, size=bucketing.round_size(n_live))
                row, nbr, orig, swapped = J.tree_take(
                    (row, nbr, orig, swapped), idx
                )
        # far-end label filter + node-table row lookup in one gather
        _, _, row_map = gi.node_scan(self.far_labels, ctx)
        if gi.num_nodes and not self.far_labels:
            # unrestricted far end: every neighbour is in the scan, so the
            # keep mask is all-true by construction — skip the count sync
            far_rows, _ = J.far_lookup(row_map, nbr)
            n_out = n_live if bucketed else int(row.shape[0])
        elif gi.num_nodes and bucketed:
            far_rows, keep = J.far_lookup(row_map, nbr)
            if int(row.shape[0]) != n_live:
                # pad lanes duplicate a real neighbour and would pass the
                # label probe — they are not rows
                keep = keep & J.row_tail_mask(keep, n_live)
            idx, n_out = _mask_to_idx_bucketed(keep)
            if n_out != n_live or int(idx.shape[0]) != int(row.shape[0]):
                if swapped is not None:
                    row, orig, far_rows, swapped = J.tree_take(
                        (row, orig, far_rows, swapped), idx
                    )
                else:
                    row, orig, far_rows = J.tree_take((row, orig, far_rows), idx)
        elif gi.num_nodes:
            far_rows, keep = J.far_lookup(row_map, nbr)
            n_dev = J.mask_sum(keep)
            with _obs_trace.sync("expand"):
                n_out = int(n_dev)
            if n_out != int(row.shape[0]):  # skip nonzero+gather when all match
                idx = J.mask_nonzero(keep, size=n_out)
                if swapped is not None:
                    row, orig, far_rows, swapped = J.tree_take(
                        (row, orig, far_rows, swapped), idx
                    )
                else:
                    row, orig, far_rows = J.tree_take((row, orig, far_rows), idx)
        else:
            far_rows = jnp.zeros(0, jnp.int64)
            n_out = 0
            row, orig = jnp.zeros(0, jnp.int64), jnp.zeros(0, jnp.int64)
            if swapped is not None:
                swapped = jnp.zeros(0, bool)
        extras = (far_rows,) if swapped is None else (far_rows, swapped)
        row, orig, extras, n_out = self._apply_enforced_pairs(
            gi, ctx, row, orig, extras, n_out
        )
        far_rows = extras[0]
        if swapped is not None:
            swapped = extras[1]
        return self._assemble(
            gi, row, orig, swapped, far_rows, self.far_labels,
            self.rel_fld, self.far_fld, n_out,
        )


class CsrExpandIntoOp(_FusedExpandBase):
    """Fused ExpandInto: both endpoints bound; the closing relationships are
    found by binary search over the sorted (src*N + dst) edge keys — the
    engine-integrated version of the ``triangle_count`` kernel probe."""

    def __init__(
        self,
        in_plan: RelationalOperator,
        classic: RelationalOperator,
        graph_obj,
        *,
        source_fld: str,
        rel_fld: str,
        target_fld: str,
        types_key: Tuple[str, ...],
        undirected: bool,
        enforced_pairs: Tuple[Tuple[str, str], ...] = (),
    ):
        super().__init__(in_plan, classic, graph_obj)
        self.source_fld = source_fld
        self.rel_fld = rel_fld
        self.target_fld = target_fld
        self.types_key = types_key
        self.undirected = undirected
        self.enforced_pairs = enforced_pairs

    def _ctor_kwargs(self) -> Dict[str, Any]:
        return dict(
            source_fld=self.source_fld,
            rel_fld=self.rel_fld,
            target_fld=self.target_fld,
            types_key=self.types_key,
            undirected=self.undirected,
        )

    def _show_inner(self) -> str:
        arrow = "-" if self.undirected else "->"
        t = "|".join(self.types_key) or "*"
        uniq = (
            " uniq" + ",".join(f"({a}<>{b})" for a, b in self.enforced_pairs)
            if self.enforced_pairs
            else ""
        )
        return (
            f"({self.source_fld})-[{self.rel_fld}:{t}]{arrow}"
            f"({self.target_fld}) into{uniq}"
        )

    def closing_edge(self) -> Optional[Tuple[str, str, Tuple[str, ...]]]:
        """``(source, target, types)`` where this is one plain directed
        relationship between two bound nodes — what a count chain can take
        as a constraint (``CsrExpandOp.chain_constraint_count``) — else
        None."""
        if self.undirected or self.enforced_pairs:
            return None
        return self.source_fld, self.target_fld, self.types_key

    def _probe(self, gi: GraphIndex, keys, s_pos, t_pos, ok, drop_loops: bool):
        """Closing-edge probe + materialize. Returns ``(row, orig, count)``;
        under bucketing the arrays are tail-padded past the true count."""
        ctx = self.context
        _, _, eo = gi.csr(self.types_key, False, ctx)
        lo, counts, total_dev = J.into_probe(
            keys, s_pos, t_pos, ok, gi.num_nodes, drop_loops=drop_loops
        )
        total = int(total_dev)
        if bucketing.enabled():
            row, orig, _ = J.into_materialize_counted(
                eo, lo, counts, total_dev, size=bucketing.round_size(total)
            )
            return row, orig, total
        row, orig = J.into_materialize(eo, lo, counts, total=total)
        return row, orig, total

    def _chain_close_count(self) -> Optional[int]:
        """count(*) over ExpandInto(fused expand chain) WITHOUT materializing
        the chain's row set: walk the chain with (base key, position) state
        (as ``distinct_endpoints_count`` does), then fuse the closing-edge
        probe into the final hop (``jit_ops.into_close_count``). The classic
        plan materializes the full k-hop table first — at SF10 the 2-hop
        set alone is ~10^8 rows; this path keeps O(nodes + edges) memory.
        None = shape doesn't fit (non-chain input, undirected chain hops,
        endpoint vars not the chain's ends) — caller materializes."""
        from ...relational.ops import CacheOp

        in_op = self.children[0]
        while isinstance(in_op, CacheOp):
            in_op = in_op.children[0]
        if (
            not isinstance(in_op, CsrExpandOp)
            or in_op._graph_obj is not self._graph_obj
        ):
            return None
        try:
            hops = in_op._chain_hops()
            base = hops[-1]
            ends = {base.frontier_fld, in_op.far_fld}
            if (
                {self.source_fld, self.target_fld} != ends
                or self.source_fld == self.target_fld
                or base.frontier_fld == in_op.far_fld
            ):
                return None
            if any(h.undirected for h in hops):
                return None
            gi = GraphIndex.of(self.graph)
            ctx = self.context
            base_in = base.children[0]
            in_t = _flat_in(base_in.table)
            frontier_var = base_in.header.var(base.frontier_fld)
            id_col = in_t._cols[
                base_in.header.column(base_in.header.id_expr(frontier_var))
            ]
            gi.node_ids(ctx)
            if gi.num_nodes >= (1 << 30):
                return None  # src*N + dst probe key must fit int64
            # eligible from here on: the close-count tiers below all sync
            fault_point("expand")
            keys = gi.edge_keys(self.types_key, ctx)
            src_is_base = self.source_fld == base.frontier_fld
            pairs = _collected_pairs(hops, (self,))
            if pairs:
                if self.undirected:
                    return None  # dual-orientation probe: materialize
                spec = _chain_enforcement_spec(
                    hops, pairs,
                    close_rel=self.rel_fld, close_types=self.types_key,
                )
                if spec is None:
                    return None  # materialized path enforces via row masks
                carry, mask_pairs, close_partners = spec
                kbo = gi.edge_keys_by_orig(self.types_key, ctx)
                exec_last = hops[0].rel_fld
                sub_cur = exec_last in close_partners
                sub_rels = tuple(
                    sorted(r for r in close_partners if r != exec_last)
                )

                def final_u(
                    rp, ci, eo, pos, deg, akey, mask, prevs, order, midx,
                    total, nvalid=None,
                ):
                    sub_idx = tuple(order.index(r) for r in sub_rels)
                    return int(
                        J.into_close_count_unique(
                            rp, ci, eo, pos, deg, akey, mask, keys, kbo, prevs,
                            total=total, src_is_base=src_is_base,
                            num_nodes=gi.num_nodes,
                            mask_idx=midx, sub_idx=sub_idx, sub_cur=sub_cur,
                            nvalid=nvalid,
                        )
                    )

                return _fused_chain_walk(
                    gi, ctx, hops, id_col, final_u, carry, mask_pairs
                )

            if (
                len(hops) == 2
                and not self.undirected
                and current_mesh() is None
                and _mxu_dense_mode()
            ):
                got = self._mxu_close_count(gi, ctx, hops, id_col, src_is_base)
                if got is not None:
                    return got

            def final(rp, ci, eo, pos, deg, akey, mask, prevs, order, midx,
                      total, nvalid=None):
                return int(
                    J.into_close_count(
                        rp, ci, pos, deg, akey, mask, keys,
                        total=total, src_is_base=src_is_base,
                        num_nodes=gi.num_nodes,
                        undirected=self.undirected, nvalid=nvalid,
                    )
                )

            return _fused_chain_walk(gi, ctx, hops, id_col, final)
        except (GraphIndexError, TpuBackendError):
            return None

    def _mxu_close_count(self, gi, ctx, hops, id_col, src_is_base):
        """Triangle/cycle close count as blocked bf16 matmuls on the MXU:
        tri = sum_a mult[a] * sum_c (A1 @ A2)[a, c] * C[a, c]. The closing
        adjacency C is oriented FROM the walk's base endpoint (probe (a, c)
        uses the forward matrix, probe (c, a) the reverse). None when the
        dense form doesn't apply (graph too large, multiplicity > bf16's
        exact range)."""
        base, final_hop = hops[1], hops[0]
        got1 = gi.dense_adj(base.types_key, base.backwards, ctx)
        got2 = gi.dense_adj(final_hop.types_key, final_hop.backwards, ctx)
        gotc = gi.dense_adj(self.types_key, not src_is_base, ctx)
        if got1 is None or got2 is None or gotc is None:
            return None
        a1, _, rowsum1 = got1
        a2, entry2, _ = got2
        cm, entry_c, _ = gotc
        if rowsum1 * entry2 * max(entry_c, 1) > (1 << 24):
            # a 2-path cell (or its product with the closing multiplicity,
            # computed in f32 BEFORE the f64 reduction) could pass f32's
            # exact-integer range — keep the walk path
            return None
        pos, present = gi.compact_of(id_col, ctx)
        npad = int(a1.shape[0])
        mult = J.frontier_multiplicity(pos, present, n=npad)
        m_b = _pad_mask(gi.label_mask(base.far_labels, ctx), npad)
        m_c = _pad_mask(gi.label_mask(final_hop.far_labels, ctx), npad)
        fault_point("expand")  # the dense-tier count sync below
        MXU_TIER_COUNTS.inc("dense")
        return int(
            J.mxu_close_count(
                a1, a2, cm, mult, m_b, m_c, block=GraphIndex.DENSE_BLOCK
            )
        )

    def _fused_table(self):
        if not self.header.expressions:
            # pure-multiplicity consumer (pruned count(*) plan): try the
            # whole-chain fused close count first
            n = self._chain_close_count()
            if n is not None:
                from .table import TpuTable

                return TpuTable({}, n)
        in_op = self.children[0]
        in_t = _flat_in(in_op.table)
        gi = GraphIndex.of(self.graph)
        ctx = self.context
        h = in_op.header
        s_col = in_t._cols[h.column(h.id_expr(h.var(self.source_fld)))]
        t_col = in_t._cols[h.column(h.id_expr(h.var(self.target_fld)))]
        s_pos, s_ok = gi.compact_of(s_col, ctx)
        t_pos, t_ok = gi.compact_of(t_col, ctx)
        ok = s_ok & t_ok
        keys = gi.edge_keys(self.types_key, ctx)
        bucketed = bucketing.enabled()
        row, orig, n_live = self._probe(gi, keys, s_pos, t_pos, ok, drop_loops=False)
        swapped = None
        if self.undirected:
            row2, orig2, n2 = self._probe(
                gi, keys, t_pos, s_pos, ok, drop_loops=True
            )
            if bucketed:
                live = J.concat_pair(
                    J.row_tail_mask(row, n_live), J.row_tail_mask(row2, n2)
                )
            row, orig, swapped = J.concat_into_halves(row, orig, row2, orig2)
            n_live += n2
            if bucketed and int(row.shape[0]) != n_live:
                # restore the tail-pad form (see CsrExpandOp._fused_table)
                idx = J.mask_nonzero(live, size=bucketing.round_size(n_live))
                row, orig, swapped = J.tree_take((row, orig, swapped), idx)
        n_out = n_live if bucketed else int(row.shape[0])
        extras = () if swapped is None else (swapped,)
        row, orig, extras, n_out = self._apply_enforced_pairs(
            gi, ctx, row, orig, extras, n_out
        )
        if swapped is not None:
            swapped = extras[0]
        return self._assemble(
            gi, row, orig, swapped, None, (), self.rel_fld, None, n_out
        )


class CsrOptionalExpandOp(_TreeCounted, _FusedExpandBase):
    """Fused OPTIONAL MATCH (frontier)-[rel]->(far): the reference plans
    Optional as a left outer join of the optional subtree
    (``RelationalPlanner.scala:298``); here matched frontier rows emit
    their expansions and unmatched rows emit ONE null-padded row, in a
    single sized CSR program. Unlabeled directed single-hop patterns only
    (labels/undirected/WHERE keep the classic outer-join shadow)."""

    def __init__(
        self,
        in_plan: RelationalOperator,
        classic: RelationalOperator,
        graph_obj,
        *,
        frontier_fld: str,
        rel_fld: str,
        far_fld: str,
        types_key: Tuple[str, ...],
        backwards: bool,
        far_labels: Tuple[str, ...] = (),
    ):
        super().__init__(in_plan, classic, graph_obj)
        self.frontier_fld = frontier_fld
        self.rel_fld = rel_fld
        self.far_fld = far_fld
        self.types_key = types_key
        self.backwards = backwards
        self.far_labels = far_labels

    def _show_inner(self) -> str:
        arrow = "<-" if self.backwards else "->"
        t = "|".join(self.types_key) or "*"
        far = ":".join((self.far_fld,) + self.far_labels)
        return f"optional ({self.frontier_fld}){arrow}[{self.rel_fld}:{t}]({far})"

    def _fused_table(self):
        from .table import TpuTable

        gi = GraphIndex.of(self.graph)
        ctx = self.context
        if gi.hop_mask(self.types_key, self.backwards, self.far_labels, ctx) is not None:
            # some neighbour lacks the far labels: which rows match is the
            # outer join's to say (the count needs no row: ``tree_count``)
            raise GraphIndexError("optional far labels not proven by the index")
        in_op = self.children[0]
        in_t = _flat_in(in_op.table)
        frontier_var = in_op.header.var(self.frontier_fld)
        id_col = in_t._cols[in_op.header.column(in_op.header.id_expr(frontier_var))]
        gi.node_ids(ctx)
        if gi.num_nodes == 0:
            raise GraphIndexError("empty graph: classic outer join handles")
        pos, present = gi.compact_of(id_col, ctx)
        rp, ci, eo = gi.csr(self.types_key, self.backwards, ctx)
        # bucket/shard pad rows are not input rows: they must emit NOTHING
        # (an unmatched REAL row emits one null row; a pad row none)
        nrows = in_t.size if in_t._phys != in_t.size else None
        deg, counts, t_dev = J.optional_expand_degrees(
            rp, pos, present, nrows=nrows
        )
        with _obs_trace.sync("expand"):
            total = int(t_dev)
        row, nbr, orig, matched = J.optional_expand_materialize(
            rp, ci, eo, pos, deg, counts, total=total
        )
        _, _, row_map = gi.node_scan((), ctx)
        far_rows, _ = J.far_lookup(row_map, nbr)
        # assembly: input pass-throughs by row; rel/far columns null-masked
        # where unmatched
        rel_cols, rel_header = gi.rel_scan(self.types_key, ctx)
        node_cols, node_header, _ = gi.node_scan((), ctx)
        canon_rel = E.Var(CANON_REL)
        canon_node = E.Var(CANON_NODE)
        plan: Dict[str, Tuple[Column, str]] = {}
        for e in self.header.expressions:
            col = self.header.column(e)
            if col in plan:
                continue
            if e in in_op.header:
                plan[col] = (in_t._cols[in_op.header.column(e)], "row")
                continue
            owner = _owner_name(e)
            if owner == self.rel_fld:
                key = rekey_element_expr(e, canon_rel)
                if key is None or key not in rel_header:
                    raise GraphIndexError(f"unmapped rel expr {e!r}")
                plan[col] = (rel_cols[rel_header.column(key)], "orig")
                continue
            if owner == self.far_fld:
                key = rekey_element_expr(e, canon_node)
                if key is None or key not in node_header:
                    raise GraphIndexError(f"unmapped node expr {e!r}")
                plan[col] = (node_cols[node_header.column(key)], "far")
                continue
            raise GraphIndexError(f"unmapped optional-expand expr {e!r}")
        out = self._gather_plan(
            plan,
            {"row": row, "orig": orig, "far": far_rows},
            null_mask_by_tag={"orig": matched, "far": matched},
        )
        return TpuTable(out, total)


def _tree_ops(top) -> List[RelationalOperator]:
    """``top`` and the ``CsrExpandOp``s / ``CsrOptionalExpandOp``s stacked
    under it over the same graph, deepest last. Caches and selects between
    them keep the row multiset and are looked through."""
    from ...relational.ops import CacheOp, SelectOp

    ops, node = [top], top
    while True:
        child = node.children[0]
        while isinstance(child, (CacheOp, SelectOp)):
            child = child.children[0]
        if (
            isinstance(child, (CsrExpandOp, CsrOptionalExpandOp))
            and child._graph_obj is top._graph_obj
        ):
            ops.append(child)
            node = child
            continue
        return ops


def _read_tree(ops):
    """The stacked expands read as a tree of the PATTERN rooted in the node
    the plan started from (``ops[-1].frontier_fld``): ``(tree, order)`` —
    ``tree`` as ``jit_ops.tree_count`` takes it, ``order`` the ops by their
    place in its ``hops`` — or the reason it is none: a node bound twice or
    bound outside the stack, a hop that grows out of an OPTIONAL node (its
    null rows match nothing: not a product), relationship uniqueness the
    operators enforce themselves (two hops of one type that can meet)."""
    if _collected_pairs(ops):
        return "uniqueness"
    base = ops[-1]
    below: Dict[str, list] = {base.frontier_fld: []}
    optional_nodes = set()
    order = list(reversed(ops))  # as executed: a hop's place is its index
    for k, op in enumerate(order):
        if op.frontier_fld not in below or op.far_fld in below:
            return "node_bound_twice"
        if op.frontier_fld in optional_nodes:
            return "under_optional"
        optional = isinstance(op, CsrOptionalExpandOp)
        below[op.far_fld] = []
        below[op.frontier_fld].append((k, optional, op.far_fld))
        if optional:
            optional_nodes.add(op.far_fld)

    def grown(name):
        return tuple((k, opt, grown(far)) for k, opt, far in below[name])

    return grown(base.frontier_fld), order


def _tree_count_program(gi: GraphIndex, base, tree, order) -> int:
    ctx = base.context
    gi.node_ids(ctx)
    if gi.num_nodes == 0:
        return 0
    fault_point("expand")
    # a leaf whose far labels the index proves is a difference of two row
    # pointers (``_hop_arrays``)
    hop_data = [_hop_arrays(gi, op, ctx) for op in order]
    frontier_scan = getattr(base, "frontier_scan", None)  # an expand's fact
    rows = None
    if frontier_scan is not None:
        # the input is the graph's own scan of these labels: their mask
        root_weight = gi.label_mask(frontier_scan, ctx)
        whole = root_weight is None
    else:
        in_op = base.children[0]
        in_t = _flat_in(in_op.table)
        h = in_op.header
        id_col = in_t._cols[h.column(h.id_expr(h.var(base.frontier_fld)))]
        pos, present = gi.compact_of(id_col, ctx)
        root_weight = J.frontier_multiplicity(pos, present, n=gi.num_nodes)
        whole = False
        # a row whose root an earlier OPTIONAL MATCH left null is in no
        # node's weight: the program counts it from the number of rows
        rows = np.int64(in_t.size)
    forms = J.tree_forms(tree, [h[5] is not None for h in hop_data], whole)
    _note_chain_forms(forms)
    _note_tree_lanes(forms, hop_data)
    _obs_trace.note(  # the leaves: nodes no hop grows out of
        "branches",
        len({op.far_fld for op in order} - {op.frontier_fld for op in order}),
    )
    n_dev = J.tree_count(
        root_weight, np.int32(gi.num_logical_nodes), tuple(hop_data),
        tree=tree, whole=whole, rows=rows,
    )
    with _obs_trace.sync("expand"):  # the one read that waits for the count
        return int(n_dev)


class CsrVarExpandOp(_FusedExpandBase):
    """Fused bounded var-length expand: the frontier-loop replacement for
    the unrolled join cascade (reference ``VarLengthExpandPlanner.scala:45-330``,
    SURVEY §5's "frontier SpMM loop"). Each hop is one sized CSR materialize
    program carrying (origin row, current node, walked edge ids); edge
    reuse kills a path via a mask (no compaction mid-chain); every length
    in [lower, upper] emits its surviving rows, which are compacted and
    concatenated once at the end.

    The fused path can assemble input pass-through columns and target-node
    columns. A required relationship-LIST column (or named path) falls back
    to the classic shadow cascade at runtime."""

    def __init__(
        self,
        in_plan: RelationalOperator,
        classic: RelationalOperator,
        graph_obj,
        *,
        source_fld: str,
        rel_fld: str,
        target_fld: str,
        types_key: Tuple[str, ...],
        lower: int,
        upper: int,
        far_labels: Tuple[str, ...],
        undirected: bool = False,
        enforced_pairs: Tuple[Tuple[str, str], ...] = (),
    ):
        super().__init__(in_plan, classic, graph_obj)
        self.source_fld = source_fld
        self.rel_fld = rel_fld
        self.target_fld = target_fld
        self.types_key = types_key
        self.lower = lower
        self.upper = upper
        self.far_labels = far_labels
        self.undirected = undirected
        # (rel_fld, fixed_rel) pairs: the walk must avoid the fixed rel's
        # edge — ``none(x IN rel_fld WHERE id(x) = id(fixed))`` enforced
        # in-kernel as an initial forbidden entry of the walked-edge masks
        self.enforced_pairs = enforced_pairs

    def _ctor_kwargs(self) -> Dict[str, Any]:
        return dict(
            source_fld=self.source_fld,
            rel_fld=self.rel_fld,
            target_fld=self.target_fld,
            types_key=self.types_key,
            lower=self.lower,
            upper=self.upper,
            far_labels=self.far_labels,
            undirected=self.undirected,
            enforced_pairs=self.enforced_pairs,
        )

    def _show_inner(self) -> str:
        t = "|".join(self.types_key) or "*"
        arrow = "-" if self.undirected else "->"
        uniq = (
            " uniq" + ",".join(f"({a}<>{b})" for a, b in self.enforced_pairs)
            if self.enforced_pairs
            else ""
        )
        return (
            f"({self.source_fld})-[{self.rel_fld}:{t}*{self.lower}.."
            f"{self.upper}]{arrow}({self.target_fld}){uniq}"
        )

    def _forbid_arrays(self, gi: GraphIndex, ctx):
        """Per-input-row forbidden canonical scan rows (one int64 array per
        enforced pair, -1 = unconstrained): fixed-rel global ids from the
        input table, bridged into this walk's scan-row space. Seeding the
        frontier loop's ``prev_edges`` with these arrays makes the existing
        walked-edge masks enforce the fixed-vs-var-length isomorphism with
        zero new kernel code."""
        if not self.enforced_pairs:
            return ()
        in_op = self.children[0]
        in_t = _flat_in(in_op.table)
        h = in_op.header
        sorted_ids, perm = gi.rel_row_index(self.types_key, ctx)
        out = []
        for ra, rb in self.enforced_pairs:
            other = rb if ra == self.rel_fld else ra
            if other == self.rel_fld:
                raise GraphIndexError("forbid pair does not name a fixed rel")
            try:
                col = in_t._cols[h.column(h.id_expr(h.var(other)))]
            except (KeyError, ValueError) as exc:
                raise GraphIndexError(
                    f"uniqueness rel {other!r} unmapped"
                ) from exc
            if col.kind == OBJ:
                raise GraphIndexError("host id column in forbid pair")
            out.append(J.rel_rows_of_ids(sorted_ids, perm, col.data, col.valid))
        return tuple(out)

    def _resolved_upper(self, ci) -> int:
        """Unbounded '*' resolves to the matching-edge count: relationship
        isomorphism bounds any duplicate-free walk by the number of edges,
        and both walk loops exit at the empty-frontier fixpoint long before
        that in practice."""
        if self.upper is not None:
            return self.upper
        return max(int(np.asarray(ci).shape[0]), self.lower, 1)

    def _fused_table(self):
        from .table import TpuTable

        fault_point("var_expand")
        in_op = self.children[0]
        header = self.header
        # the rel var materializes as a host LIST column — fused assembly
        # cannot produce it; let the classic cascade answer
        for e in header.expressions:
            if _owner_name(e) == self.rel_fld:
                raise GraphIndexError("var-length rel list required")
        count_only = not header.expressions
        gi = GraphIndex.of(self.graph)
        ctx = self.context
        in_t = _flat_in(in_op.table)
        frontier_var = in_op.header.var(self.source_fld)
        id_col = in_t._cols[in_op.header.column(in_op.header.id_expr(frontier_var))]
        gi.node_ids(ctx)
        if gi.num_nodes == 0:
            return TpuTable({}, 0) if count_only else self._assemble_levels(gi, [])
        pos, present = gi.compact_of(id_col, ctx)
        if self.undirected:
            # both-orientation CSR: one frontier loop replaces the classic
            # planner's per-step orientation-product cascade; the shared
            # edge_orig makes the walked-edge masks direction-agnostic
            rp, ci, eo = gi.csr_undirected(self.types_key, ctx)
        else:
            rp, ci, eo = gi.csr(self.types_key, False, ctx)
        _, _, row_map = gi.node_scan(self.far_labels, ctx)
        forbid = self._forbid_arrays(gi, ctx)
        row0 = None
        # forbidden edges seed the walked-edge masks: the loop's existing
        # ``orig != prev`` checks then enforce fixed-vs-var-length
        # relationship isomorphism with no extra kernel
        prev_edges: Tuple[Any, ...] = forbid
        total_count = 0
        levels: List[Tuple[Any, Any]] = []
        if self.lower == 0:
            # length 0: the target IS the source node (must carry the far
            # labels) — the identity frontier prepended to the loop's levels
            row00, far, keep, k_dev = J.varlen_zero(pos, present, row_map)
            if count_only:
                total_count += int(k_dev)
            else:
                k = int(k_dev)
                if k:
                    # tpulint: allow[pad-invariant] reason=exact emission gather — pad lanes would enter _assemble_levels' concat as live rows; the recompile driver (the hop program) is bucketed via round_size(total) below
                    idx = J.mask_nonzero(keep, size=k)
                    levels.append(J.tree_take((row00, far), idx))
        bucketed = bucketing.enabled()
        for level in range(1, self._resolved_upper(ci) + 1):
            fault_point("var_expand")
            deg, t_dev = J.expand_degrees_total(rp, pos, present)
            with _obs_trace.sync("var_expand"):
                total = int(t_dev)
            if total == 0:
                break
            # pre-flight: each hop row carries (row0, nbr, orig) plus one
            # walked-edge lane per uniqueness mask, padded on the lattice
            bucketing.admit(
                total, 8 * (3 + len(prev_edges) + 1), "var_expand"
            )
            # bucketed: every hop level whose emission count shares a
            # bucket reuses ONE compiled hop program (the frontier loop's
            # per-level sizes are the worst recompile driver otherwise)
            row0, nbr, orig, prev_edges, iso = J.varlen_hop(
                rp, ci, eo, pos, deg, row0, prev_edges,
                total=bucketing.round_size(total) if bucketed else total,
                nvalid=t_dev if bucketed else None,
            )
            if level >= self.lower:
                far, keep, k_dev = J.varlen_emit(nbr, iso, row_map)
                if count_only:
                    total_count += int(k_dev)
                else:
                    k = int(k_dev)
                    if k:
                        # tpulint: allow[pad-invariant] reason=exact emission gather — pad lanes would enter _assemble_levels' concat as live rows; the hop program above is the bucketed one
                        idx = J.mask_nonzero(keep, size=k)
                        levels.append(J.tree_take((row0, far), idx))
            pos, present = nbr, iso
        if count_only:
            return TpuTable({}, total_count)
        return self._assemble_levels(gi, levels)

    def _assemble_levels(self, gi: GraphIndex, levels):
        """Concat per-level (origin row, far row) frames and gather output
        columns: input pass-throughs by origin row, target-var columns from
        the far-label canonical node scan."""
        from .table import TpuTable

        ctx = self.context
        in_op = self.children[0]
        in_t = _flat_in(in_op.table)
        header = self.header
        if not levels:
            row0 = jnp.zeros(0, jnp.int64)
            far = jnp.zeros(0, jnp.int64)
        elif len(levels) == 1:
            row0, far = levels[0]
        else:
            row0, far = J.concat_rows(tuple(levels))
        n_out = int(row0.shape[0])
        node_cols, node_header, _ = gi.node_scan(self.far_labels, ctx)
        canon_node = E.Var(CANON_NODE)
        plan: Dict[str, Tuple[Column, str]] = {}
        for e in header.expressions:
            col = header.column(e)
            if col in plan:
                continue
            if e in in_op.header:
                plan[col] = (in_t._cols[in_op.header.column(e)], "row")
                continue
            if _owner_name(e) == self.target_fld:
                key = rekey_element_expr(e, canon_node)
                if key is None or key not in node_header:
                    raise GraphIndexError(f"unmapped var-expand target expr {e!r}")
                plan[col] = (node_cols[node_header.column(key)], "far")
                continue
            raise GraphIndexError(f"unmapped var-expand expr {e!r}")
        out = self._gather_plan(plan, {"row": row0, "far": far})
        return TpuTable(out, n_out)


# ---------------------------------------------------------------------------
# Planner hooks (installed via TpuTable.plan_expand_fastpath/_into)
# ---------------------------------------------------------------------------


def plan_expand_fastpath(planner, op, lhs, rhs, classic) -> Optional[RelationalOperator]:
    """Swap the classic Expand cascade for ``CsrExpandOp`` when statically
    safe; return None to keep the classic plan."""
    from ...logical import ops as L

    if op.direction not in (">", "-"):
        return None
    lhs_vars = {v.name for v in lhs.header.vars}
    if op.rel in lhs_vars:
        return None  # re-bound rel var: keep the generic join semantics
    backwards = op.source not in lhs_vars
    frontier = op.target if backwards else op.source
    far = op.source if backwards else op.target
    if frontier not in lhs_vars or far in lhs_vars:
        return None
    if {v.name for v in rhs.header.vars} != {far}:
        return None
    if not isinstance(op.rhs, L.NodeScan):
        return None  # far side must be a plain node scan (label filter only)
    m = op.rhs.node_type.material
    far_labels = tuple(sorted(getattr(m, "labels", ()) or ()))
    types = getattr(op.rel_type.material, "types", frozenset()) or frozenset()
    frontier_scan = None
    if (
        isinstance(op.lhs, L.NodeScan)
        and not op.lhs.in_op.fields
        and lhs.graph is rhs.graph
    ):
        # ``lhs`` is then ``graph.scan_operator`` of this type and nothing
        # else (``RelationalPlanner._plan_NodeScan``)
        fm = op.lhs.node_type.material
        frontier_scan = tuple(sorted(getattr(fm, "labels", ()) or ()))
    return CsrExpandOp(
        lhs,
        classic,
        rhs.graph,
        frontier_fld=frontier,
        rel_fld=op.rel,
        far_fld=far,
        types_key=GraphIndex.types_key(types),
        undirected=op.direction == "-",
        backwards=backwards,
        far_labels=far_labels,
        frontier_scan=frontier_scan,
    )


def plan_optional_expand_fastpath(planner, op, lhs, rhs_planned, classic) -> Optional[RelationalOperator]:
    """Swap Optional(single unlabeled directed Expand) for the fused
    left-outer expand; None keeps the classic outer join. The optional
    subtree must be exactly Expand(NodeScan, NodeScan) — any Filter (WHERE
    inside OPTIONAL), labels, or undirected step keeps the general plan."""
    from ...logical import ops as L

    e = op.rhs
    if not isinstance(e, L.Expand) or e.direction != ">":
        return None
    # one hop straight off the left side's rows, whatever planned them
    if e.lhs is not op.lhs or not isinstance(e.rhs, L.NodeScan):
        return None
    lhs_vars = {v.name for v in lhs.header.vars}
    bound = {e.source, e.rel, e.target} & lhs_vars
    if e.rel in bound:
        return None
    if bound == {e.source}:
        frontier, far, backwards = e.source, e.target, False
    elif bound == {e.target}:
        frontier, far, backwards = e.target, e.source, True
    else:
        return None
    # far-side labels change which rows match: the operator builds rows
    # only where the index proves them of every neighbour (else its shadow,
    # the outer join, does), and counts under them as a mask
    far_labels = tuple(sorted(getattr(e.rhs.node_type.material, "labels", ()) or ()))
    types = getattr(e.rel_type.material, "types", frozenset()) or frozenset()
    graph_obj = getattr(rhs_planned, "graph", None)
    if graph_obj is None:
        return None
    return CsrOptionalExpandOp(
        lhs,
        classic,
        graph_obj,
        frontier_fld=frontier,
        rel_fld=e.rel,
        far_fld=far,
        types_key=GraphIndex.types_key(types),
        backwards=backwards,
        far_labels=far_labels,
    )


def plan_var_expand_fastpath(planner, op, lhs, rhs, classic) -> Optional[RelationalOperator]:
    """Swap the unrolled var-length join cascade for ``CsrVarExpandOp`` when
    statically safe; None keeps the classic plan. Directed and undirected
    steps and zero-length lower bounds all fuse (undirected walks ride the
    both-orientation CSR — replacing the orientation-product cascade of
    reference ``VarLengthExpandPlanner.scala:264-310``); named-path capture
    and pre-bound endpoints keep the general machinery."""
    from ...logical import ops as L

    if op.direction not in (">", "-") or getattr(op, "capture_path_nodes", False):
        return None
    lhs_vars = {v.name for v in lhs.header.vars}
    if op.rel in lhs_vars or op.source not in lhs_vars or op.target in lhs_vars:
        return None
    if {v.name for v in rhs.header.vars} != {op.target}:
        return None
    if not isinstance(op.rhs, L.NodeScan):
        return None
    m = op.rhs.node_type.material
    far_labels = tuple(sorted(getattr(m, "labels", ()) or ()))
    types = getattr(op.rel_type.material, "types", frozenset()) or frozenset()
    return CsrVarExpandOp(
        lhs,
        classic,
        rhs.graph,
        source_fld=op.source,
        rel_fld=op.rel,
        target_fld=op.target,
        types_key=GraphIndex.types_key(types),
        lower=op.lower,
        upper=op.upper,
        far_labels=far_labels,
        undirected=op.direction == "-",
    )


def _rel_neq_pair(pred) -> Optional[Tuple[str, str]]:
    """Recognize a relationship-uniqueness predicate id(a) <> id(b) over two
    relationship variables (the shape ``ir.builder`` emits)."""
    from ...api import types as T

    if not isinstance(pred, E.Neq):
        return None
    l, r = pred.lhs, pred.rhs
    if not (isinstance(l, E.Id) and isinstance(r, E.Id)):
        return None
    lv, rv = l.expr, r.expr
    if not (isinstance(lv, E.Var) and isinstance(rv, E.Var)):
        return None
    for v in (lv, rv):
        t = getattr(v, "cypher_type", None)
        if t is None or not isinstance(t.material, T.CTRelationshipType):
            return None
    return lv.name, rv.name


def _rel_list_none_pair(pred) -> Optional[Tuple[str, str]]:
    """Recognize the fixed-vs-var-length isomorphism predicate
    ``none(x IN rs WHERE id(x) = id(r))`` (the shape ``ir.builder`` emits
    for a var-length rel list ``rs`` vs a fixed rel ``r``); returns
    (list_var, fixed_var) or None."""
    from ...api import types as T

    if not isinstance(pred, E.Quantified) or pred.kind != "none":
        return None
    lst = pred.list_expr
    if not isinstance(lst, E.Var):
        return None
    lt = getattr(lst, "cypher_type", None)
    if lt is None or not isinstance(lt.material, T.CTListType):
        return None
    if not isinstance(lt.material.inner.material, T.CTRelationshipType):
        return None
    eq = pred.predicate
    if not isinstance(eq, E.Equals):
        return None
    l, r = eq.lhs, eq.rhs
    if not (isinstance(l, E.Id) and isinstance(r, E.Id)):
        return None
    lv, rv = l.expr, r.expr
    if not (isinstance(lv, E.Var) and isinstance(rv, E.Var)):
        return None
    names = {lv.name, rv.name}
    if pred.var.name not in names:
        return None
    (other,) = names - {pred.var.name} if len(names) == 2 else (None,)
    if other is None:
        return None
    for v in (lv, rv):
        t = getattr(v, "cypher_type", None)
        if t is None or not isinstance(t.material, T.CTRelationshipType):
            return None
    return lst.name, other


def _graph_loop_free(graph_obj, types_key, ctx) -> bool:
    """True when no relationship of the type set is a self-loop (host-cached
    on the GraphIndex)."""
    gi = GraphIndex.of(graph_obj)
    cache = getattr(gi, "_loop_free", None)
    if cache is None:
        cache = gi._loop_free = {}
    got = cache.get(types_key)
    if got is None:
        try:
            s, d, _ = gi._edge_endpoints(types_key, ctx)
        except (GraphIndexError, TpuBackendError):
            cache[types_key] = False
            return False
        got = cache[types_key] = not bool((s == d).any())
    return got


def _chain_rel_ends(hops) -> Optional[Dict[str, Tuple[str, str, Tuple[str, ...]]]]:
    """Per-rel GRAPH-direction endpoints ``rel -> (src_fld, dst_fld,
    types_key)`` for a directed chain; None when any hop is undirected
    (orientation-ambiguous) or a rel field repeats (re-bound rel)."""
    out: Dict[str, Tuple[str, str, Tuple[str, ...]]] = {}
    for h in hops:
        if h.undirected or h.rel_fld in out:
            return None
        out[h.rel_fld] = (
            (h.far_fld, h.frontier_fld, h.types_key)
            if h.backwards
            else (h.frontier_fld, h.far_fld, h.types_key)
        )
    return out


def _rel_uniqueness_redundant(rel_ends, ra, rb, graph_obj, ctx) -> bool:
    """Sound redundancy proof for a rel-uniqueness filter ``id(ra) <>
    id(rb)`` over the subtree binding the relationships in ``rel_ends``.

    If the two relationships were the SAME edge, their graph sources
    coincide and their graph targets coincide. Propagating just those two
    node equalities (union-find over endpoint fields — shared pattern
    variables merge by name), any relationship whose endpoints land in one
    equivalence class is forced to be a SELF-LOOP of its own type set; if
    that type set is loop-free in this graph, the scenario is impossible,
    the filter can never remove a row, and dropping it is sound.

    Orientation-aware by construction: a forward/backward adjacent pair
    merges the two OUTER endpoints and forces no loop — the exact shape the
    round-3 proof dropped unsoundly (fork patterns returned 9 where
    openCypher requires 6). The reference gets these semantics from
    Neo4j's AddUniquenessPredicates + literal per-step filters
    (``VarLengthExpandPlanner.scala:107-165``)."""
    ea, eb = rel_ends.get(ra), rel_ends.get(rb)
    if ea is None or eb is None:
        return False
    parent: Dict[str, str] = {}

    def find(x: str) -> str:
        parent.setdefault(x, x)
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    parent[find(ea[0])] = find(eb[0])
    parent[find(ea[1])] = find(eb[1])
    for s, d, tk in rel_ends.values():
        if find(s) == find(d) and _graph_loop_free(graph_obj, tk, ctx):
            return True
    return False


def plan_filter_fastpath(planner, op, child) -> Optional[RelationalOperator]:
    """Resolve a relationship-uniqueness filter over a fused expand subtree
    so count(*)/DISTINCT chains keep their whole-plan fusion (the openCypher
    isomorphism predicates ``ir.builder`` adds would otherwise force the
    chain to materialize just to compare edge ids):

    1. PROOF: ``_rel_uniqueness_redundant`` — equality would force a
       self-loop of a loop-free type set: drop the filter outright (the
       SpMV count path stays available);
    2. ENFORCEMENT: same type set on both rels — drop the filter and clone
       the subtree's top operator with the pair recorded in
       ``enforced_pairs``; every execution path re-imposes it (fused walks
       via carried edge ids, materializing paths via id-column masks, the
       classic shadow via a real FilterOp wrapped around it);
    3. otherwise keep the generic FilterOp plan.

    The local oracle has no such hook and evaluates every predicate
    literally — differential tests pin both mechanisms."""
    from ...relational.ops import CacheOp

    pair = _rel_neq_pair(op.predicate)
    list_pair = _rel_list_none_pair(op.predicate) if pair is None else None
    if pair is None and list_pair is None:
        return None
    wraps = 0
    node = child
    while isinstance(node, CacheOp):
        node = node.children[0]
        wraps += 1

    def rewrap(n: RelationalOperator) -> RelationalOperator:
        for _ in range(wraps):
            n = CacheOp(n)
        return n

    if list_pair is not None:
        # fixed-vs-var-length isomorphism: push the fixed rel into the fused
        # walk as a forbidden edge (seeded walked-edge mask); the classic
        # shadow keeps the quantified predicate as a literal FilterOp
        rs, r = list_pair
        if not isinstance(node, CsrVarExpandOp) or node.rel_fld != rs:
            return None
        in_vars = {v.name for v in node.children[0].header.vars}
        if r not in in_vars or r == node.rel_fld:
            return None
        key = tuple(sorted((rs, r)))
        if key in node.enforced_pairs:
            return child  # duplicate predicate: already enforced below
        return rewrap(node._with_pair(key, op.predicate))

    from .wcoj import MultiwayIntersectOp

    if isinstance(node, MultiwayIntersectOp):
        # the multiway op enforces pairs by comparing GLOBAL element ids
        # (canonical rel scans / input id columns), so unlike the in-op
        # paths below it needs no same-type-set restriction
        rel_ends = node._rel_ends()
        if rel_ends is None:
            return None
        key = tuple(sorted(pair))
        if not set(key) <= set(rel_ends):
            return None
        if key in node.enforced_pairs:
            return child  # duplicate predicate: already enforced below
        if _rel_uniqueness_redundant(
            rel_ends, key[0], key[1], node._graph_obj, node.context
        ):
            return child
        return rewrap(node._with_pair(key, op.predicate))

    if isinstance(node, CsrExpandIntoOp) and not node.undirected:
        in_op = node.children[0]
        while isinstance(in_op, CacheOp):
            in_op = in_op.children[0]
        if not (
            isinstance(in_op, CsrExpandOp)
            and in_op._graph_obj is node._graph_obj
        ):
            return None
        rel_ends = _chain_rel_ends(in_op._chain_hops())
        if rel_ends is None or node.rel_fld in rel_ends:
            return None
        rel_ends[node.rel_fld] = (
            node.source_fld, node.target_fld, node.types_key
        )
    elif isinstance(node, CsrExpandOp):
        rel_ends = _chain_rel_ends(node._chain_hops())
        if rel_ends is None:
            return None
    else:
        return None
    key = tuple(sorted(pair))
    if not set(key) <= set(rel_ends):
        return None
    if key in node.enforced_pairs:
        return child  # duplicate predicate: already enforced below
    if _rel_uniqueness_redundant(
        rel_ends, key[0], key[1], node._graph_obj, node.context
    ):
        return child
    if rel_ends[key[0]][2] == rel_ends[key[1]][2]:
        # carried edge scan rows are only comparable within one canonical
        # rel scan, so in-op enforcement needs identical type sets
        return rewrap(node._with_pair(key, op.predicate))
    return None


def plan_expand_into_fastpath(planner, op, in_plan, classic) -> Optional[RelationalOperator]:
    if op.direction not in (">", "-"):
        return None
    in_vars = {v.name for v in in_plan.header.vars}
    if op.rel in in_vars or op.source not in in_vars or op.target not in in_vars:
        return None
    types = getattr(op.rel_type.material, "types", frozenset()) or frozenset()
    return CsrExpandIntoOp(
        in_plan,
        classic,
        in_plan.graph,
        source_fld=op.source,
        rel_fld=op.rel,
        target_fld=op.target,
        types_key=GraphIndex.types_key(types),
        undirected=op.direction == "-",
    )


# every jitted program of this module dispatches under an obs.trace
# ``dispatch`` leaf (last: the decorators above stay plain ``jax.jit``)
_obs_trace.wrap_programs(globals())
