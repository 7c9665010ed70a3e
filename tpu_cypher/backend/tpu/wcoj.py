"""Worst-case-optimal multiway join: leapfrog intersection on sorted CSR.

Cyclic Cypher patterns (triangles, diamonds, cliques) are where binary
join plans blow up: closing a cycle over a k-hop chain first materializes
the full k-hop row set — at SF10 the triangle's 2-hop intermediate alone
is ~10^8 rows, which is why the bench ladder skipped the large triangle
rung outright. The WCOJ literature (Ngo/Porat/Re/Rudra generic join,
leapfrog triejoin; TrieJax shows the dataflow mapping, EmptyHeaded the
planner rule) bounds cyclic joins by the fractional edge cover instead:
intersect the candidate's adjacency lists directly and never materialize
the acyclic intermediate.

``MultiwayIntersectOp`` is that operator for ONE cycle-closing binding:
the candidate variable ``c`` must lie in the intersection of K adjacency
lists, each anchored at a variable already bound per input row —

* the PIVOT list: the peeled top expand ``(b)-[r]->(c)`` — candidates
  are ``N(b)`` with pivot-edge multiplicity;
* one CLOSE list per cycle-closing relationship ``(a)-[q]->(c)`` (or
  ``(c)-[q]->(a)``): membership + multiplicity via range counts over the
  sorted ``anchor*N + candidate`` edge keys (``GraphIndex.edge_keys``,
  both orientations — the sorted-by-neighbor CSR contract
  ``GraphIndex.csr_sorted`` is what makes the range contiguous).

Execution is vertex-ordered and per-row ADAPTIVE (the leapfrog move):
every list can serve either role, so each input row iterates its
MINIMUM-degree list and binary-searches the others. Total expanded lanes
are bounded by sum(min_k deg_k) — the AGM-style bound that keeps the
SF10 triangle at ~E*log instead of ~E*d rows. All intermediate sizes
round up the bucket lattice (one compiled program per bucket, pad lanes
masked dead), the sorted-range search is ``jit_ops.range_count``, and
every failure degrades: fused op -> classic shadow plan
(``GraphIndexError``), query -> guard ladder.

Bag semantics match the classic cascade by construction: one output row
per (input row, pivot edge, close-edge combination), candidate label
masks applied once. Relationship uniqueness (openCypher isomorphism)
rides ``enforced_pairs`` exactly like the other fused ops: provably
redundant pairs are dropped by ``plan_filter_fastpath``; the rest are
enforced on the materializing path by comparing global element ids
(output-sized, i.e. cycle-count-sized — small).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ...ir import expr as E
from ...obs import trace as _obs_trace
from ...obs.metrics import REGISTRY as _OBS_REGISTRY
from ...obs.metrics import CounterView
from ...runtime.faults import fault_point
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
from .expand_op import (
    CsrExpandOp,
    _FusedExpandBase,
    _chain_rel_ends,
    _owner_name,
)
from .graph_index import (
    CANON_NODE,
    CANON_REL,
    GraphIndex,
    GraphIndexError,
    rekey_element_expr,
)

# which tier answered each multiway-intersect pull
WCOJ_TIER_COUNTS = CounterView(
    _OBS_REGISTRY.counter(
        "tpu_cypher_wcoj_tier_total",
        "multiway-intersect executions per resolved tier",
        labels=("tier",),
    ),
    "tier",
    ("count", "materialize", "factorized", "shadow"),
)

_MESH_WCOJ_TOTAL = _OBS_REGISTRY.counter(
    "tpu_cypher_mesh_wcoj_total",
    "WCOJ count executions whose range probes ran on the sharded "
    "(per-shard local searchsorted + psum) intersect tier",
)


def _mesh_range_counter(lists):
    """The sharded range-count program for the WCOJ count tier, or None.

    Eligible when a multi-device mesh is active, ``TPU_CYPHER_MESH_WCOJ``
    is ``auto``, and every intersection list's sorted ``edge_keys`` length
    is shard-divisible (free whenever the graph was ingested under the
    mesh: ``padded_to_mesh`` pads edge keys to a shard multiple with the
    above-everything sentinel, which can never match a probe). Each shard
    then leapfrog-intersects its LOCAL adjacency slice — two binary
    searches over the local keys — and the per-query counts tree-combine
    with ``psum`` (see ``parallel.mesh.sharded_range_count``)."""
    from ...parallel import mesh as PM

    mesh = PM.current_mesh()
    nsh = PM.mesh_size()
    if mesh is None or nsh <= 1:
        return None
    from ...utils.config import MESH_WCOJ

    if MESH_WCOJ.get().strip().lower() != "auto":
        return None
    for lst in lists:
        n_keys = int(lst.keys.shape[0])
        if n_keys == 0 or n_keys % nsh != 0:
            return None
    return PM.sharded_range_count(mesh), nsh


class PivotSpec(NamedTuple):
    """The peeled top expand supplying candidate+multiplicity by CSR row."""

    frontier_fld: str
    rel_fld: str
    far_fld: str  # the candidate variable
    types_key: Tuple[str, ...]
    backwards: bool
    far_labels: Tuple[str, ...]


class CloseSpec(NamedTuple):
    """One cycle-closing relationship tested by sorted-key range count.
    ``rev=True`` means the closing edge runs candidate -> anchor (the
    membership probe uses the reverse-orientation edge keys)."""

    anchor_fld: str
    rel_fld: str
    types_key: Tuple[str, ...]
    rev: bool


class _ListSpec(NamedTuple):
    """One intersection list, fully resolved against the GraphIndex."""

    rp: Any
    ci: Any
    eo: Any
    keys: Any
    pos: Any
    ok: Any
    rel_fld: str


@jax.jit
def _argmin_arm(degs, valid):
    """Per-row index of the minimum-degree list (ties -> first, i.e. the
    pivot); rows with any absent anchor never win an arm (their degrees
    read as +inf and their masked degree is 0 everywhere anyway)."""
    d = jnp.stack(degs)
    big = jnp.int64(1) << 62
    masked = jnp.where(valid[None, :], d, big)
    return jnp.argmin(masked, axis=0).astype(jnp.int32)


@jax.jit
def _arm_degrees(deg, arm, a, valid):
    """Degrees restricted to rows whose minimum list is ``a`` (a python
    int literal — one program per arm index, stable across queries)."""
    deg_a = jnp.where((arm == a) & valid, deg, 0)
    return deg_a, jnp.sum(deg_a)


@partial(jax.jit, static_argnames=("n",))
def _probe_queries(a_pos, a_ok, row, cand, live, n: int):
    """Sorted-key probes ``anchor*N + candidate`` for one searched list.
    Pad lanes (``live`` False, row/cand sanitized to 0) come out invalid so
    their range counts are zeroed inside the range-count contract."""
    q = jnp.take(a_pos, row) * n + cand
    ok = jnp.take(a_ok, row)
    if live is not None:
        ok = ok & live
    return q, ok


@jax.jit
def _mul(a, b):
    return a * b


@jax.jit
def _apply_label_mask(m, mask, cand):
    return m * jnp.take(mask, cand).astype(jnp.int64)


@jax.jit
def _sum_counts(m):
    return jnp.sum(m)


@jax.jit
def _clamp_rows(far_rows):
    # pad lanes may gather a label-filtered node's -1 row-map entry; they
    # are dead past the true count, so clamping keeps the gather in-bounds
    return jnp.maximum(far_rows, 0)


@jax.jit
def _zero_counts(m, keep):
    # lane-domain uniqueness folds into the run multiplicities: a dropped
    # lane contributes zero flat rows, so the factorized form never even
    # decodes it
    return jnp.where(keep, m, 0)


@jax.jit
def _eo_at(eo, pos):
    # run positions of dead/pad rows are clamped by the decode; clip keeps
    # the orig-edge gather in-bounds regardless (OOB under jit fills with
    # int64 min, which would poison downstream rel-scan gathers)
    return jnp.take(eo, jnp.clip(pos, 0, eo.shape[0] - 1))


class MultiwayIntersectOp(_FusedExpandBase):
    """Relational operator: candidate = intersection of K adjacency lists.

    ``children = (in_plan, classic)`` like every fused op: ``in_plan`` is
    the PIVOT's input (it binds the pivot frontier and every close
    anchor), ``classic`` the ExpandInto join cascade with identical
    header — the shadow plan for anything the fused path declines."""

    def __init__(
        self,
        in_plan: RelationalOperator,
        classic: RelationalOperator,
        graph_obj,
        *,
        pivot: PivotSpec,
        closes: Tuple[CloseSpec, ...],
        enforced_pairs: Tuple[Tuple[str, str], ...] = (),
    ):
        super().__init__(in_plan, classic, graph_obj)
        self.pivot = pivot
        self.closes = closes
        self.enforced_pairs = enforced_pairs

    @property
    def candidate_fld(self) -> str:
        return self.pivot.far_fld

    def _ctor_kwargs(self) -> Dict[str, Any]:
        return dict(pivot=self.pivot, closes=self.closes)

    def _show_inner(self) -> str:
        p = self.pivot
        arrow = "<-" if p.backwards else "->"
        t = "|".join(p.types_key) or "*"
        parts = [f"({p.frontier_fld}){arrow}[{p.rel_fld}:{t}]({p.far_fld})"]
        for c in self.closes:
            ct = "|".join(c.types_key) or "*"
            ca = "<-" if c.rev else "->"
            parts.append(f"({c.anchor_fld}){ca}[{c.rel_fld}:{ct}](.)")
        uniq = (
            " uniq" + ",".join(f"({a}<>{b})" for a, b in self.enforced_pairs)
            if self.enforced_pairs
            else ""
        )
        return "wcoj " + " x ".join(parts) + uniq

    # -- uniqueness-proof support -----------------------------------------

    def _rel_ends(self) -> Optional[Dict[str, Tuple[str, str, Tuple[str, ...]]]]:
        """Per-rel GRAPH-direction endpoints over this op's whole fused
        subtree (input chain + pivot + closes) for the redundancy proof in
        ``plan_filter_fastpath``; None when orientation-ambiguous or a rel
        repeats. An unrecognized input contributes nothing — pairs naming
        its rels simply stay unproven."""
        from ...relational.ops import CacheOp

        in_op = self.children[0]
        while isinstance(in_op, CacheOp):
            in_op = in_op.children[0]
        if (
            isinstance(in_op, MultiwayIntersectOp)
            and in_op._graph_obj is self._graph_obj
        ):
            out = in_op._rel_ends()
            if out is None:
                return None
        elif (
            isinstance(in_op, CsrExpandOp)
            and in_op._graph_obj is self._graph_obj
        ):
            out = _chain_rel_ends(in_op._chain_hops())
            if out is None:
                return None
        else:
            out = {}
        p = self.pivot
        ends = [
            (
                p.rel_fld,
                (p.far_fld, p.frontier_fld, p.types_key)
                if p.backwards
                else (p.frontier_fld, p.far_fld, p.types_key),
            )
        ]
        for c in self.closes:
            ends.append(
                (
                    c.rel_fld,
                    (p.far_fld, c.anchor_fld, c.types_key)
                    if c.rev
                    else (c.anchor_fld, p.far_fld, c.types_key),
                )
            )
        for r, v in ends:
            if r in out:
                return None
            out[r] = v
        return out

    # -- execution ---------------------------------------------------------

    def _anchor_flds(self) -> Tuple[str, ...]:
        return (self.pivot.frontier_fld,) + tuple(
            c.anchor_fld for c in self.closes
        )

    def _id_positions(self, gi: GraphIndex, ctx):
        """Compact positions + presence per anchor variable; ``valid`` is
        the all-anchors-present row mask (an absent anchor matches no
        edge, exactly the classic join's null semantics)."""
        from .table import ensure_flat

        in_op = self.children[0]
        in_t = ensure_flat(in_op.table)
        h = in_op.header
        out = []
        valid = None
        for f in self._anchor_flds():
            try:
                col = in_t._cols[h.column(h.id_expr(h.var(f)))]
            except (KeyError, ValueError) as exc:
                raise GraphIndexError(f"intersect anchor {f!r} unmapped") from exc
            pos, ok = gi.compact_of(col, ctx)
            out.append((pos, ok))
            valid = ok if valid is None else valid & ok
        return out, valid

    def _lists(self, gi: GraphIndex, ctx, positions):
        """The unified intersection lists: [0] = pivot, [1:] = closes.
        Each list's CSR orientation puts its ANCHOR on the row axis, and
        its edge keys sort by (anchor*N + candidate) in the same order —
        the one orientation serves both iteration and range counting."""
        p = self.pivot
        specs = [(p.types_key, p.backwards, p.rel_fld)] + [
            (c.types_key, c.rev, c.rel_fld) for c in self.closes
        ]
        out = []
        for (types_key, rev, rel_fld), (pos, ok) in zip(specs, positions):
            rp, ci, eo = gi.csr(types_key, rev, ctx)
            keys = gi.edge_keys(types_key, ctx, reverse=rev)
            out.append(_ListSpec(rp, ci, eo, keys, pos, ok, rel_fld))
        return out

    def _count(self, gi: GraphIndex, ctx, lists, valid) -> int:
        """Pure count tier — the WCOJ hot path. Per arm: expand the rows
        whose minimum-degree list is that arm, range-count every other
        list, multiply, sum. No output materialize, no acyclic
        intermediate; expanded lanes total sum(min_k deg_k)."""

        fault_point("expand")  # the per-arm count-tier syncs below

        mask = gi.label_mask(self.pivot.far_labels, ctx)
        degs = []
        for lst in lists:
            deg, _ = J.expand_degrees_total(lst.rp, lst.pos, valid)
            degs.append(deg)
        arm = _argmin_arm(tuple(degs), valid)
        bucketed = bucketing.enabled()
        n = gi.num_nodes
        mesh_tier = _mesh_range_counter(lists)
        if mesh_tier is not None:
            mesh_count, nsh = mesh_tier
            _MESH_WCOJ_TOTAL.inc()
            _obs_trace.note("wcoj_shards", nsh)
        total = 0
        for a, lst in enumerate(lists):
            deg_a, t_dev = _arm_degrees(degs[a], arm, a, valid)
            n_a = int(t_dev)
            if n_a == 0:
                continue
            # lanes: row + cand + orig (24B) plus one 8B count per probe
            bucketing.admit(n_a, 24 + 8 * (len(lists) - 1), "intersect")
            if bucketed:
                size = bucketing.round_size(n_a)
                row, cand, _, live = J.expand_materialize_counted(
                    lst.rp, lst.ci, lst.eo, lst.pos, deg_a, t_dev, size=size
                )
            else:
                row, cand, _ = J.expand_materialize(
                    lst.rp, lst.ci, lst.eo, lst.pos, deg_a, total=n_a
                )
                live = None
            m = None
            for b, other in enumerate(lists):
                if b == a:
                    continue
                q, qok = _probe_queries(
                    other.pos, other.ok, row, cand, live, n=n
                )
                if mesh_tier is not None:
                    cnt = mesh_count(other.keys, q, qok)
                else:
                    _, cnt, _ = J.range_count(other.keys, q, qok)
                m = cnt if m is None else _mul(m, cnt)
            if mask is not None:
                m = _apply_label_mask(m, mask, cand)
            total += int(_sum_counts(m))
        return total

    def _materialize(self, gi: GraphIndex, ctx, lists, valid):
        """Materializing tier (row-producing headers and/or uniqueness
        enforcement): iterate the pivot, expand each lane by its close
        range count so close-edge origs are recoverable as ``eo[lo+k]``.
        Single close keeps the classic output-bound flat path unless the
        factorized router (``optimizer.cost.prefer_factorized``) swaps in
        the run-compressed form; a multi-close materialize (a 4-clique
        whose rel vars someone reads) runs through
        :meth:`_materialize_multi_close` instead of declining to the
        shadow."""
        from .table import TpuTable
        from ...optimizer.cost import prefer_factorized

        if len(self.closes) != 1:
            return self._materialize_multi_close(gi, ctx, lists, valid)
        pivot, close = lists[0], lists[1]
        n = gi.num_nodes
        mask = gi.label_mask(self.pivot.far_labels, ctx)
        deg, t_dev = J.expand_degrees_total(pivot.rp, pivot.pos, valid)
        total = int(t_dev)
        bucketing.admit(total, 40, "intersect")
        bucketed = bucketing.enabled()
        if bucketed:
            size = bucketing.round_size(total)
            row, cand, orig_p, live = J.expand_materialize_counted(
                pivot.rp, pivot.ci, pivot.eo, pivot.pos, deg, t_dev, size=size
            )
        else:
            row, cand, orig_p = J.expand_materialize(
                pivot.rp, pivot.ci, pivot.eo, pivot.pos, deg, total=total
            )
            live = None
        q, qok = _probe_queries(close.pos, close.ok, row, cand, live, n=n)
        lo, m, out_dev = J.range_count(close.keys, q, qok)
        if mask is not None:
            m = _apply_label_mask(m, mask, cand)
            out_dev = _sum_counts(m)
        n_out = int(out_dev)
        pair_flds = {r for pr in self.enforced_pairs for r in pr}
        if (
            self.header.expressions
            and self.closes[0].rel_fld not in pair_flds
            and prefer_factorized(
                n_out, 32 + 9 * max(len(self.header.expressions), 1)
            )
        ):
            if self.enforced_pairs:
                # no pair names the close rel, so uniqueness reads only
                # lane-indexed ids and folds into the run multiplicities
                fault_point("compact")
                keep = self._wcoj_pair_keep(gi, ctx, row, orig_p, {})
                m = _zero_counts(m, keep)
                out_dev = _sum_counts(m)
                n_out = int(out_dev)
            fact = self._factorized_assemble(
                gi, ctx, (close,), row, cand, orig_p, total, (lo,), (m,), n_out
            )
            if fact is not None:
                return fact
        bucketing.admit(
            n_out, 32 + 9 * max(len(self.header.expressions), 1), "intersect"
        )
        # one materialize for both modes: with bucketing off, round_size is
        # the identity and the live mask degenerates to all-True, so the
        # counted path IS the exact path — and the size always routes
        # through the lattice
        size2 = bucketing.round_size(n_out)
        lane, orig_c, _ = J.into_materialize_counted(
            close.eo, lo, m, out_dev, size=size2
        )
        in_row, cand2, orig_p2 = J.tree_take((row, cand, orig_p), lane)
        if self.enforced_pairs and n_out:
            # same compaction discipline as _apply_enforced_pairs (two own
            # rels here, so the keep mask is built locally)
            fault_point("compact")
            keep = self._wcoj_pair_keep(
                gi, ctx, in_row, orig_p2, {self.closes[0].rel_fld: orig_c}
            )
            if bucketed:
                if int(in_row.shape[0]) != n_out:
                    keep = keep & J.row_tail_mask(in_row, n_out)
                idx, n_out = _mask_to_idx_bucketed(keep)
                in_row, cand2, orig_p2, orig_c = J.tree_take(
                    (in_row, cand2, orig_p2, orig_c), idx
                )
            else:
                n2 = int(J.mask_sum(keep))
                if n2 != n_out:
                    # tpulint: allow[pad-invariant] reason=bucketing-off branch only (the enabled branch above routes through _mask_to_idx_bucketed); exact size is the contract here
                    idx = J.mask_nonzero(keep, size=n2)
                    in_row, cand2, orig_p2, orig_c = J.tree_take(
                        (in_row, cand2, orig_p2, orig_c), idx
                    )
                    n_out = n2
        if not self.header.expressions:
            return TpuTable({}, n_out)
        _, _, row_map = gi.node_scan(self.pivot.far_labels, ctx)
        far_rows, _ = J.far_lookup(row_map, cand2)
        far_rows = _clamp_rows(far_rows)
        return self._assemble_multi(
            gi, ctx, in_row, orig_p2,
            {self.closes[0].rel_fld: orig_c}, far_rows, n_out,
        )

    def _materialize_multi_close(self, gi: GraphIndex, ctx, lists, valid):
        """Multi-close materialize (a 4-clique whose rel vars someone
        reads, or whose uniqueness pairs survive the planner proof)
        through the run-compressed representation: one suffix level per
        close, lane weights = per-lane range-count products. The flat row
        product (clique4 at SF1: ~878M rows) never materializes — either
        the output stays a ``FactorizedTable``, or the decode walks the
        runs directly at the OUTPUT extent (cycle-count-sized).
        ``TPU_CYPHER_FACTORIZE=off`` keeps the classic decline-to-shadow."""
        from .factorized import _decode_runs, _runs_weights, factorize_mode
        from .table import TpuTable
        from ...optimizer.cost import prefer_factorized

        if factorize_mode() == "off":
            raise GraphIndexError(
                "multiway materialize supports exactly one close constraint"
            )
        fault_point("expand")  # lane/output totals sync below
        pivot, closes = lists[0], lists[1:]
        n = gi.num_nodes
        mask = gi.label_mask(self.pivot.far_labels, ctx)
        deg, t_dev = J.expand_degrees_total(pivot.rp, pivot.pos, valid)
        total = int(t_dev)
        bucketing.admit(total, 24 + 16 * len(closes), "intersect")
        bucketed = bucketing.enabled()
        if bucketed:
            size = bucketing.round_size(total)
            row, cand, orig_p, live = J.expand_materialize_counted(
                pivot.rp, pivot.ci, pivot.eo, pivot.pos, deg, t_dev, size=size
            )
        else:
            row, cand, orig_p = J.expand_materialize(
                pivot.rp, pivot.ci, pivot.eo, pivot.pos, deg, total=total
            )
            live = None
        los, cnts = [], []
        for j, close in enumerate(closes):
            q, qok = _probe_queries(close.pos, close.ok, row, cand, live, n=n)
            lo_j, m_j, _ = J.range_count(close.keys, q, qok)
            if j == 0 and mask is not None:
                m_j = _apply_label_mask(m_j, mask, cand)
            los.append(lo_j)
            cnts.append(m_j)
        pair_flds = {r for pr in self.enforced_pairs for r in pr}
        pairs_on_close = bool(pair_flds & {c.rel_fld for c in self.closes})
        if self.enforced_pairs and not pairs_on_close:
            fault_point("compact")
            keep = self._wcoj_pair_keep(gi, ctx, row, orig_p, {})
            cnts[0] = _zero_counts(cnts[0], keep)
        w, W, tot = _runs_weights(tuple(cnts), t_dev)
        n_out = int(tot)
        nexprs = max(len(self.header.expressions), 1)
        if (
            self.header.expressions
            and not pairs_on_close
            and prefer_factorized(n_out, 32 + 9 * nexprs)
        ):
            fact = self._factorized_assemble(
                gi, ctx, closes, row, cand, orig_p, total,
                tuple(los), tuple(cnts), n_out,
            )
            if fact is not None:
                return fact
        # flat through the runs: decode positions at the OUTPUT extent —
        # the per-close blowup never exists on device
        bucketing.admit(n_out, 32 + 9 * nexprs, "intersect")
        size2 = bucketing.round_size(n_out)
        i, pos, live2 = _decode_runs(
            W, w, tuple(los), tuple(cnts), np.int64(0), np.int64(n_out), size2
        )
        in_row, cand2, orig_p2 = J.tree_take((row, cand, orig_p), i)
        orig_cs = {
            c.rel_fld: _eo_at(lst.eo, p_j)
            for c, lst, p_j in zip(self.closes, closes, pos)
        }
        if self.enforced_pairs and pairs_on_close and n_out:
            fault_point("compact")
            keep = self._wcoj_pair_keep(gi, ctx, in_row, orig_p2, orig_cs)
            if bucketed:
                keep = keep & live2
                idx, n_out = _mask_to_idx_bucketed(keep)
                in_row, cand2, orig_p2 = J.tree_take(
                    (in_row, cand2, orig_p2), idx
                )
                orig_cs = J.tree_take(orig_cs, idx)
            else:
                idx, n2 = _mask_to_idx(keep)
                if n2 != n_out:
                    in_row, cand2, orig_p2 = J.tree_take(
                        (in_row, cand2, orig_p2), idx
                    )
                    orig_cs = J.tree_take(orig_cs, idx)
                    n_out = n2
        if not self.header.expressions:
            return TpuTable({}, n_out)
        _, _, row_map = gi.node_scan(self.pivot.far_labels, ctx)
        far_rows, _ = J.far_lookup(row_map, cand2)
        far_rows = _clamp_rows(far_rows)
        return self._assemble_multi(
            gi, ctx, in_row, orig_p2, orig_cs, far_rows, n_out
        )

    def _factorized_assemble(
        self, gi: GraphIndex, ctx, closes, row, cand, orig_p, total,
        los, cnts, n_out: int,
    ):
        """The materialize output in factorized form: prefix = the pivot
        expansion's lane table (input pass-through at ``row``, pivot rel
        at ``orig_p``, candidate node columns at ``far_rows``), one
        suffix run level per close whose columns decode through the
        ``eo[pos]`` gather-map chain at collect time. Admission pays for
        LANES, never the flat product. Returns None when a close-rel
        header column cannot ride the device decode (OBJ or empty rel
        scan) — the caller keeps the flat path."""
        from .factorized import FactorizedTable, RunLevel, note_factorized
        from .table import TpuTable, ensure_flat

        p = self.pivot
        in_op = self.children[0]
        in_t = ensure_flat(in_op.table)
        relp_cols, relp_header = gi.rel_scan(p.types_key, ctx)
        node_cols, node_header, row_map = gi.node_scan(p.far_labels, ctx)
        canon_rel = E.Var(CANON_REL)
        canon_node = E.Var(CANON_NODE)
        close_index = {c.rel_fld: j for j, c in enumerate(self.closes)}
        plan: Dict[str, Tuple[Column, str]] = {}
        level_plans = tuple({} for _ in closes)
        for e in self.header.expressions:
            col = self.header.column(e)
            if col in plan or any(col in lp for lp in level_plans):
                continue
            if e in in_op.header:
                plan[col] = (in_t._cols[in_op.header.column(e)], "row")
                continue
            owner = _owner_name(e)
            if owner == p.rel_fld or owner in close_index:
                key = rekey_element_expr(e, canon_rel)
                if owner == p.rel_fld:
                    cc, hh = relp_cols, relp_header
                else:
                    cc, hh = gi.rel_scan(
                        self.closes[close_index[owner]].types_key, ctx
                    )
                if key is None or key not in hh:
                    raise GraphIndexError(f"unmapped rel expr {e!r}")
                src = cc[hh.column(key)]
                if owner == p.rel_fld:
                    plan[col] = (src, "origp")
                    continue
                if src.kind == OBJ or len(src) == 0:
                    return None
                level_plans[close_index[owner]][col] = src
                continue
            if owner == p.far_fld:
                key = rekey_element_expr(e, canon_node)
                if key is None or key not in node_header:
                    raise GraphIndexError(f"unmapped node expr {e!r}")
                plan[col] = (node_cols[node_header.column(key)], "far")
                continue
            raise GraphIndexError(f"unmapped expr {e!r}")
        far_rows, _ = J.far_lookup(row_map, cand)
        far_rows = _clamp_rows(far_rows)
        bucketing.admit(total, 9 * max(len(plan), 1), "factorized")
        count = total if bucketing.enabled() else None
        pfx_cols = self._gather_plan(
            plan, {"row": row, "origp": orig_p, "far": far_rows}, count=count
        )
        levels = [
            RunLevel(lo_j, m_j, {c: (src, (lst.eo,)) for c, src in lp.items()})
            for lo_j, m_j, lst, lp in zip(los, cnts, closes, level_plans)
        ]
        out = FactorizedTable(TpuTable(pfx_cols, total), levels, nrows=n_out)
        note_factorized(n_out, int(row.shape[0]), total)
        return out

    def _wcoj_pair_keep(self, gi: GraphIndex, ctx, row, orig_p, orig_cs):
        """Row-keep mask for enforced uniqueness pairs: the pivot rel reads
        its canonical rel-scan id at ``orig_p``, a close rel its scan at
        ``orig_cs[rel]`` (an empty dict means the caller proved no pair
        names a close — the lane-domain fold), any other rel its
        input-table id column at ``row`` — element ids are global, so
        cross-type comparisons stay sound."""
        from .table import ensure_flat

        in_op = self.children[0]
        in_t = ensure_flat(in_op.table)
        p = self.pivot
        close_types = {c.rel_fld: c.types_key for c in self.closes}
        cache: Dict[str, Any] = {}

        def ids_of(r):
            if r in cache:
                return cache[r]
            if r == p.rel_fld or r in orig_cs:
                types_key = p.types_key if r == p.rel_fld else close_types[r]
                orig = orig_p if r == p.rel_fld else orig_cs[r]
                cols, hh = gi.rel_scan(types_key, ctx)
                cid = hh.id_expr(hh.var(CANON_REL))
                out = jnp.take(cols[hh.column(cid)].data, orig)
            else:
                h = in_op.header
                try:
                    col = in_t._cols[h.column(h.id_expr(h.var(r)))]
                except (KeyError, ValueError) as exc:
                    raise GraphIndexError(
                        f"uniqueness rel {r!r} unmapped"
                    ) from exc
                out = jnp.take(col.data, row)
            cache[r] = out
            return out

        keep = None
        for ra, rb in self.enforced_pairs:
            k = ids_of(ra) != ids_of(rb)
            keep = k if keep is None else keep & k
        return keep

    def _assemble_multi(self, gi: GraphIndex, ctx, row, orig_p, orig_cs,
                        far_rows, n_out: int):
        """Column assembly with one rel source per fused rel: input
        pass-through at ``row``, pivot rel at ``orig_p``, close rel ``r``
        at ``orig_cs[r]``, candidate node columns at ``far_rows``
        (``_assemble`` handles one rel var; everything else is the same
        tagged-gather plan)."""
        from .table import TpuTable, ensure_flat

        in_op = self.children[0]
        in_t = ensure_flat(in_op.table)
        p = self.pivot
        relp_cols, relp_header = gi.rel_scan(p.types_key, ctx)
        close_scans = {
            c.rel_fld: gi.rel_scan(c.types_key, ctx)
            for c in self.closes
            if c.rel_fld in orig_cs
        }
        node_cols, node_header, _ = gi.node_scan(p.far_labels, ctx)
        canon_rel = E.Var(CANON_REL)
        canon_node = E.Var(CANON_NODE)
        tags = {r: f"origc{j}" for j, r in enumerate(orig_cs)}
        plan: Dict[str, Tuple[Column, str]] = {}
        for e in self.header.expressions:
            col = self.header.column(e)
            if col in plan:
                continue
            if e in in_op.header:
                plan[col] = (in_t._cols[in_op.header.column(e)], "row")
                continue
            owner = _owner_name(e)
            if owner == p.rel_fld or owner in close_scans:
                key = rekey_element_expr(e, canon_rel)
                if owner == p.rel_fld:
                    cc, hh, tag = relp_cols, relp_header, "origp"
                else:
                    cc, hh = close_scans[owner]
                    tag = tags[owner]
                if key is None or key not in hh:
                    raise GraphIndexError(f"unmapped rel expr {e!r}")
                plan[col] = (cc[hh.column(key)], tag)
                continue
            if owner == p.far_fld:
                key = rekey_element_expr(e, canon_node)
                if key is None or key not in node_header:
                    raise GraphIndexError(f"unmapped node expr {e!r}")
                plan[col] = (node_cols[node_header.column(key)], "far")
                continue
            raise GraphIndexError(f"unmapped expr {e!r}")
        count = n_out if bucketing.enabled() else None
        idx_by_tag = {"row": row, "origp": orig_p, "far": far_rows}
        for r, tag in tags.items():
            idx_by_tag[tag] = orig_cs[r]
        out = self._gather_plan(plan, idx_by_tag, count=count)
        return TpuTable(out, n_out)

    def _fused_table(self):
        from ...utils.config import WCOJ_MODE
        from .table import TpuTable

        # the multiway count/materialize syncs sit behind the expand-class
        # fault site like every other fused CSR operator
        fault_point("expand")
        gi = GraphIndex.of(self.graph)
        ctx = self.context
        gi.node_ids(ctx)
        if gi.num_nodes == 0:
            raise GraphIndexError("empty node space: shadow answers")
        if gi.num_nodes >= (1 << 30):
            raise GraphIndexError("intersect keys need pos*N+cand in int64")
        if (
            not self.header.expressions
            and not self.enforced_pairs
            and len(self.closes) == 1
            and WCOJ_MODE.get().strip().lower() != "force"
            and _fused_binary_count_available(gi)
        ):
            # WCOJ's edge is avoiding the MATERIALIZED intermediate. A
            # pure count never materializes on the binary side either
            # when a fused counting tier is in reach (the dense MXU A@A
            # tier under its node cap) — it counts the blowup without ever
            # building it. Auto mode hands the count back to the classic
            # plan; force keeps the pure WCOJ path (differentials).
            # ONLY single-close shapes hand back: the classic fused tiers
            # count one cycle close, so a multi-close count (clique4+)
            # would shadow into the materialized blowup (the 878M-row
            # r06 note) when `_count`'s range-count products answer it
            # without materializing anything.
            raise GraphIndexError(
                "fused binary count tier predicted faster: shadow answers"
            )
        positions, valid = self._id_positions(gi, ctx)
        lists = self._lists(gi, ctx, positions)
        if not self.header.expressions and not self.enforced_pairs:
            WCOJ_TIER_COUNTS.inc("count")
            _obs_trace.note("wcoj_tier", "count")
            return TpuTable({}, self._count(gi, ctx, lists, valid))
        from .factorized import FactorizedTable

        out = self._materialize(gi, ctx, lists, valid)
        tier = "factorized" if isinstance(out, FactorizedTable) else "materialize"
        WCOJ_TIER_COUNTS.inc(tier)
        _obs_trace.note("wcoj_tier", tier)
        return out

    def _compute_table(self):
        try:
            return self._fused_table()
        except (GraphIndexError, TpuBackendError):
            WCOJ_TIER_COUNTS.inc("shadow")
            _obs_trace.note("wcoj_tier", "shadow")
            return self.children[1].table


# ---------------------------------------------------------------------------
# Planner hook (installed via TpuTable.plan_multiway_intersect_fastpath)
# ---------------------------------------------------------------------------


def _fused_binary_count_available(gi: GraphIndex) -> bool:
    """Will the CLASSIC plan answer a pure cycle-close count through a
    fused counting tier that never materializes the intermediate? True
    whenever the dense MXU ``A @ A`` tier is live under ``dense_adj``'s
    node cap: the binary side then dodges the blowup WCOJ exists to
    avoid, and its matmul beats per-lane sorted probing — so auto mode
    should not steal the count. Materializing shapes are untouched: there
    the binary plan really does build the blowup and the multiway
    intersection wins."""
    from ...optimizer.cost import mxu_dense_node_cap
    from .expand_op import _mxu_dense_mode

    # dense_adj's size gate: past it the dense form is declined and the
    # binary plan falls back to materializing frontiers
    return _mxu_dense_mode() and 0 < gi.num_nodes <= mxu_dense_node_cap()


def _est_binary_blowup(gi: GraphIndex, ctx, types_key, rev: bool) -> int:
    """Upper bound on the binary plan's intermediate for closing a cycle
    over the pivot: edges(pivot types) * max_degree(pivot orientation) —
    each frontier row of an edge-shaped input can expand by up to the max
    degree before the close filters. Host-cached per (types, orientation);
    the EmptyHeaded-style rule compares it against the cost model's
    per-graph routing threshold (``optimizer.cost.wcoj_threshold``)."""
    cache = getattr(gi, "_wcoj_est", None)
    if cache is None:
        cache = gi._wcoj_est = {}
    got = cache.get((types_key, rev))
    if got is None:
        s, _, _ = gi._edge_endpoints(types_key, ctx)
        max_deg = gi.csr_max_degree(types_key, rev, ctx)
        got = cache[(types_key, rev)] = int(len(s)) * int(max(max_deg, 1))
    return got


def plan_multiway_intersect_fastpath(
    planner, op, in_plan, classic
) -> Optional[RelationalOperator]:
    """Route a cycle-closing ExpandInto to ``MultiwayIntersectOp``.

    The planner only calls this when its join-variable cycle detection
    fired (``_closes_pattern_cycle``); this hook adds the BACKEND half of
    the EmptyHeaded rule: structural fit (a directed fused expand to peel
    as the pivot, or an existing multiway op to extend with one more
    close) plus, in ``auto`` mode, the degree-stats blowup estimate —
    small graphs keep today's binary plan, blowup-prone ones switch.
    ``TPU_CYPHER_WCOJ=force`` routes every structural fit (differential
    tests), ``off`` disables routing entirely."""
    from ...relational.ops import CacheOp
    from ...utils.config import WCOJ_MODE

    mode = WCOJ_MODE.get().strip().lower()
    if mode not in ("auto", "force"):
        return None
    if op.direction != ">":
        return None
    in_vars = {v.name for v in in_plan.header.vars}
    if op.rel in in_vars or op.source not in in_vars or op.target not in in_vars:
        return None
    if op.source == op.target:
        return None
    node = in_plan
    while isinstance(node, CacheOp):
        node = node.children[0]
    types = getattr(op.rel_type.material, "types", frozenset()) or frozenset()
    types_key = GraphIndex.types_key(types)

    def shadow_plan():
        # the shadow child should be the plan "off" mode would have built
        # — the FUSED CsrExpandIntoOp (native/MXU count tiers, edge-key
        # probe), not the naive rel-scan JoinOp the planner hands us. A
        # tier decline (auto count hand-back, multi-close materialize,
        # corner graphs) then costs what the binary plan costs, instead
        # of paying a full hash-join cascade. The JoinOp stays the
        # fallback for anything the fused fastpath itself declines.
        fast_into = getattr(planner.ctx.table_cls, "plan_expand_into_fastpath", None)
        if fast_into is not None:
            upgraded = fast_into(planner, op, in_plan, classic)
            if upgraded is not None:
                return upgraded
        return classic

    if isinstance(node, MultiwayIntersectOp):
        # extend: one more close constraint on the same candidate
        # (4-cliques and denser); eligibility was already decided when the
        # base op routed
        cand = node.candidate_fld
        if cand not in (op.source, op.target):
            return None
        anchor = op.target if cand == op.source else op.source
        rel_names = {node.pivot.rel_fld} | {c.rel_fld for c in node.closes}
        if op.rel in rel_names or anchor == cand:
            return None
        if anchor not in {v.name for v in node.children[0].header.vars}:
            return None
        close = CloseSpec(anchor, op.rel, types_key, rev=cand == op.source)
        return MultiwayIntersectOp(
            node.children[0],
            shadow_plan(),
            node._graph_obj,
            pivot=node.pivot,
            closes=node.closes + (close,),
            enforced_pairs=node.enforced_pairs,
        )

    if not isinstance(node, CsrExpandOp) or node.undirected:
        return None
    cand = node.far_fld
    if cand not in (op.source, op.target):
        return None
    anchor = op.target if cand == op.source else op.source
    if anchor == cand or op.rel == node.rel_fld:
        return None
    if anchor not in {v.name for v in node.children[0].header.vars}:
        return None
    graph_obj = node._graph_obj
    try:
        gi = GraphIndex.of(graph_obj)
        ctx = in_plan.context
        gi.node_ids(ctx)
        if gi.num_nodes == 0 or gi.num_nodes >= (1 << 30):
            return None
        if mode == "auto":
            # the routing threshold is the cost model's, not the env
            # constant: `wcoj_threshold` returns the measured per-graph
            # crossover (intersect-vs-binary unit costs from profile
            # feedback), honouring TPU_CYPHER_WCOJ_MIN_ROWS verbatim when
            # the operator pinned it and reproducing the hand-tuned
            # default exactly while uncalibrated
            from ...optimizer.cost import prefer_wcoj

            est = _est_binary_blowup(gi, ctx, node.types_key, node.backwards)
            if not prefer_wcoj(est, graph_obj, ctx):
                return None
    except (GraphIndexError, TpuBackendError):
        return None
    pivot = PivotSpec(
        node.frontier_fld,
        node.rel_fld,
        node.far_fld,
        node.types_key,
        node.backwards,
        node.far_labels,
    )
    close = CloseSpec(anchor, op.rel, types_key, rev=cand == op.source)
    return MultiwayIntersectOp(
        node.children[0],
        shadow_plan(),
        graph_obj,
        pivot=pivot,
        closes=(close,),
        enforced_pairs=node.enforced_pairs,
    )


# every jitted program of this module dispatches under an obs.trace
# ``dispatch`` leaf (last: the decorators above stay plain ``jax.jit``)
_obs_trace.wrap_programs(globals())
