"""Abstract shape interpretation: the semantic layer under the shape rules.

The engine's two deepest invariants — every traced shape rounds the bucket
lattice (compile-cache stability) and every padded lane is masked before a
pad-sensitive consumer — were until now only *lexically* checked
(``pad-invariant`` matches ``size=`` kwargs, ``recompile-hazard`` matches
``jax.jit`` call shapes). This module interprets the array-manipulating
code of ``backend/tpu/``, ``parallel/``, and ``relational/`` over an
abstract shape lattice instead:

* ``STATIC(n)`` — a compile-time-fixed extent (a literal, a shape of an
  already-padded array, a static jit parameter);
* ``BUCKETED(lattice, origin)`` — an extent that routes through one of the
  ``bucketing`` rounding helpers, so it takes at most a bounded number of
  distinct values (one compiled program per lattice rung, not per count).
  ``masked`` additionally records that the pad lanes past the true count
  have been proven neutral (a 3-arg ``jnp.where`` against a liveness mask,
  or a comparison against an ``arange`` iota);
* ``DATA_DEPENDENT`` — an unrounded data-dependent count (a synced
  reduction, an unsized ``jnp.nonzero``): one XLA program per distinct
  value if it ever reaches a compile boundary;
* ``UNKNOWN`` — the conservative top. Like the device-taint lattice,
  UNKNOWN never fires a rule: every sharp verdict requires positive
  evidence.

Two classification *facets* share one recursive evaluator: the SIZE facet
("what count does this integer expression hold?") and the ARRAY facet
("what is the leading-dim extent of this array expression?"). They differ
exactly where arrays and counts diverge — a reduction is a STATIC scalar
as an array but a DATA_DEPENDENT value as a size.

Function boundaries reuse the PR 7 call graph unchanged: per-function
return summaries (fixed verdict or parameter passthrough, mirroring
``dataflow.DeviceTaint``) solved to fixpoint, with argument shape classes
flowing into parameter shape classes across every resolved call site.

The interpreter also exports its facts (``collect_facts``) as a
schema-versioned JSON artifact: the per-operator padded-shape transfer
catalog plus every classified size site — the cost-model feedstock for
the ROADMAP item 2 optimizer, whose padded-lattice cost model needs
exactly "what padded shape does this operator run at, as a function of
its lattice inputs". ``predict_padded`` is the pure (engine-import-free)
re-implementation of ``bucketing.round_size`` that makes static
predictions comparable against the padded-vs-true pairs obs spans stamp
at runtime; a test pins the two lattices equal so they cannot drift.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field, replace
from typing import Dict, FrozenSet, Iterable, List, Optional, Tuple, Union

from .core import FileContext, dotted_name

# directories whose array code the interpreter covers (relational/ is in
# scope for compile-boundary rules; the pad-mask rule narrows further)
SCOPE_DIRS = ("backend/tpu/", "parallel/", "relational/")
_BUCKETING_SUFFIX = "backend/tpu/bucketing.py"

FACTS_SCHEMA_VERSION = 1

# the smallest nonzero bucket — mirrors bucketing._BUCKET_FLOOR; pinned
# equal by tests/test_shape_facts.py so the pure predictor cannot drift
BUCKET_FLOOR = 32

# ---------------------------------------------------------------------------
# the abstract domain
# ---------------------------------------------------------------------------

STATIC_KIND = "static"
BUCKETED_KIND = "bucketed"
DATA_KIND = "data"
UNKNOWN_KIND = "unknown"

_RANK = {STATIC_KIND: 0, BUCKETED_KIND: 1, DATA_KIND: 2, UNKNOWN_KIND: 3}

# static upper bound on distinct lattice rungs a bucketed size can take
# (counts up to 2^40 rows — far past any single-device graph): the
# bucket-cardinality bound exported per site
BUCKET_BOUNDS = {
    "pow2": 36,       # pow2 rungs from the floor to 2^40
    "1.25": 112,      # 1.25-ratio rungs over the same range
    "mode": 112,      # round_size: whichever lattice MODE selects
    "multiple": 64,   # round_up_multiple: bounded by the padded axis cap
    "derived": 160,   # concatenations/sums of bucketed extents
}


@dataclass(frozen=True)
class ShapeVal:
    """One point of the abstract shape lattice."""

    kind: str
    n: Optional[int] = None       # known extent (STATIC only)
    lattice: Optional[str] = None  # pow2 | 1.25 | mode | multiple | derived
    origin: str = ""              # where the class was introduced
    masked: bool = False          # pad lanes proven neutral (BUCKETED)
    iota: bool = False            # an arange over the axis (compare => mask)

    def render(self) -> str:
        if self.kind == STATIC_KIND:
            return f"static({self.n})" if self.n is not None else "static"
        if self.kind == BUCKETED_KIND:
            m = ", masked" if self.masked else ""
            return f"bucketed({self.lattice}{m})"
        if self.kind == DATA_KIND:
            o = f": {self.origin}" if self.origin else ""
            return f"data-dependent{o}"
        return "unknown"


def STATIC(n: Optional[int] = None, **kw) -> ShapeVal:
    return ShapeVal(STATIC_KIND, n=n, **kw)


def BUCKETED(lattice: str, origin: str = "", masked: bool = False) -> ShapeVal:
    return ShapeVal(BUCKETED_KIND, lattice=lattice, origin=origin, masked=masked)


def DATA(origin: str = "") -> ShapeVal:
    return ShapeVal(DATA_KIND, origin=origin)


UNKNOWN_SHAPE = ShapeVal(UNKNOWN_KIND)


def join(vals: Iterable[ShapeVal], masked_any: bool = False) -> ShapeVal:
    """Lattice join. UNKNOWN absorbs everything (conservative: a rule
    never fires on a join it did not fully understand); DATA absorbs
    BUCKETED absorbs STATIC. ``masked_any`` selects the mask-combining
    policy: AND by default (every contributor must be proven neutral),
    OR for operators that force pads dead when ANY operand does
    (``x & live``, ``x * live``)."""
    vals = list(vals)
    if not vals:
        return UNKNOWN_SHAPE
    top = max(vals, key=lambda v: _RANK[v.kind])
    if top.kind == UNKNOWN_KIND:
        return UNKNOWN_SHAPE
    if top.kind == DATA_KIND:
        return top
    if top.kind == BUCKETED_KIND:
        bucketed = [v for v in vals if v.kind == BUCKETED_KIND]
        lattices = {v.lattice for v in bucketed}
        lattice = lattices.pop() if len(lattices) == 1 else "derived"
        if masked_any:
            masked = any(v.masked for v in vals)
        else:
            masked = all(v.masked for v in vals)
        return BUCKETED(lattice, origin=bucketed[0].origin, masked=masked)
    ns = {v.n for v in vals}
    return STATIC(ns.pop() if len(ns) == 1 else None,
                  iota=any(v.iota for v in vals))


# ---------------------------------------------------------------------------
# the pure padded-shape predictor (no engine import: the agreement test
# pins it equal to bucketing.round_size so the two can never drift)
# ---------------------------------------------------------------------------


def predict_padded(n: int, mode: str = "pow2") -> int:
    """The padded extent ``bucketing.round_size`` produces for a true
    count ``n`` under lattice ``mode`` — re-derived from the lattice
    definition alone. ``n <= 0`` stays 0 (the empty case keeps its own
    trivially-cheap program); ``off`` is identity."""
    n = int(n)
    if n <= 0:
        return 0
    if mode == "off":
        return n
    if mode == "1.25":
        rung = BUCKET_FLOOR
        while rung < n:
            rung = max(rung + 1, int(rung * 1.25))
        return rung
    # pow2: smallest power of two >= max(n, floor)
    m = max(n, BUCKET_FLOOR)
    return 1 << (m - 1).bit_length() if m > 1 else 1


# ---------------------------------------------------------------------------
# the transfer catalog: how each primitive the engine uses maps input
# shape classes to its padded output shape. This table IS the per-operator
# facts payload; the evaluator's call transfer consults the same leaf sets.
# ---------------------------------------------------------------------------

# leaf names of array-producing calls with an explicit static size kwarg
SIZE_KWARGS = ("size", "total_repeat_length", "num_segments")

_REDUCERS = frozenset(
    "sum prod mean min max amin amax any all argmin argmax count_nonzero "
    "nanmin nanmax nansum median average".split()
)
_SORTS = frozenset("sort argsort lexsort".split())
_ELEMENTWISE = frozenset(
    "abs clip astype asarray minimum maximum logical_and logical_or "
    "logical_not isnan isfinite sign negative add subtract multiply "
    "floor_divide mod equal not_equal less less_equal greater "
    "greater_equal bitwise_and bitwise_or invert where_keep exp log".split()
)
_PRESERVING = frozenset("reshape ravel flatten copy block_until_ready".split())
_ROUNDER_LATTICE = {
    "round_size": "mode",
    "round_up_pow2": "pow2",
    "round_up_multiple": "multiple",
    "bucket_pad_host": "mode",
}
_DEVICE_PREFIXES = ("jnp.", "jax.", "lax.", "J.", "np.", "numpy.")

# the exported per-operator padded-shape formulas, as functions of the
# abstract inputs. ``padded_shape`` is the leading-dim extent of the
# result; ``class`` names the transfer family the evaluator applies.
OPERATOR_FORMULAS: List[Dict[str, str]] = [
    {"op": "jnp.nonzero", "class": "sized_materialize",
     "padded_shape": "size (DATA_DEPENDENT when the size kwarg is absent)"},
    {"op": "jnp.repeat", "class": "sized_materialize",
     "padded_shape": "total_repeat_length (DATA_DEPENDENT when absent and "
                     "repeats is traced)"},
    {"op": "jnp.unique", "class": "sized_materialize",
     "padded_shape": "size (DATA_DEPENDENT when the size kwarg is absent)"},
    {"op": "jax.ops.segment_sum", "class": "sized_materialize",
     "padded_shape": "num_segments"},
    {"op": "jnp.where", "class": "select",
     "padded_shape": "join(x, y); masked=True (3-arg form); "
                     "DATA_DEPENDENT (1-arg form)"},
    {"op": "jnp.arange", "class": "iota",
     "padded_shape": "stop; iota=True (a compare against it is a "
                     "liveness mask)"},
    {"op": "jnp.zeros|ones|full|empty", "class": "alloc",
     "padded_shape": "shape[0]"},
    {"op": "jnp.concatenate|hstack|append", "class": "concat",
     "padded_shape": "sum(parts) -> bucketed(derived) when any part is "
                     "bucketed"},
    {"op": "jnp.stack", "class": "concat",
     "padded_shape": "len(parts) along the new axis; parts join"},
    {"op": "jnp.reshape|ravel", "class": "preserve",
     "padded_shape": "input (total extent preserved)"},
    {"op": "jnp.pad", "class": "pad",
     "padded_shape": "input + pad_width; masked=False (fresh pad lanes "
                     "are live garbage until masked)"},
    {"op": "jnp.sort|argsort|lexsort|lax.sort", "class": "sort",
     "padded_shape": "input (pad-sensitive consumer: pads must sort last "
                     "via the ID_SENTINEL discipline)"},
    {"op": "jnp.searchsorted", "class": "search",
     "padded_shape": "shape(v); the sorted operand is the pad-sensitive "
                     "side"},
    {"op": "jnp.cumsum", "class": "scan",
     "padded_shape": "input; masked=False (pad lanes absorb the running "
                     "total)"},
    {"op": "jnp.take|take_along_axis", "class": "gather",
     "padded_shape": "shape(indices); masked=False (pad lanes gather "
                     "duplicate payload)"},
    {"op": "jnp.sum|max|min|any|all|argmin|argmax|count_nonzero",
     "class": "reduction",
     "padded_shape": "scalar as an array; DATA_DEPENDENT as a size"},
    {"op": "lax.top_k", "class": "sized_materialize", "padded_shape": "k"},
    {"op": "lax.dynamic_slice_in_dim", "class": "sized_materialize",
     "padded_shape": "slice_size"},
    {"op": "jnp.dot|matmul", "class": "contraction",
     "padded_shape": "shape(lhs)[0]"},
    {"op": "bucketing.round_size", "class": "rounder",
     "padded_shape": "bucketed(mode): next rung of the active lattice "
                     "(pow2 floor 32 | 1.25 ratio from 32)"},
    {"op": "bucketing.round_up_pow2", "class": "rounder",
     "padded_shape": "bucketed(pow2): 1 << ceil(log2(max(n, floor)))"},
    {"op": "bucketing.round_up_multiple", "class": "rounder",
     "padded_shape": "bucketed(multiple): ceil(n / m) * m"},
    {"op": "bucketing.bucket_pad_host", "class": "rounder",
     "padded_shape": "bucketed(mode): host tail-pad up to round_size"},
    {"op": "int|float|bool", "class": "sync",
     "padded_shape": "preserves the size class of the synced operand "
                     "(a synced DATA_DEPENDENT count stays DATA_DEPENDENT)"},
    # the factorized run-decompress family (backend/tpu/factorized.py):
    # lane-extent prefix programs plus the bucketed flat-extent decode
    {"op": "factorized._runs_weights", "class": "run_prefix",
     "padded_shape": "lane extent (input); per-lane run products cumsum "
                     "into exclusive prefixes masked to ID_SENTINEL past "
                     "the live lanes (the pad-mask discipline cumsum "
                     "otherwise forfeits)"},
    {"op": "factorized._decode_runs", "class": "run_decode",
     "padded_shape": "size (bucketed: round_size(chunk or total) passed "
                     "static); searchsorted over the sentinel-masked "
                     "prefix then mixed-radix positions at the same "
                     "extent"},
    {"op": "factorized._gather_decoded", "class": "gather",
     "padded_shape": "shape(i) (the decoded flat extent); pad lanes "
                     "gather duplicate payload and stay dead via the "
                     "decode's live mask"},
]


def jit_static_argnames(fn: ast.AST) -> FrozenSet[str]:
    """The ``static_argnames`` a ``jax.jit``/``partial(jax.jit, ..)``
    decorator declares on ``fn`` — the compile-cache-keyed parameters a
    bucket-cardinality bound must exist for."""
    names: set = set()
    for dec in fn.decorator_list:
        if not isinstance(dec, ast.Call):
            continue
        d = dotted_name(dec.func)
        inner, kwsrc = d, dec.keywords
        if d.split(".")[-1] == "partial" and dec.args:
            inner = dotted_name(dec.args[0])
        if not (inner in ("jax.jit", "jit") or inner.endswith(".jit")):
            continue
        for kw in kwsrc:
            if kw.arg != "static_argnames":
                continue
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                names.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                for el in v.elts:
                    if isinstance(el, ast.Constant) and isinstance(el.value, str):
                        names.add(el.value)
    return frozenset(names)


def in_scope(relpath: str) -> bool:
    if relpath.endswith(_BUCKETING_SUFFIX):
        return False  # the lattice itself
    return any(d in relpath for d in SCOPE_DIRS)


# ---------------------------------------------------------------------------
# the interprocedural analysis
# ---------------------------------------------------------------------------

SIZE = "size"
ARRAY = "array"

# a symbolic summary component: ("param", name, masked_through)
_Param = Tuple[str, str, bool]
# per-(function, facet) return summary
Summary = Union[ShapeVal, Tuple[str, FrozenSet[str], bool]]


class ShapeAnalysis:
    """Per-function shape summaries + parameter shape classes, solved to
    fixpoint over the call graph — ``dataflow.DeviceTaint`` shaped, with
    ShapeVal as the lattice and two facets per function."""

    def __init__(self, graph):
        self.graph = graph
        self.infos = [
            info for info in graph.infos.values() if in_scope(info.ctx.relpath)
        ]
        self._scope_nodes = {info.node for info in self.infos}
        # (fn node, facet) -> Summary; (fn node, param, facet) -> ShapeVal
        self.returns: Dict[Tuple[ast.AST, str], Summary] = {}
        self.params: Dict[Tuple[ast.AST, str, str], ShapeVal] = {}
        # post-fixpoint query memo: (expr node, facet) -> ShapeVal
        self._memo: Dict[Tuple[ast.AST, str], ShapeVal] = {}
        # precomputed per-round inputs: walking every function AST each
        # fixpoint round is what would blow the <5s budget
        self._returns_of: Dict[ast.AST, List[ast.AST]] = {}
        for info in self.infos:
            self._returns_of[info.node] = [
                n.value
                for n in ast.walk(info.node)
                if isinstance(n, ast.Return)
                and n.value is not None
                and info.ctx.enclosing_function(n) is info.node
            ]
        self._callee_sites = {}
        for info in self.infos:
            sites = []
            for site, targets in graph.callees(info):
                tgts = [t for t in targets if t.node in self._scope_nodes]
                if tgts:
                    sites.append((site, tgts))
            if sites:
                self._callee_sites[info.node] = sites
        self._solve()

    # -- public --------------------------------------------------------------

    def classify_size(
        self, ctx: FileContext, fn: Optional[ast.AST], expr: ast.AST
    ) -> ShapeVal:
        """The abstract class of an integer count expression."""
        return self._query(ctx, fn, expr, SIZE)

    def classify_array(
        self, ctx: FileContext, fn: Optional[ast.AST], expr: ast.AST
    ) -> ShapeVal:
        """The abstract leading-dim extent of an array expression."""
        return self._query(ctx, fn, expr, ARRAY)

    def _query(self, ctx, fn, expr, facet) -> ShapeVal:
        key = (expr, facet)
        hit = self._memo.get(key)
        if hit is None:
            v = self._eval(ctx, fn, expr, facet, 0, symbolic=False)
            hit = v if isinstance(v, ShapeVal) else UNKNOWN_SHAPE
            self._memo[key] = hit
        return hit

    # -- fixpoint ------------------------------------------------------------

    def _solve(self, max_rounds: int = 8) -> None:
        for _ in range(max_rounds):
            changed = False
            for info in self.infos:
                for facet in (SIZE, ARRAY):
                    new = self._summarize(info, facet)
                    key = (info.node, facet)
                    if self.returns.get(key) != new:
                        self.returns[key] = new
                        changed = True
            changed |= self._flow_params()
            if not changed:
                return

    def _summarize(self, info, facet: str) -> Summary:
        ctx, fn = info.ctx, info.node
        verdicts: List[ShapeVal] = []
        passthrough: set = set()
        masked_through = False
        for ret in self._returns_of.get(fn, ()):
            v = self._eval(ctx, fn, ret, facet, 0, symbolic=True)
            if isinstance(v, tuple):
                passthrough.add(v[1])
                masked_through |= v[2]
            else:
                verdicts.append(v)
        sharp = [v for v in verdicts if v.kind in (DATA_KIND, BUCKETED_KIND)]
        if sharp:
            # any data/bucketed return dominates: report the join of the
            # sharp returns (a mixed passthrough demotes masked)
            out = join(sharp)
            if passthrough and out.kind == BUCKETED_KIND and not masked_through:
                out = replace(out, masked=False)
            return out
        if passthrough:
            return ("passthrough", frozenset(passthrough), masked_through)
        if verdicts:
            return join(verdicts)
        return UNKNOWN_SHAPE

    def _flow_params(self) -> bool:
        incoming: Dict[Tuple[ast.AST, str, str], List[ShapeVal]] = {}
        for info in self.infos:
            for site, targets in self._callee_sites.get(info.node, ()):
                for facet in (SIZE, ARRAY):
                    arg_vals = [
                        self._arg_val(site.ctx, info.node, a, facet)
                        for a in site.call.args
                    ]
                    kw_vals = {
                        kw.arg: self._arg_val(site.ctx, info.node, kw.value, facet)
                        for kw in site.call.keywords
                        if kw.arg is not None
                    }
                    for tgt in targets:
                        names = tgt.ctx.param_names(tgt.node)
                        if names and names[0] == "self":
                            names = names[1:]
                        for i, v in enumerate(arg_vals):
                            if i < len(names):
                                incoming.setdefault(
                                    (tgt.node, names[i], facet), []
                                ).append(v)
                        for k, v in kw_vals.items():
                            if k in names:
                                incoming.setdefault(
                                    (tgt.node, k, facet), []
                                ).append(v)
        changed = False
        for key, vals in incoming.items():
            new = join(vals)
            if self.params.get(key, UNKNOWN_SHAPE) != new:
                self.params[key] = new
                changed = True
        return changed

    def _arg_val(self, ctx, fn, expr, facet) -> ShapeVal:
        v = self._eval(ctx, fn, expr, facet, 0, symbolic=False)
        return v if isinstance(v, ShapeVal) else UNKNOWN_SHAPE

    # -- the evaluator -------------------------------------------------------

    def _eval(self, ctx, fn, expr, facet, depth, symbolic):
        """-> ShapeVal | ("param", name, masked_through). Depth-capped,
        UNKNOWN on anything not understood."""
        if depth > 6:
            return UNKNOWN_SHAPE
        if isinstance(expr, ast.Constant):
            if facet == SIZE and isinstance(expr.value, int):
                return STATIC(int(expr.value))
            return STATIC()
        if isinstance(expr, ast.Name):
            return self._eval_name(ctx, fn, expr.id, facet, depth, symbolic)
        if isinstance(expr, ast.Call):
            return self._eval_call(ctx, fn, expr, facet, depth, symbolic)
        if isinstance(expr, ast.Subscript):
            # x.shape[0] / x.shape[axis]: the array facet of x, as a size
            if (
                isinstance(expr.value, ast.Attribute)
                and expr.value.attr == "shape"
            ):
                return self._eval(
                    ctx, fn, expr.value.value, ARRAY, depth + 1, symbolic
                )
            # plain subscripts/slices approximately preserve the class
            return self._eval(ctx, fn, expr.value, facet, depth + 1, symbolic)
        if isinstance(expr, ast.Attribute):
            if expr.attr in ("size", "shape"):
                return self._eval(ctx, fn, expr.value, ARRAY, depth + 1, symbolic)
            # other attributes (self._cap, table.nrows): precomputed state,
            # already padded/static by the time it is an attribute — but not
            # provably, so stay at the non-firing top
            return UNKNOWN_SHAPE
        if isinstance(expr, ast.BinOp):
            vs = [
                self._eval(ctx, fn, s, facet, depth + 1, symbolic)
                for s in (expr.left, expr.right)
            ]
            return self._combine(
                vs, masked_any=isinstance(expr.op, (ast.Mult, ast.BitAnd))
            )
        if isinstance(expr, ast.Compare):
            sides = [expr.left] + list(expr.comparators)
            vs = [
                self._eval(ctx, fn, s, ARRAY, depth + 1, symbolic)
                for s in sides
            ]
            iota = any(isinstance(v, ShapeVal) and v.iota for v in vs)
            out = self._combine(vs, masked_any=iota)
            if iota and isinstance(out, ShapeVal):
                # lane < nvalid over an iota: THE liveness-mask idiom — pad
                # lanes are False by construction
                out = replace(out, masked=True, iota=False)
            return out
        if isinstance(expr, ast.BoolOp):
            vs = [
                self._eval(ctx, fn, s, facet, depth + 1, symbolic)
                for s in expr.values
            ]
            return self._combine(vs, masked_any=isinstance(expr.op, ast.And))
        if isinstance(expr, ast.UnaryOp):
            v = self._eval(ctx, fn, expr.operand, facet, depth + 1, symbolic)
            if isinstance(v, ShapeVal) and isinstance(expr.op, ast.Not):
                # ~live flips pad lanes True: the mask proof does not survive
                return replace(v, masked=False)
            return v
        if isinstance(expr, ast.IfExp):
            vs = [
                self._eval(ctx, fn, s, facet, depth + 1, symbolic)
                for s in (expr.body, expr.orelse)
            ]
            return self._combine(vs)
        if isinstance(expr, (ast.Tuple, ast.List)):
            vs = [
                self._eval(ctx, fn, e, facet, depth + 1, symbolic)
                for e in expr.elts
            ]
            return self._combine(vs)
        if isinstance(expr, ast.Starred):
            return self._eval(ctx, fn, expr.value, facet, depth + 1, symbolic)
        return UNKNOWN_SHAPE

    def _combine(self, vs, masked_any: bool = False):
        params = [v for v in vs if isinstance(v, tuple)]
        shapes = [v for v in vs if isinstance(v, ShapeVal)]
        if params:
            # an op OVER a param is still param-shaped for the summary;
            # record whether a mask-forcing op was part of the chain
            masked = any(p[2] for p in params) or (
                masked_any and any(s.masked for s in shapes)
            )
            return ("param", params[0][1], masked)
        return join(shapes, masked_any=masked_any)

    def _eval_name(self, ctx, fn, name, facet, depth, symbolic):
        if fn is not None and name in ctx.param_names(fn):
            if not ctx.assignments(fn, name):
                if symbolic:
                    return ("param", name, False)
                return self.params.get((fn, name, facet), UNKNOWN_SHAPE)
        vals = [
            self._eval(ctx, fn, v, facet, depth + 1, symbolic)
            for v in ctx.assignments(fn, name)
        ]
        if not vals:
            return UNKNOWN_SHAPE
        return self._combine(vals)

    def _eval_call(self, ctx, fn, call, facet, depth, symbolic):
        name = dotted_name(call.func)
        leaf = name.split(".")[-1] if name else ""
        line = getattr(call, "lineno", 0)

        # -- rounders: the lattice entry points -----------------------------
        if leaf in _ROUNDER_LATTICE:
            return BUCKETED(
                _ROUNDER_LATTICE[leaf], origin=f"{leaf}@{ctx.relpath}:{line}"
            )

        # -- host syncs / casts preserve the size class ---------------------
        if leaf in ("int", "float", "bool") and len(call.args) == 1 and not name.count("."):
            if facet == SIZE:
                return self._eval(ctx, fn, call.args[0], SIZE, depth + 1, symbolic)
            return STATIC()  # a synced scalar has no leading dim
        if leaf == "len" and len(call.args) == 1 and name == "len":
            return self._eval(ctx, fn, call.args[0], ARRAY, depth + 1, symbolic)
        if name in ("min", "max", "abs") and call.args:
            vs = [
                self._eval(ctx, fn, a, facet, depth + 1, symbolic)
                for a in call.args
            ]
            return self._combine(vs)

        device = name.startswith(_DEVICE_PREFIXES)

        # -- .item() and reductions: scalar arrays, data-dependent sizes ----
        if isinstance(call.func, ast.Attribute) and leaf == "item" and not call.args:
            if facet == SIZE:
                return self._eval(
                    ctx, fn, call.func.value, SIZE, depth + 1, symbolic
                )
            return STATIC()
        if leaf in _REDUCERS and (device or isinstance(call.func, ast.Attribute)):
            if facet == SIZE:
                return DATA(f"{name or leaf}@{ctx.relpath}:{line}")
            return STATIC()  # reduced away: scalar (or trailing-axes) result

        # -- the array-op transfer catalog ----------------------------------
        if device:
            v = self._transfer_device(ctx, fn, call, leaf, facet, depth, symbolic)
            if v is not None:
                return v

        # -- project calls: consume the fixpoint summaries ------------------
        targets = self.graph.resolve_call(ctx, call)
        scope_targets = [t for t in targets if t.node in self._scope_nodes]
        if scope_targets:
            vs = []
            for tgt in scope_targets:
                summary = self.returns.get((tgt.node, facet), UNKNOWN_SHAPE)
                if isinstance(summary, tuple):
                    vs.append(
                        self._passthrough_at_site(
                            ctx, fn, call, tgt, summary, facet, depth, symbolic
                        )
                    )
                else:
                    vs.append(summary)
            return self._combine(vs)
        return UNKNOWN_SHAPE

    def _transfer_device(self, ctx, fn, call, leaf, facet, depth, symbolic):
        """The jnp/lax transfer functions. Returns None for ops the
        catalog does not model (the caller falls through to UNKNOWN)."""
        size_kw = next(
            (kw for kw in call.keywords if kw.arg in SIZE_KWARGS), None
        )
        line = getattr(call, "lineno", 0)

        if leaf in ("nonzero", "unique"):
            if size_kw is not None:
                return self._size_as_shape(ctx, fn, size_kw.value, depth, symbolic)
            return DATA(f"jnp.{leaf} (unsized)@{ctx.relpath}:{line}")
        if leaf == "repeat":
            if size_kw is not None:
                return self._size_as_shape(ctx, fn, size_kw.value, depth, symbolic)
            if len(call.args) >= 2 and isinstance(call.args[1], ast.Constant):
                # static repeats: extent scales by a constant, class preserved
                return self._eval(ctx, fn, call.args[0], ARRAY, depth + 1, symbolic)
            return DATA(f"jnp.repeat (unsized)@{ctx.relpath}:{line}")
        if leaf == "where":
            if len(call.args) == 1:
                return DATA(f"jnp.where (1-arg)@{ctx.relpath}:{line}")
            if len(call.args) == 3:
                vs = [
                    self._eval(ctx, fn, a, ARRAY, depth + 1, symbolic)
                    for a in call.args[1:3]
                ]
                out = self._combine(vs)
                if isinstance(out, ShapeVal):
                    return replace(out, masked=True)
                return ("param", out[1], True)
            return UNKNOWN_SHAPE
        if leaf == "arange":
            v = self._size_as_shape(
                ctx, fn, call.args[-1] if call.args else call, depth, symbolic
            )
            if isinstance(v, ShapeVal):
                return replace(v, iota=True)
            return v
        if leaf in ("zeros", "ones", "full", "empty"):
            shape_arg = call.args[0] if call.args else None
            if isinstance(shape_arg, (ast.Tuple, ast.List)) and shape_arg.elts:
                shape_arg = shape_arg.elts[0]
            if shape_arg is not None:
                return self._size_as_shape(ctx, fn, shape_arg, depth, symbolic)
            return UNKNOWN_SHAPE
        if leaf in ("concatenate", "hstack", "append", "stack"):
            parts = call.args[0].elts if (
                call.args and isinstance(call.args[0], (ast.Tuple, ast.List))
            ) else call.args
            vs = [
                self._eval(ctx, fn, p, ARRAY, depth + 1, symbolic)
                for p in parts
            ]
            out = self._combine(vs)
            if isinstance(out, ShapeVal) and out.kind == BUCKETED_KIND:
                # a concat of bucketed extents leaves the source lattice
                return replace(out, lattice="derived")
            return out
        if leaf in _PRESERVING:
            src = (
                call.func.value
                if isinstance(call.func, ast.Attribute)
                else (call.args[0] if call.args else None)
            )
            if src is None:
                return UNKNOWN_SHAPE
            return self._eval(ctx, fn, src, ARRAY, depth + 1, symbolic)
        if leaf == "pad":
            v = self._eval(
                ctx, fn, call.args[0] if call.args else call, ARRAY, depth + 1,
                symbolic,
            )
            if isinstance(v, ShapeVal):
                # fresh pad lanes are live garbage until masked
                return replace(v, masked=False,
                               lattice="derived" if v.kind == BUCKETED_KIND
                               else v.lattice)
            return v
        if leaf in _SORTS:
            ops = call.args[0].elts if (
                leaf == "lexsort"
                and call.args
                and isinstance(call.args[0], (ast.Tuple, ast.List))
            ) else call.args[:1]
            vs = [
                self._eval(ctx, fn, o, ARRAY, depth + 1, symbolic) for o in ops
            ]
            return self._combine(vs)
        if leaf == "searchsorted":
            if len(call.args) >= 2:
                return self._eval(ctx, fn, call.args[1], ARRAY, depth + 1, symbolic)
            return UNKNOWN_SHAPE
        if leaf in ("cumsum", "cummax"):
            v = self._eval(
                ctx, fn, call.args[0] if call.args else call, ARRAY, depth + 1,
                symbolic,
            )
            if isinstance(v, ShapeVal):
                return replace(v, masked=False)  # pads absorb the running total
            return v
        if leaf in ("take", "take_along_axis"):
            idx = (
                call.args[1]
                if len(call.args) >= 2
                else next(
                    (kw.value for kw in call.keywords if kw.arg == "indices"),
                    None,
                )
            )
            if idx is None:
                return UNKNOWN_SHAPE
            v = self._eval(ctx, fn, idx, ARRAY, depth + 1, symbolic)
            if isinstance(v, ShapeVal):
                return replace(v, masked=False, iota=False)
            return v
        if leaf == "top_k" and len(call.args) >= 2:
            return self._size_as_shape(ctx, fn, call.args[1], depth, symbolic)
        if leaf == "dynamic_slice_in_dim" and len(call.args) >= 3:
            return self._size_as_shape(ctx, fn, call.args[2], depth, symbolic)
        if leaf in ("dot", "matmul"):
            v = self._eval(
                ctx, fn, call.args[0] if call.args else call, ARRAY, depth + 1,
                symbolic,
            )
            if isinstance(v, ShapeVal):
                return replace(v, masked=False)
            return v
        if leaf.startswith("segment_"):
            if size_kw is not None:
                return self._size_as_shape(ctx, fn, size_kw.value, depth, symbolic)
            return UNKNOWN_SHAPE
        if leaf in _ELEMENTWISE:
            src = (
                call.func.value
                if isinstance(call.func, ast.Attribute)
                and not dotted_name(call.func).startswith(_DEVICE_PREFIXES)
                else (call.args[0] if call.args else None)
            )
            if src is None:
                return UNKNOWN_SHAPE
            return self._eval(ctx, fn, src, ARRAY, depth + 1, symbolic)
        return None

    def _size_as_shape(self, ctx, fn, size_expr, depth, symbolic):
        """A materialize whose leading dim IS a size expression: the
        array-facet result takes the size facet's class."""
        v = self._eval(ctx, fn, size_expr, SIZE, depth + 1, symbolic)
        return v

    def _passthrough_at_site(
        self, ctx, fn, call, tgt, summary, facet, depth, symbolic
    ):
        _tag, param_names, masked_through = summary
        names = tgt.ctx.param_names(tgt.node)
        if names and names[0] == "self":
            names = names[1:]
        vals = []
        for i, arg in enumerate(call.args):
            if i < len(names) and names[i] in param_names:
                vals.append(self._eval(ctx, fn, arg, facet, depth + 1, symbolic))
        for kw in call.keywords:
            if kw.arg in param_names:
                vals.append(self._eval(ctx, fn, kw.value, facet, depth + 1, symbolic))
        out = self._combine(vals) if vals else UNKNOWN_SHAPE
        if masked_through and isinstance(out, ShapeVal):
            out = replace(out, masked=True)
        return out


# ---------------------------------------------------------------------------
# the process-wide summary cache
# ---------------------------------------------------------------------------

# [(contexts tuple, ShapeAnalysis)] — identity-keyed: the runner's parse
# cache hands back the SAME FileContext objects for unchanged files, so
# repeated engine runs in one process (the test suite runs the analyzer
# dozens of times) solve the fixpoint once. Strong refs, tiny LRU.
_SUMMARY_CACHE: List[Tuple[Tuple[FileContext, ...], ShapeAnalysis]] = []
_SUMMARY_CACHE_MAX = 4


def analysis_for(project) -> Tuple[ShapeAnalysis, bool]:
    """The ShapeAnalysis for a ProjectContext, cached by the identity of
    the analyzed file set (every context, not just the in-scope ones —
    resolution can cross the scope boundary). Returns ``(analysis,
    cache_hit)`` so the runner can report per-run cache traffic."""
    key = tuple(project.contexts)
    for i, (ctxs, ana) in enumerate(_SUMMARY_CACHE):
        if len(ctxs) == len(key) and all(a is b for a, b in zip(ctxs, key)):
            if i != 0:
                _SUMMARY_CACHE.insert(0, _SUMMARY_CACHE.pop(i))
            return ana, True
    ana = ShapeAnalysis(project.callgraph)
    _SUMMARY_CACHE.insert(0, (key, ana))
    del _SUMMARY_CACHE[_SUMMARY_CACHE_MAX:]
    return ana, False


# ---------------------------------------------------------------------------
# facts export: the cost-model feedstock
# ---------------------------------------------------------------------------


def _bucket_bound(v: ShapeVal) -> Optional[int]:
    if v.kind == STATIC_KIND:
        return 1
    if v.kind == BUCKETED_KIND:
        return BUCKET_BOUNDS.get(v.lattice or "derived", BUCKET_BOUNDS["derived"])
    return None  # data-dependent: unbounded; unknown: no claim


def collect_facts(project) -> Dict[str, object]:
    """Everything the interpreter statically knows, as one JSON-stable
    artifact: the lattice definition, the per-operator padded-shape
    transfer catalog, and every classified size site (sized materializes
    and static args of jitted primitives) with its abstract class and
    bucket-signature bound."""
    shapes = project.shapes
    graph = project.callgraph
    sites: List[Dict[str, object]] = []
    for ctx in project.contexts:
        if not in_scope(ctx.relpath):
            continue
        for call in ctx.calls:
            fn = ctx.enclosing_function(call)
            name = dotted_name(call.func)
            args: List[Dict[str, object]] = []
            for kw in call.keywords:
                if kw.arg in SIZE_KWARGS:
                    v = shapes.classify_size(ctx, fn, kw.value)
                    args.append(
                        {"name": kw.arg, "shape": v.render(),
                         "bucket_bound": _bucket_bound(v)}
                    )
            for tgt in graph.resolve_call(ctx, call):
                statics = jit_static_argnames(tgt.node)
                if not statics:
                    continue
                names = tgt.ctx.param_names(tgt.node)
                if names and names[0] == "self":
                    names = names[1:]
                for i, a in enumerate(call.args):
                    if i < len(names) and names[i] in statics:
                        v = shapes.classify_size(ctx, fn, a)
                        args.append(
                            {"name": names[i], "shape": v.render(),
                             "bucket_bound": _bucket_bound(v)}
                        )
                for kw in call.keywords:
                    if kw.arg in statics and kw.arg not in SIZE_KWARGS:
                        v = shapes.classify_size(ctx, fn, kw.value)
                        args.append(
                            {"name": kw.arg, "shape": v.render(),
                             "bucket_bound": _bucket_bound(v)}
                        )
            if not args:
                continue
            bounds = [a["bucket_bound"] for a in args]
            verdict = (
                "unbounded"
                if any(a["shape"].startswith("data") for a in args)
                else ("bounded" if all(b is not None for b in bounds) else "unknown")
            )
            sites.append(
                {
                    "path": ctx.relpath,
                    "line": getattr(call, "lineno", 0),
                    "op": name or "<call>",
                    "args": args,
                    "verdict": verdict,
                }
            )
    sites.sort(key=lambda s: (s["path"], s["line"], s["op"]))
    data_sites = sum(1 for s in sites if s["verdict"] == "unbounded")
    bucketed_sites = sum(
        1
        for s in sites
        if any(str(a["shape"]).startswith("bucketed") for a in s["args"])
    )
    return {
        "schema_version": FACTS_SCHEMA_VERSION,
        "lattice": {
            "floor": BUCKET_FLOOR,
            "modes": {
                "off": "n (identity)",
                "pow2": "1 << ceil(log2(max(n, floor))) for n > 0; 0 stays 0",
                "1.25": "first rung >= n of [floor, max(prev+1, "
                        "int(prev*1.25)), ...]; 0 stays 0",
            },
            "bounds": dict(BUCKET_BOUNDS),
        },
        "operators": [dict(f) for f in OPERATOR_FORMULAS],
        "sites": sites,
        "summary": {
            "facts_emitted": len(OPERATOR_FORMULAS) + len(sites),
            "data_dependent_sites": data_sites,
            "bucketed_sites": bucketed_sites,
        },
    }


def engine_shape_summary() -> Dict[str, object]:
    """The facts summary over the installed engine. Never raises — a
    crash reports itself in the summary."""
    try:
        from .runner import ENGINE_ROOT, run_paths

        report = run_paths([ENGINE_ROOT], rules=[])
        facts = collect_facts(report.project)
        return dict(facts["summary"])
    except Exception as exc:  # fault-ok: a facts crash reports itself in the summary
        return {
            "facts_emitted": 0,
            "data_dependent_sites": -1,
            "bucketed_sites": -1,
            "error": str(exc)[:200],
        }
