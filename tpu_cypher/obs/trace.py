"""Per-query trace spans: a context-local span tree from frontend to kernels.

A ``QueryTrace`` is opened per query by ``relational/session.py`` and nested
per pipeline phase (parse -> ir -> logical -> relational -> execute) and per
relational operator (``relational/ops.py`` wraps every lazy ``table`` pull in
an operator span). Inside operators, kernel dispatches
(``backend/tpu/pallas/dispatch.py``) open kernel spans, the bucket lattice
(``backend/tpu/bucketing.round_size``) annotates the enclosing span with
padded-vs-true row counts, and every named fault site
(``runtime/faults.fault_point``) — the engine's natural device sync points —
stamps a site hit. The finished tree attaches to ``CypherResult`` as
``result.profile()`` (rendered tree + JSON): the ``PROFILE``-style sibling
of the ``EXPLAIN``-style ``result.plans``.

Costs, by design:

* spans record HOST wall time only (``perf_counter``) — never a device sync
  (``block_until_ready``), so profiling adds ZERO device syncs and an
  operator span measures dispatch time under JAX async dispatch (the
  ``collect`` span at the end absorbs the drain, like Spark UI's stage
  boundaries absorb action time);
* when no trace is active every instrumentation point is one contextvar
  read returning a shared null span;
* every live span also opens a ``jax.profiler.TraceAnnotation``
  (``tpu_cypher:<kind>:<name>``): a no-op while no profiler session runs,
  and inside ANY capture — the operator's own ``jax.profiler.start_trace``,
  TensorBoard, ``chipbench --keep-trace`` — the same tree shows up
  region-named beside the device's operations.

The clock: a ``QueryTrace`` stamps its root when it is made
(``perf_counter`` and ``time_ns``), every span keeps its start and end on
that ``perf_counter``, and ``to_dict()`` gives each span ``start_s``, its
offset from the root's start. A served request is ONE tree
(``serve/server.py``): root ``request``, the serving stages as closed
spans of kind ``serve``, the engine's own tree grafted under ``dispatch``
as ``engine``. Finished request trees go to a bounded in-process log
(``finish`` / ``recent``) that the benchmark's readers read; a tree holds
numbers and strings only.

Context-locality: the active trace/span ride ``contextvars``, so
interleaved queries (threads, asyncio, nested view execution) each grow
their own tree — the same isolation discipline as the metrics scopes and
the execution guard.
"""

from __future__ import annotations

import collections
import contextvars
import itertools
import json
import time
from typing import Any, Deque, Dict, List, Optional

from . import metrics as M

SCHEMA_VERSION = 2

# finished request trees kept by ``finish`` for ``recent`` (about 4 KB each)
RECENT_CAPACITY = 4096

HOST_SYNCS = M.REGISTRY.counter(
    "tpu_cypher_host_syncs_total",
    "blocking device->host reads, by the enclosing fault site",
    labels=("site",),
)

COUNT_CHAIN_HOPS = M.REGISTRY.counter(
    "tpu_cypher_count_chain_hops_total",
    "hops of fused count chains by the form taken: degree (row_ptr "
    "differences, no edge read), reduce (one gather and one sum over the "
    "edges), scan (gather, prefix scan, boundary reads)",
    labels=("form",),
)

SEGMENT_REDUCE = M.REGISTRY.counter(
    "tpu_cypher_segment_reduce_total",
    "aggregators dispatched on the device by the form of their segment "
    "reduction: dense (compare-and-reduce over a small number of groups, "
    "no scatter) or scatter (jax.ops.segment_*)",
    labels=("form",),
)
for _form in ("dense", "scatter"):  # both series export from the start
    SEGMENT_REDUCE.inc(0, form=_form)

ORDER_LIMIT = M.REGISTRY.counter(
    "tpu_cypher_order_limit_total",
    "LIMIT directly over ORDER BY, by what was gathered: topk (k rows, "
    "found by one top-k over the keys packed into 62 bits), sort_prefix (k "
    "rows, at the first k entries of the stable sort's permutation) or "
    "full (the whole table sorted and gathered, then sliced: the backend "
    "has no prefix form or declined, or a CSE sibling had built the sorted "
    "table)",
    labels=("path",),
)
for _path in ("topk", "sort_prefix", "full"):  # a sound deployment: 0 full
    ORDER_LIMIT.inc(0, path=_path)

PROGRAM_DISPATCHES = M.REGISTRY.counter(
    "tpu_cypher_program_dispatches_total",
    "calls of a jitted program from host code (obs.trace.dispatch), named "
    "as the device trace names the program; a program called while another "
    "is being traced is part of that one and is not counted",
    labels=("program",),
)


class Span:
    """One node of the tree: a named, timed region with attributes."""

    __slots__ = ("span_id", "name", "kind", "attrs", "t0", "t1", "seconds",
                 "status", "children", "remote")

    def __init__(self, span_id: int, name: str, kind: str,
                 attrs: Optional[Dict[str, Any]] = None):
        self.span_id = span_id
        self.name = name
        # "query" | "phase" | "operator" | "kernel" | "serve" | "sync" |
        # "build" | "dispatch" | "step" | "span"
        self.kind = kind
        self.attrs: Dict[str, Any] = dict(attrs or {})
        # start and end on the process's perf_counter (None: never timed)
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None
        self.seconds: float = 0.0
        self.status = "ok"
        self.children: List["Span"] = []
        # a tree rendered in ANOTHER process (cluster mode: the worker's
        # profile), emitted as a last child with its own offsets
        self.remote: Optional[Dict[str, Any]] = None

    @property
    def self_seconds(self) -> float:
        """Wall time minus child spans — the per-operator cost that sums
        (within tolerance) to the parent's total."""
        return max(self.seconds - sum(c.seconds for c in self.children), 0.0)

    def close(self, t1: Optional[float] = None) -> None:
        """Stamp the end (now, unless measured by the caller)."""
        self.t1 = time.perf_counter() if t1 is None else t1
        self.seconds = max(self.t1 - self.t0, 0.0)

    def add(self, name: str, kind: str, t0: float, t1: Optional[float] = None,
            **attrs) -> "Span":
        """Append a child whose ends the caller measured itself (the
        serving stages: their code runs across ``await``s and threads, where
        no ``with`` block can stand). ``t1=None`` leaves it open for a later
        ``close``. Span ids are per tree; such children take none (0)."""
        sp = Span(0, name, kind, attrs)
        sp.t0 = t0
        if t1 is not None:
            sp.close(t1)
        self.children.append(sp)
        return sp

    def absorb(self, t0: float, t1: float) -> None:
        """Fold a LATER interval into this closed span: its seconds add up,
        its end moves, attr ``pages`` counts what it holds. How a result of
        many pages leaves a tree of bounded size (``seconds`` is then a
        sum, not the span's extent)."""
        self.attrs["pages"] = self.attrs.get("pages", 1) + 1
        self.seconds += max(t1 - t0, 0.0)
        self.t1 = t1

    def note(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def count(self, key: str, amount: int = 1) -> None:
        self.attrs[key] = self.attrs.get(key, 0) + amount

    # per-span cap on retained (true, padded) pairs: enough for every
    # rounding a single operator performs, bounded against pathological
    # loops so a span never grows without limit
    ROWS_PAIRS_CAP = 64

    def add_rows(
        self,
        true_rows: int,
        padded_rows: int,
        shards: int = 1,
        local_true: Optional[int] = None,
        local_padded: Optional[int] = None,
    ) -> None:
        """Accumulate a padded-vs-true row count from the bucket lattice.

        Besides the running sums, the individual ``(true, padded)`` pairs
        are retained (bounded) so static shape predictions
        (``analysis.shapes.predict_padded``) can be checked against what
        the lattice actually produced, per rounding, not just in
        aggregate. Under a mesh the lattice rounds per shard: the span
        additionally records the shard count and the per-shard
        ``(local true extent, local padded)`` pairs, the sharded analog
        of the same static-vs-runtime agreement gate."""
        self.attrs["rows_true"] = self.attrs.get("rows_true", 0) + int(true_rows)
        self.attrs["rows_padded"] = (
            self.attrs.get("rows_padded", 0) + int(padded_rows)
        )
        pairs = self.attrs.setdefault("rows_pairs", [])
        if len(pairs) < self.ROWS_PAIRS_CAP:
            pairs.append([int(true_rows), int(padded_rows)])
        if shards > 1 and local_true is not None and local_padded is not None:
            self.attrs["shards"] = int(shards)
            spairs = self.attrs.setdefault("shard_rows_pairs", [])
            if len(spairs) < self.ROWS_PAIRS_CAP:
                spairs.append([int(local_true), int(local_padded)])

    def to_dict(self, origin: Optional[float] = None) -> Dict[str, Any]:
        """JSON form; ``start_s`` is the offset from ``origin`` (the root's
        start; this span's own where none is given)."""
        if origin is None:
            origin = self.t0
        out: Dict[str, Any] = {
            "span_id": self.span_id,
            "name": self.name,
            "kind": self.kind,
            "start_s": (
                round(self.t0 - origin, 6) if self.t0 is not None else 0.0
            ),
            "seconds": round(self.seconds, 6),
            "self_seconds": round(self.self_seconds, 6),
            "status": self.status,
        }
        if self.attrs:
            out["attrs"] = dict(self.attrs)
        children = [c.to_dict(origin) for c in self.children]
        if self.remote is not None:
            children.append(self.remote)
        if children:
            out["children"] = children
        return out


class _NullSpan:
    """The no-trace fast path: every mutator is a no-op."""

    __slots__ = ()

    def note(self, key, value):  # noqa: D401
        pass

    def count(self, key, amount=1):
        pass

    def add_rows(self, true_rows, padded_rows, shards=1, local_true=None,
                 local_padded=None):
        pass


NULL_SPAN = _NullSpan()


class QueryTrace:
    """The span tree for ONE query: a root plus per-phase children. The
    root's duration is the SUM of its phase durations (a lazy result may
    sit unpulled for minutes between planning and execution — idle wall
    time between phases is not query time)."""

    def __init__(self, name: str = "query", kind: str = "query", **attrs):
        self._ids = itertools.count(1)
        self.root = Span(0, name, kind, attrs)
        # the tree's clock: every span's t0/t1 is on this perf_counter;
        # unix_ns places the root on the wall clock
        self.root.t0 = time.perf_counter()
        self.unix_ns = time.time_ns()
        # deepest span open when the current execution attempt failed —
        # reset per ladder attempt, read into ``execution_log`` entries
        self.failed_span_id: Optional[int] = None

    # -- aggregate views ---------------------------------------------------

    @property
    def total_seconds(self) -> float:
        return sum(c.seconds for c in self.root.children)

    def phase_seconds(self) -> Dict[str, float]:
        """{phase name: summed seconds} over the root's direct children
        (retried phases, e.g. ladder execute attempts, sum)."""
        out: Dict[str, float] = {}
        for c in self.root.children:
            out[c.name] = out.get(c.name, 0.0) + c.seconds
        return out

    def spans(self) -> List[Span]:
        """Every span, preorder."""
        out: List[Span] = []
        stack = [self.root]
        while stack:
            s = stack.pop()
            out.append(s)
            stack.extend(reversed(s.children))
        return out

    def close(self) -> None:
        """Stamp the root's end. A root never closed (a lazy result)
        renders with the extent of its children instead."""
        self.root.close()

    def to_dict(self) -> Dict[str, Any]:
        root = self.root
        if root.t1 is None:
            ends = [c.t1 for c in root.children if c.t1 is not None]
            if ends:
                root.seconds = max(max(ends) - root.t0, 0.0)
        return {
            "schema_version": SCHEMA_VERSION,
            "total_seconds": round(self.total_seconds, 6),
            "start_perf_s": root.t0,
            "start_unix_ns": self.unix_ns,
            "root": root.to_dict(),
        }


# the active trace + innermost open span in THIS context
_TRACE: contextvars.ContextVar[Optional[QueryTrace]] = contextvars.ContextVar(
    "tpu_cypher_trace", default=None
)
_SPAN: contextvars.ContextVar[Optional[Span]] = contextvars.ContextVar(
    "tpu_cypher_span", default=None
)


def current_trace() -> Optional[QueryTrace]:
    return _TRACE.get()


def current_span() -> Optional[Span]:
    return _SPAN.get()


def enabled() -> bool:
    return _TRACE.get() is not None


def note(key: str, value: Any) -> None:
    sp = _SPAN.get()
    if sp is not None:
        sp.attrs[key] = value


def note_rows(
    true_rows: int,
    padded_rows: int,
    shards: int = 1,
    local_true: Optional[int] = None,
    local_padded: Optional[int] = None,
) -> None:
    """Record a bucket-lattice materialize on the innermost open span
    (plus the per-shard extent pair while a mesh is active)."""
    sp = _SPAN.get()
    if sp is not None:
        sp.add_rows(
            true_rows, padded_rows,
            shards=shards, local_true=local_true, local_padded=local_padded,
        )


def note_agg_form(form: str) -> None:
    """Count one aggregator dispatched on the device by the form of its
    segment reduction (``jit_ops.segment_aggregate_form``): in the registry,
    and as ``agg_form`` (form -> aggregators) on the innermost open span."""
    SEGMENT_REDUCE.inc(form=form)
    sp = _SPAN.get()
    if sp is not None:
        forms = sp.attrs.setdefault("agg_form", {})
        forms[form] = forms.get(form, 0) + 1


def note_order_limit(path: str) -> None:
    """Count one LIMIT over ORDER BY by what it gathered: in the registry,
    and as ``order_limit`` on the innermost open span."""
    ORDER_LIMIT.inc(path=path)
    note("order_limit", path)


def note_site(site: str) -> None:
    """Stamp a fault-site hit (a device sync point) on the innermost open
    span: ``attrs["sites"]`` maps site name -> hit count."""
    sp = _SPAN.get()
    if sp is not None:
        sites = sp.attrs.setdefault("sites", {})
        sites[site] = sites.get(site, 0) + 1


class activate:
    """``with activate(trace):`` — make ``trace`` the context's active
    trace, its root the innermost span. Used once per pipeline run AND
    re-entered by the lazy execution ladder / ``collect`` (a CypherResult
    is planned now, pulled later, possibly from another context)."""

    def __init__(self, trace: QueryTrace):
        self._trace = trace
        self._t1 = None
        self._t2 = None

    def __enter__(self) -> QueryTrace:
        self._t1 = _TRACE.set(self._trace)
        self._t2 = _SPAN.set(self._trace.root)
        return self._trace

    def __exit__(self, *exc) -> None:
        _SPAN.reset(self._t2)
        _TRACE.reset(self._t1)


_ANNOTATION = None  # jax.profiler.TraceAnnotation, or False: unavailable


def _annotation(name: str):
    """An entered ``TraceAnnotation`` (None where JAX has none). While no
    profiler session runs it records nothing."""
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            import jax

            _ANNOTATION = jax.profiler.TraceAnnotation
        except Exception:  # fault-ok: profiling must never fail a query
            _ANNOTATION = False
    if not _ANNOTATION:
        return None
    try:
        dev = _ANNOTATION(name)
        dev.__enter__()
        return dev
    except Exception:  # fault-ok: profiling must never fail a query
        return None


class span:
    """``with span(name, kind=..., **attrs) as sp:`` — open a child of the
    innermost span. Returns ``NULL_SPAN`` (and records nothing) when no
    trace is active, so instrumentation points cost one contextvar read
    on the untraced path."""

    __slots__ = ("_name", "_kind", "_attrs", "_span", "_tok", "_dev")

    def __init__(self, name: str, kind: str = "span", **attrs):
        self._name = name
        self._kind = kind
        self._attrs = attrs
        self._span: Optional[Span] = None
        self._tok = None
        self._dev = None

    def __enter__(self):
        tr = _TRACE.get()
        if tr is None:
            return NULL_SPAN
        parent = _SPAN.get() or tr.root
        sp = Span(next(tr._ids), self._name, self._kind, self._attrs)
        parent.children.append(sp)
        self._tok = _SPAN.set(sp)
        # the same region, named inside whatever jax.profiler capture is
        # running (the operator's own included)
        self._dev = _annotation(f"tpu_cypher:{self._kind}:{self._name}")
        sp.t0 = time.perf_counter()
        self._span = sp
        return sp

    def __exit__(self, exc_type, exc, tb) -> bool:
        sp = self._span
        if sp is None:
            return False
        sp.close()
        if self._dev is not None:
            try:
                self._dev.__exit__(exc_type, exc, tb)
            except Exception:  # pragma: no cover - fault-ok: best-effort profiler teardown
                pass
        _SPAN.reset(self._tok)
        if exc_type is not None:
            sp.status = "error"
            tr = _TRACE.get()
            # exits unwind deepest-first: the FIRST error exit is the
            # failing operator the execution_log entry should name
            if tr is not None and tr.failed_span_id is None:
                tr.failed_span_id = sp.span_id
        if self._kind == "phase":
            M.record_stage(self._name, sp.seconds)
        return False


def sync(site: str) -> span:
    """``with sync(site): n = int(device_scalar)`` — the span (kind
    ``sync``) round ONE blocking device->host read, named for the enclosing
    fault site. It wraps the read that blocks, not the dispatch before it,
    and reads nothing itself. The count survives outside a traced run in
    ``tpu_cypher_host_syncs_total{site=}``."""
    HOST_SYNCS.inc(site=site)
    return span(site, kind="sync")


def _leaf(parent: Span, name: str, t0: float, t1: float) -> Span:
    """A closed ``dispatch`` child of ``parent`` (``Span.add`` without the
    attrs: this runs once per program call)."""
    sp = Span(0, name, "dispatch")
    sp.t0, sp.t1, sp.seconds = t0, t1, max(t1 - t0, 0.0)
    parent.children.append(sp)
    return sp


class dispatch:
    """``with dispatch("jit_cols_take"): out = program(...)`` — the leaf
    (kind ``dispatch``) round exactly ONE call of a jitted program from
    host code: the call, not the host work before it, and never a blocking
    read. ``program`` is the name the device trace gives the program, so a
    leaf reads ``.../LimitOp/jit_order_permutation``. The sibling of
    ``sync``: the count survives outside a traced run in
    ``tpu_cypher_program_dispatches_total{program=}``.

    A closed child of the innermost open span, never the innermost span
    itself: notes (``note``, ``note_rows``, ``note_site``, ...) keep
    landing on the operator. No contextvar is set, and no
    ``TraceAnnotation`` entered — inside a capture the program's launch
    and its device event already stand there. A program made through
    ``program`` / ``wrap_programs`` needs no ``with`` at its call sites."""

    __slots__ = ("_name", "_parent", "_t0")

    def __init__(self, program: str):
        self._name = program

    def __enter__(self) -> None:
        PROGRAM_DISPATCHES.inc(program=self._name)
        self._parent = _SPAN.get()
        self._t0 = time.perf_counter()

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._parent is not None:
            leaf = _leaf(
                self._parent, self._name, self._t0, time.perf_counter()
            )
            if exc_type is not None:
                leaf.status = "error"
        return False


_TRACE_STATE_CLEAN = None  # jax's "no trace is being built on this thread"


def inside_jax_trace() -> bool:
    """Is JAX building a trace on this thread (a jaxpr of some program)?"""
    global _TRACE_STATE_CLEAN
    if _TRACE_STATE_CLEAN is None:
        from jax._src.core import trace_state_clean

        _TRACE_STATE_CLEAN = trace_state_clean
    return not _TRACE_STATE_CLEAN()


class Program:
    """A jitted program whose every call from host code is a ``dispatch``
    leaf (``__call__`` is ``dispatch`` written out: it runs once per
    program call of every request). Made once, where the program is made
    (``program``, ``wrap_programs``); everything but the call (``lower``,
    ``_cache_size``, ``clear_cache``, ...) is the ``jax.jit`` object's own.
    Called while JAX traces another program it is part of that program,
    not a dispatch, and steps aside."""

    def __init__(self, fn):
        self.__wrapped__ = fn
        self.__name__ = getattr(fn, "__name__", type(fn).__name__)
        self.__qualname__ = getattr(fn, "__qualname__", self.__name__)
        self.__doc__ = getattr(fn, "__doc__", None)
        self.__module__ = getattr(fn, "__module__", None)
        # as the device trace names it: jit_<function>
        self.program = "jit_" + self.__name__
        self._key = None  # the counter's series, resolved at the first call
        inside_jax_trace()  # binds jax's trace state before the first call

    def __call__(self, *args, **kwargs):
        if not _TRACE_STATE_CLEAN():
            return self.__wrapped__(*args, **kwargs)
        key = self._key
        if key is None:
            key = self._key = PROGRAM_DISPATCHES.key(program=self.program)
        PROGRAM_DISPATCHES.inc_key(key)
        parent = _SPAN.get()
        if parent is None:
            return self.__wrapped__(*args, **kwargs)
        status = "error"
        t0 = time.perf_counter()
        try:
            out = self.__wrapped__(*args, **kwargs)
            status = "ok"
            return out
        finally:
            _leaf(parent, self.program, t0, time.perf_counter()).status = status

    def __getattr__(self, name):
        if name == "__wrapped__":  # an instance made without __init__ (copy)
            raise AttributeError(name)
        return getattr(self.__wrapped__, name)

    def __repr__(self) -> str:
        return f"<Program {self.program}>"


def program(fn) -> Program:
    """``fn = program(jax.jit(...))``: see ``Program``. Idempotent."""
    return fn if isinstance(fn, Program) else Program(fn)


def wrap_programs(namespace: Dict[str, Any]) -> None:
    """Wrap every ``jax.jit`` object bound in a module's namespace (call it
    last in the module, with ``globals()``): the decorators stay what the
    static analysis reads, and a program that calls another one while it
    is traced finds a wrapper that steps aside."""
    from jax.stages import Wrapped  # what ``jax.jit`` returns

    for name, value in list(namespace.items()):
        if isinstance(value, Wrapped):
            namespace[name] = program(value)


# ---------------------------------------------------------------------------
# the log of finished request trees
# ---------------------------------------------------------------------------

_RECENT: Deque[QueryTrace] = collections.deque(maxlen=RECENT_CAPACITY)


def finish(trace: QueryTrace) -> QueryTrace:
    """Close a request's tree and keep it in the bounded log. Nothing is
    rendered here — this runs before the request's last message goes out;
    whoever reads the tree (``recent``, ``/queries/<id>``) pays for its
    rendering. A tree holds numbers and strings only, never a table or a
    device buffer."""
    trace.close()
    _RECENT.append(trace)
    return trace


def recent() -> List[Dict[str, Any]]:
    """The last ``RECENT_CAPACITY`` finished request trees, rendered
    (``QueryTrace.to_dict``), newest last."""
    return [trace.to_dict() for trace in list(_RECENT)]


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------

_SKIP_ATTRS = ("sites",)  # rendered separately


def _attr_str(sp: Span) -> str:
    parts = [
        f"{k}={v}" for k, v in sp.attrs.items()
        if k not in _SKIP_ATTRS and not isinstance(v, (dict, list))
    ]
    sites = sp.attrs.get("sites")
    if sites:
        parts.append("sites=" + "+".join(f"{k}:{v}" for k, v in sorted(sites.items())))
    return f"  [{', '.join(parts)}]" if parts else ""


def render(trace: QueryTrace) -> str:
    """ASCII tree with per-span total and self wall times. The dispatch
    leaves of one span print as ONE last line (their number and summed
    time); the JSON form keeps them all."""
    lines = [
        f"{trace.root.name} (total {trace.total_seconds * 1000:.2f} ms)"
        f"{_attr_str(trace.root)}"
    ]

    def walk(sp: Span, prefix: str, last: bool) -> None:
        branch = "`- " if last else "|- "
        mark = " !" if sp.status == "error" else ""
        self_part = (
            f" (self {sp.self_seconds * 1000:.2f} ms)" if sp.children else ""
        )
        lines.append(
            f"{prefix}{branch}{sp.name} {sp.seconds * 1000:.2f} ms"
            f"{self_part}{mark}{_attr_str(sp)}"
        )
        children(sp, prefix + ("   " if last else "|  "))

    def children(sp: Span, prefix: str) -> None:
        shown = [c for c in sp.children if c.kind != "dispatch"]
        leaves = len(sp.children) - len(shown)
        for i, c in enumerate(shown):
            walk(c, prefix, not leaves and i == len(shown) - 1)
        if leaves:
            ms = 1000 * sum(
                c.seconds for c in sp.children if c.kind == "dispatch"
            )
            lines.append(f"{prefix}`- dispatch x{leaves} {ms:.2f} ms")

    children(trace.root, "")
    return "\n".join(lines)


class QueryProfile:
    """What ``CypherResult.profile()`` returns: the rendered tree plus the
    JSON form of the same data."""

    def __init__(self, trace: QueryTrace):
        self.trace = trace

    def render(self) -> str:
        return render(self.trace)

    def to_dict(self) -> Dict[str, Any]:
        return self.trace.to_dict()

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    def phase_seconds(self) -> Dict[str, float]:
        return self.trace.phase_seconds()

    @property
    def total_seconds(self) -> float:
        return self.trace.total_seconds

    def __str__(self) -> str:
        return self.render()

    def __repr__(self) -> str:
        n = len(self.trace.spans()) - 1
        return (
            f"QueryProfile({n} spans, "
            f"total {self.trace.total_seconds * 1000:.2f} ms)"
        )
