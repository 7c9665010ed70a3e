"""The device's idle seconds in the traced slice, put down to the host code
that filled them: each gap between device operations goes to the program's
own span that was open over it (``tpu_cypher.obs.trace.recent()``, the log
of finished request trees: run.py holds the server in its own process).

The two clocks. The trace has its own; the spans are on the process's
``perf_counter``, and so is ``Request.submitted``, which the client reads
one statement after it enters the request's ``q:`` annotation. So for the
requests inside the slice, ``q:`` start − ``submitted`` is the offset
between the clocks: the median is taken, and the largest distance from it
(the residual) is printed.

Each gap of the first device is cut at span boundaries and each piece goes
to the deepest span open over it (where requests overlap: the one that
started last); a piece under no span is ``unattributed``. A piece carries
two labels: ``leaf``, the span's path from the root, and ``phase``, the
name of its nearest ancestor-or-self of kind ``serve`` or ``phase``.

``read(w, phases)`` gives the idle seconds whose phase is one of
``phases``, per traced pass; ``read(w, share="unattributed")`` the
unattributed share of all idle seconds, in per cent. No trace, a program
without the log, or a slice whose requests are no longer in it gives
``None``, never 0. The phase names live in the metric files.
"""

import bisect
import statistics

import trace_reduce

UNATTRIBUTED = "unattributed"
LABEL_KINDS = ("serve", "phase")
TOP = 10


def clock_offset(trace, requests):
    """(offset, residual, the requests matched) with trace clock =
    ``perf_counter`` + offset; None where the slice's requests are no run
    of the window's, shape for shape. Only requests made under the
    profiler's session have a ``q:`` interval, so the slice's are a run of
    the window's in the order sent. Which run: the one whose starts AND
    ends agree best with one offset (a pass repeats its shapes, but never
    its durations to the microsecond)."""
    inside = trace.requests_in_slice()
    sent = sorted((r for r in requests if r.finished is not None),
                  key=lambda r: r.submitted)
    best = None
    for j in range(len(sent) - len(inside) + 1):
        run = sent[j:j + len(inside)]
        if not inside or any(r.shape != q[0] for r, q in zip(run, inside)):
            continue
        starts = [q[1] - r.submitted for r, q in zip(run, inside)]
        ends = [q[2] - r.finished for r, q in zip(run, inside)]
        offset = statistics.median(starts)
        fit = max(abs(d - offset) for d in starts + ends)
        if best is None or fit < best[0]:
            residual = max(abs(d - offset) for d in starts)
            best = (fit, offset, residual, run)
    return None if best is None else best[1:]


def flatten(tree, offset):
    """The spans of one rendered tree as (lo, hi, depth, leaf, phase) on
    the trace's clock."""
    origin = tree["start_perf_s"] + offset
    out = []

    def walk(node, depth, path, phase):
        if "clock" in node:  # rendered in another process: not this clock
            return
        name = node["name"]
        if node["kind"] == "sync":
            name = "sync:" + name
        path = f"{path}/{name}" if path else name
        if node["kind"] in LABEL_KINDS:
            phase = node["name"]
        lo = origin + node["start_s"]
        out.append((lo, lo + node["seconds"], depth, path, phase))
        for child in node.get("children", ()):
            walk(child, depth + 1, path, phase)

    walk(tree["root"], 0, "", tree["root"]["name"])
    return out


def owners(spans):
    """Boundaries b[0] < b[1] < ... and, for each [b[k], b[k+1]), the span
    that owns it: the deepest open there, the latest start among equals."""
    bounds = sorted({t for lo, hi, *_ in spans for t in (lo, hi)})
    owner = [None] * max(len(bounds) - 1, 0)
    for span in spans:
        lo, hi, depth = span[0], span[1], span[2]
        for k in range(bisect.bisect_left(bounds, lo),
                       bisect.bisect_left(bounds, hi)):
            held = owner[k]
            if held is None or (depth, lo) > (held[2], held[0]):
                owner[k] = span
    return bounds, owner


def attribute(gaps, spans):
    """{leaf: seconds}, {phase: seconds} over the gaps' pieces."""
    bounds, owner = owners(spans)
    by_leaf, by_phase = {}, {}

    def put(span, seconds):
        leaf, phase = (span[3], span[4]) if span else (UNATTRIBUTED,) * 2
        by_leaf[leaf] = by_leaf.get(leaf, 0.0) + seconds
        by_phase[phase] = by_phase.get(phase, 0.0) + seconds

    for a, b in gaps:
        at = a
        k = bisect.bisect_right(bounds, a) - 1
        while at < b:
            if k < 0:  # before the first span
                end, span = (min(b, bounds[0]) if bounds else b), None
            elif k >= len(owner):  # after the last
                end, span = b, None
            else:
                end, span = min(b, bounds[k + 1]), owner[k]
            put(span, end - at)
            at, k = end, k + 1
    return by_leaf, by_phase


def table(w):
    """The window's attribution, made once and kept on the window."""
    if hasattr(w, "_idle_by_span"):
        return w._idle_by_span
    w._idle_by_span = None
    try:
        from tpu_cypher.obs import trace as program_trace

        log = program_trace.recent()
    except (ImportError, AttributeError):  # a program without the log
        return None
    if w.trace is None or not log:
        return None
    clock = clock_offset(w.trace, w.requests)
    if clock is None:
        return None
    offset, residual, matched = clock
    trees = {t["root"].get("attrs", {}).get("id"): t for t in log}
    if any(r.qid not in trees for r in matched):
        return None  # the slice's requests have left the log
    lo, hi = w.trace.slice
    spans = [
        s for t in log
        if t["start_perf_s"] + offset < hi
        and t["start_perf_s"] + offset + t["root"]["seconds"] > lo
        for s in flatten(t, offset)
    ]
    gaps = trace_reduce.gaps(w.trace.busy[0], lo, hi)
    by_leaf, by_phase = attribute(gaps, spans)
    per_pass = len(w.requests) / w.passes if w.passes else None
    passes = len(matched) / per_pass if per_pass else 1.0
    top = sorted(by_leaf.items(), key=lambda kv: -kv[1])[:TOP]
    print("idle by span: " + "; ".join(
        f"{leaf} {seconds:.6f}" for leaf, seconds in top
    ) + f" (s of {sum(by_leaf.values()):.6f} idle in {passes:g} pass(es); "
        f"clock residual {residual * 1e3:.4f} ms)", flush=True)
    w._idle_by_span = {"by_leaf": by_leaf, "by_phase": by_phase,
                       "passes": passes, "offset": offset,
                       "residual": residual}
    return w._idle_by_span


def read(w, phases=None, share=None):
    found = table(w)
    if found is None:
        return None
    by_phase = found["by_phase"]
    total = sum(by_phase.values())
    if not total:
        return None
    if share is not None:
        return 100.0 * by_phase.get(share, 0.0) / total
    return sum(by_phase.get(p, 0.0) for p in phases) / found["passes"]
