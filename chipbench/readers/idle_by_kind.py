"""The device's idle seconds in the traced slice by the KIND of the
program's span that was open over them: ``idle_by_span``'s attribution
(its clock, its rule — each gap cut at span boundaries, each piece to the
deepest span open over it — and its slice), labelled by what the host was
doing there rather than where in the tree: ``operator`` (an operator's own
time: host work no span names), ``dispatch`` (inside one call of a jitted
program), ``step`` (a named host-only block), ``sync`` (a blocking read),
``kernel``, ``build``, ``mesh``, ``serve``, ``phase``, ``query``; a piece
under no span is ``unattributed``. Over all labels the seconds add up to
``idle_by_span``'s total, exactly.

``read(w, kinds)`` gives the idle seconds per traced pass whose label is
one of ``kinds`` (0.0 where the program opens no span of those kinds: a
program from before them). Where ``idle_by_span`` has nothing to read — no
trace, a program without the log, a slice whose requests have left it —
this gives ``None`` too, never 0. Only the flattening is this file's own:
it has to carry the span's kind.
"""

import trace_reduce
from client import load_module

idle_by_span = load_module("readers", "idle_by_span")


def flatten(tree, offset):
    """The spans of one rendered tree as (lo, hi, depth, kind, kind) on the
    trace's clock: ``idle_by_span.attribute`` labels by the last two."""
    origin = tree["start_perf_s"] + offset
    out = []

    def walk(node, depth):
        if "clock" in node:  # rendered in another process: not this clock
            return
        lo = origin + node["start_s"]
        out.append((lo, lo + node["seconds"], depth, node["kind"],
                    node["kind"]))
        for child in node.get("children", ()):
            walk(child, depth + 1)

    walk(tree["root"], 0)
    return out


def table(w):
    """{kind: idle seconds in the slice} and the passes they span, made
    once and kept on the window."""
    if hasattr(w, "_idle_by_kind"):
        return w._idle_by_kind
    w._idle_by_kind = None
    by_span = idle_by_span.table(w)
    if by_span is None:
        return None
    from tpu_cypher.obs import trace as program_trace

    offset = by_span["offset"]
    lo, hi = w.trace.slice
    spans = [
        s for t in program_trace.recent()
        if t["start_perf_s"] + offset < hi
        and t["start_perf_s"] + offset + t["root"]["seconds"] > lo
        for s in flatten(t, offset)
    ]
    gaps = trace_reduce.gaps(w.trace.busy[0], lo, hi)
    by_kind, _ = idle_by_span.attribute(gaps, spans)
    print("idle by kind: " + "; ".join(
        f"{kind} {seconds:.6f}"
        for kind, seconds in sorted(by_kind.items(), key=lambda kv: -kv[1])
    ) + f" (s of {sum(by_kind.values()):.6f} idle in "
        f"{by_span['passes']:g} pass(es))", flush=True)
    w._idle_by_kind = {"by_kind": by_kind, "passes": by_span["passes"]}
    return w._idle_by_kind


def read(w, kinds):
    found = table(w)
    if found is None or not sum(found["by_kind"].values()):
        return None
    return sum(found["by_kind"].get(k, 0.0) for k in kinds) / found["passes"]
