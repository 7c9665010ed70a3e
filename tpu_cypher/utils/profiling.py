"""Device-level observability: jax.profiler traces and compiled-HLO dumps.

The reference delegates engine-level profiling to Spark UI /
``tableEnv.explain`` (used in ``flink-cypher/.../Demo.scala:84``); the TPU
equivalents are the XLA profiler (TensorBoard-compatible traces) and the
compiled HLO of the jitted kernels. ``TPU_CYPHER_PROFILE_DIR`` has one
use: when set, every ``CypherSession.cypher`` execution is wrapped in a
profiler capture of its own (``relational/session.py``). The ``obs.trace``
spans need no knob: each opens a ``jax.profiler.TraceAnnotation`` whatever
capture is running, this one or the operator's own
(``docs/observability.md``).
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, Callable, Optional

from .config import PROFILE_DIR


@contextlib.contextmanager
def profile_trace(log_dir: Optional[str] = None):
    """Wrap a block in a ``jax.profiler`` trace (viewable in TensorBoard /
    Perfetto). No-op when no directory is configured or the profiler is
    unavailable."""
    d = log_dir or PROFILE_DIR.get()
    if not d:
        yield
        return
    try:
        import jax

        jax.profiler.start_trace(d)
    except Exception:  # pragma: no cover - fault-ok: profiler start is best-effort (no jax, double-start)
        yield
        return
    try:
        yield
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception:  # pragma: no cover - fault-ok: best-effort profiler stop
            pass


def lowered_hlo(fn: Callable, *args: Any, **kw: Any) -> str:
    """StableHLO text for a jittable function on example args — the per-node
    plan introspection analog of the reference's ``tableEnv.explain``."""
    import jax

    # tpulint: allow[recompile-hazard] reason=one-shot plan introspection, not on the query path
    return jax.jit(fn).lower(*args, **kw).as_text()


def compiled_hlo(fn: Callable, *args: Any, **kw: Any) -> str:
    """Post-XLA-optimization HLO (what actually runs on the device)."""
    import jax

    # tpulint: allow[recompile-hazard] reason=one-shot HLO dump for diagnostics, not on the query path
    compiled = jax.jit(fn).lower(*args, **kw).compile()
    return "\n".join(m.to_string() for m in compiled.runtime_executable().hlo_modules())


def annotate(name: str):
    """Named profiler span for region attribution inside traces."""
    import jax

    return jax.profiler.TraceAnnotation(name)
