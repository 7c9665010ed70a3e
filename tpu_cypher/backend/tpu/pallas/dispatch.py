"""Kernel dispatch: ONE gate between the engine and every Pallas program.

* **mode** — ``TPU_CYPHER_PALLAS=auto|interpret|off``:
  ``auto`` (default) compiles kernels on a TPU backend and answers from
  the jnp formulation elsewhere; ``interpret`` runs the IDENTICAL Pallas
  programs through the interpreter on any backend (tier-1/CPU parity —
  the differential tests pin them bit-identical to the jnp oracle);
  ``off`` restores the pre-kernel execution path exactly.
* **registry** — every kernel registers (name, fault site, the names of
  the functions that contain its raw ``pl.pallas_call``). The AST guard
  test walks ``backend/tpu`` and fails on any ``pallas_call`` outside a
  registered impl — no kernel can bypass eligibility.
* **no hidden fallback** — a kernel that is selected either runs or
  raises: a lowering refusal on the compiled path surfaces as a typed
  ``CompileFailure`` (the session ladder handles it in the open, and
  ``tests/test_chip_compile.py`` keeps every kernel compiling for the
  chip at its eligibility cap). Only a data-dependent DECLINE
  (``pallas_fn`` returns ``None``) hands the call to the jnp formulation.
* **fault sites** — each launch passes through ``fault_point(site)``, so
  ``TPU_CYPHER_FAULTS=oom@kernel_agg:1`` etc. drive the degrade ladder
  through the kernel tier with no TPU attached.
* **use counters** — per-kernel pallas/fallback counts served by the
  unified obs registry (``tpu_cypher_pallas_launch_total``), and each
  launch opens a ``kernel:<name>`` trace span carrying the tier it
  resolved to.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

from ....obs import trace as _obs_trace
from ....obs.metrics import REGISTRY as _REGISTRY
from ....utils.config import PALLAS_MODE as MODE

# auto      — compiled kernels on a TPU backend, jnp fallback elsewhere
# interpret — interpreted kernels on ANY backend (tests/CPU parity)
# off       — kernels disabled entirely (today's exact execution path)
# (declared in utils/config.py as TPU_CYPHER_PALLAS)

_VALID_MODES = ("auto", "interpret", "off")


def mode() -> str:
    m = MODE.get().strip().lower()
    return m if m in _VALID_MODES else "auto"


@dataclass(frozen=True)
class KernelSpec:
    """One registered kernel: its fault site and the functions holding its
    raw ``pl.pallas_call`` (the AST guard's allowlist)."""

    name: str
    site: str
    impls: Tuple[str, ...]


_KERNELS: Dict[str, KernelSpec] = {}

# per-kernel launch counts, served by the unified obs registry
# (docs/observability.md): tier="pallas" is a real kernel launch,
# tier="fallback" is the jnp formulation answering instead
PALLAS_LAUNCH = _REGISTRY.counter(
    "tpu_cypher_pallas_launch_total",
    "kernel dispatch outcomes per (kernel, tier=pallas|fallback)",
    labels=("kernel", "tier"),
)


def register(name: str, site: str, impls: Tuple[str, ...]) -> None:
    _KERNELS[name] = KernelSpec(name, site, tuple(impls))
    # pre-seed both tiers at zero so use_counts()/Prometheus export show
    # every registered kernel explicitly
    PALLAS_LAUNCH.inc(0, kernel=name, tier="pallas")
    PALLAS_LAUNCH.inc(0, kernel=name, tier="fallback")


def registry() -> Dict[str, KernelSpec]:
    return dict(_KERNELS)


def reset(name: Optional[str] = None) -> None:
    """Zero the launch counters (tests); ``name`` limits the reset to one
    kernel's series."""
    if name is None:
        PALLAS_LAUNCH.reset()
    else:
        PALLAS_LAUNCH.reset(kernel=name)


def use_counts() -> Dict[str, Dict[str, int]]:
    """{kernel: {"pallas": n, "fallback": n}} — a view over the registry
    series (every registered kernel present, zeros explicit)."""
    out: Dict[str, Dict[str, int]] = {
        name: {"pallas": 0, "fallback": 0} for name in _KERNELS
    }
    for lbl, v in PALLAS_LAUNCH.items():
        out.setdefault(lbl["kernel"], {"pallas": 0, "fallback": 0})[
            lbl["tier"]
        ] = int(v)
    return out


def _count(name: str, which: str) -> None:
    PALLAS_LAUNCH.inc(kernel=name, tier=which)


def launch(
    name: str,
    pallas_fn: Callable[..., Any],
    fallback_fn: Callable[[], Any],
    *,
    eligible: bool = True,
) -> Any:
    """Run ``pallas_fn(interpret=...)`` when the kernel tier is active for
    ``name``, else ``fallback_fn()``.

    ``eligible``: the caller's per-call shape/dtype/VMEM verdict.

    A ``pallas_fn`` may return ``None`` to DECLINE after a data-dependent
    check — the fallback runs. Exceptions from an interpreted program
    re-raise as they are (real bugs); a compiled-path failure is
    classified (``reraise_if_device`` — an OOM mid-kernel surfaces typed
    to the ladder as what it is) and anything else the lowering raised
    becomes a typed ``CompileFailure``. It never turns into a silent
    fallback.
    """
    spec = _KERNELS[name]
    m = mode()
    interp = m == "interpret"
    active = eligible and (interp or (m != "off" and _backend_is_tpu()))
    with _obs_trace.span(f"kernel:{name}", kind="kernel") as sp:
        if not active:
            sp.note("tier", "fallback")
            _count(name, "fallback")
            return fallback_fn()
        from ....runtime.faults import fault_point

        fault_point(spec.site)
        try:
            out = pallas_fn(interpret=interp)
        except Exception as exc:
            if interp:
                raise
            from ....errors import CompileFailure, reraise_if_device

            reraise_if_device(exc, site=spec.site)
            raise CompileFailure(
                f"[site={spec.site}] Pallas kernel {name!r} was refused by "
                f"the TPU lowering: {type(exc).__name__}: {exc}",
                site=spec.site,
                cause=exc,
            ) from exc
        if out is None:  # kernel declined post-eligibility
            sp.note("tier", "fallback")
            sp.note("declined", True)
            _count(name, "fallback")
            return fallback_fn()
        sp.note("tier", "pallas" if not interp else "pallas-interpret")
        _count(name, "pallas")
        return out


def _backend_is_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"
