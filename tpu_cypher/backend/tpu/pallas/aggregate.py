"""Pallas masked segment-reduce kernel for grouped aggregation.

``jax.ops.segment_sum``/``segment_min``/``segment_max`` lower as
scatter-reduces, which the TPU serializes (SURVEY: scatter is the one
primitive the VPU cannot vectorize); ``jit_ops.segment_aggregate`` takes
them only past ``SEGMENT_DENSE_MAX_GROUPS`` groups and is a plain dense
compare-and-reduce under it. The hand-scheduled version never scatters
either: the rows stream through VMEM in ``(_TILE_ROWS, 128)`` tiles, and
each 128-lane row is compared against a group iota broadcast along
sublanes, folding into ONE VMEM-resident ``(k_pad, 128)`` accumulator of
per-(group, lane) partials that every grid step revisits. The 128 lane
partials per group combine with one dense reduction outside the kernel.
Nothing in the kernel crosses lanes or relayouts a vector, and every block
obeys the TPU lowering's (8, 128) rule.

Lane width: Mosaic has no 64-bit lanes (and XLA's x64 rewriter cannot
split a custom call's int64 operand), so the kernel takes 32-bit planes
only. That covers ``count`` over any column (a 0/1 int32 mask) and
min/max over BOOL (0/1 int32); int64 / float64 / dict-coded values keep
the plain formulation by eligibility — a 64-bit kernel is never handed
to the compiler.

Masking discipline (docs/pad-invariants.md): kernel tile pad lanes carry
segment id -1, which matches no group lane — mask-dead INSIDE the kernel,
not at the materialize boundary. Identities mirror ``segment_aggregate``'s
exactly, so empty groups come out bit-identical to the ``jax.ops.segment_*``
formulation, including the sentinel payloads that validity masks hide
downstream.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ....obs import trace as _obs_trace
from ....utils.config import PALLAS_MAX_GROUPS
from . import dispatch
from .. import jit_ops as J
from ..jit_ops import BOOL

_LANES = 128
# rows of 128 lanes per grid step: 128 KiB per int32 input tile
_TILE_ROWS = 256
_BLOCK = _TILE_ROWS * _LANES

def _i32(x: int):
    # index arithmetic stays int32 under JAX_ENABLE_X64: Mosaic refuses an
    # i64 block index or loop counter
    return jnp.int32(x)


def _seg_reduce_kernel_for(op: str, identity: int):
    def kernel(vals_ref, seg_ref, out_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            out_ref[...] = jnp.full(out_ref.shape, identity, out_ref.dtype)

        kidx = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
        ident = jnp.full(out_ref.shape, identity, out_ref.dtype)

        def body(r, acc):
            v = vals_ref[pl.ds(r, 1), :]
            s = seg_ref[pl.ds(r, 1), :]
            # dead lanes carry -1: never matches a group lane
            x = jnp.where(s == kidx, v, ident)
            if op == "sum":
                return acc + x
            if op == "min":
                return jnp.minimum(acc, x)
            return jnp.maximum(acc, x)

        out_ref[...] = jax.lax.fori_loop(
            _i32(0), _i32(vals_ref.shape[0]), body, out_ref[...]
        )

    return kernel


@partial(jax.jit, static_argnames=("identity", "op", "k", "interpret"))
def _seg_reduce_pallas(vals, seg, identity: int, op: str, k: int, interpret: bool):
    """One int32 segment reduction, exactly ``jax.ops.segment_<op>(vals,
    seg, num_segments=k)``: tile the rows, fold every tile into the
    resident per-(group, lane) accumulator, dense lane combine. Only
    kernel TILE PAD lanes carry segment -1 (mask-dead inside the kernel);
    value-level masking is the CALLER's, same as the scatter formulation's
    ``where``-fed inputs — so per-group results (including the empty-group
    identity) are bit-identical. ``identity`` is the op's neutral element
    as a STATIC Python scalar (Pallas kernels cannot close over traced
    values)."""
    n = vals.shape[0]
    npad = ((max(n, 1) + _BLOCK - 1) // _BLOCK) * _BLOCK
    k_pad = ((k + 7) // 8) * 8
    pad = npad - n
    if pad:
        vals = jnp.concatenate([vals, jnp.full(pad, identity, vals.dtype)])
        seg = jnp.concatenate([seg, jnp.full(pad, -1, seg.dtype)])
    shape2d = (npad // _LANES, _LANES)
    tile = pl.BlockSpec((_TILE_ROWS, _LANES), lambda i: (i, _i32(0)))
    partials = pl.pallas_call(
        _seg_reduce_kernel_for(op, identity),
        out_shape=jax.ShapeDtypeStruct((k_pad, _LANES), jnp.int32),
        grid=(npad // _BLOCK,),
        in_specs=[tile, tile],
        # every grid step revisits the one accumulator block
        out_specs=pl.BlockSpec(
            (k_pad, _LANES), lambda i: (_i32(0), _i32(0))
        ),
        interpret=interpret,
    )(vals.reshape(shape2d), seg.reshape(shape2d))
    if op == "sum":
        return jnp.sum(partials, axis=1, dtype=jnp.int32)[:k]
    if op == "min":
        return jnp.min(partials, axis=1)[:k]
    return jnp.max(partials, axis=1)[:k]


dispatch.register(
    "segment_agg", "kernel_agg", impls=("_seg_reduce_pallas",)
)


@partial(jax.jit, static_argnames=("name", "k", "interpret"))
def _segment_aggregate_pallas(
    data, valid, seg_j, name: str, k: int, interpret: bool
):
    """Kernel-backed mirror of ``jit_ops.segment_aggregate`` for the
    eligible subset (count over anything; min / max over BOOL). Every
    masking rule, identity, and output dtype matches the scatter
    formulation bit-for-bit — pinned by the differential tests."""
    n = data.shape[0]
    v = valid if valid is not None else jnp.ones(n, bool)
    seg32 = seg_j.astype(jnp.int32)
    cnt = _seg_reduce_pallas(
        v.astype(jnp.int32), seg32, 0, "sum", k, interpret
    ).astype(jnp.int64)
    if name == "count":
        return cnt, None, None, None
    # BOOL min / max compare as 0/1 ints, mirroring segment_aggregate
    # value-for-value: invalid rows participate carrying the identity-side
    # sentinel, empty groups come out as the segment op's identity
    d = data.astype(jnp.int32)
    big = int(jnp.iinfo(jnp.int32).max)
    lowest = int(jnp.iinfo(jnp.int32).min)
    if name == "min":
        agged = _seg_reduce_pallas(
            jnp.where(v, d, big), seg32, big, "min", k, interpret
        )
    else:
        agged = _seg_reduce_pallas(
            jnp.where(v, d, -big), seg32, lowest, "max", k, interpret
        )
    return agged.astype(bool), cnt > 0, None, None


def segment_aggregate(
    data, valid, iflag, seg_j, *, name: str, kind: str, k: int, plain=None
):
    """Dispatching drop-in for ``jit_ops.segment_aggregate`` (same 4-tuple
    contract; ``plain``: the caller's own call of it, for one that wants to
    know that it ran). Eligible: count over anything; min/max over BOOL — the
    aggregates whose working planes are 32-bit (see the module docstring).
    GROUP BY cardinality is capped (``TPU_CYPHER_PALLAS_MAX_GROUPS``): the
    (k_pad, 128) int32 accumulator is 128 KiB (32 vregs) at the declared
    default; larger GROUP BYs keep the plain formulation."""
    eligible = (
        0 < k <= int(PALLAS_MAX_GROUPS.get())
        and data.ndim == 1
        and (
            name == "count"
            or (name in ("min", "max") and kind == BOOL and iflag is None)
        )
    )
    return dispatch.launch(
        "segment_agg",
        lambda interpret: _segment_aggregate_pallas(
            data, valid, seg_j, name=name, k=k, interpret=interpret
        ),
        plain or (lambda: J.segment_aggregate(
            data, valid, iflag, seg_j, name=name, kind=kind, k=k
        )),
        eligible=eligible,
    )


# every jitted program of this module dispatches under an obs.trace
# ``dispatch`` leaf (last: the decorators above stay plain ``jax.jit``)
_obs_trace.wrap_programs(globals())
