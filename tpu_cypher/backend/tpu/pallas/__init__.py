"""Hand-scheduled Pallas kernels for the query hot path.

One package, one policy: every kernel registers with ``dispatch`` (mode /
eligibility / fault sites / use counters) and ships next to the jnp
formulation it replaces, so ``TPU_CYPHER_PALLAS=off`` is always the exact
pre-kernel execution path and ``=interpret`` runs the identical programs
on any backend (tier-1 parity). A kernel stays here only while the chip's
compiler accepts it at its eligibility cap (``tests/test_chip_compile.py``)
— see docs/performance.md ("kernel tiers") and docs/pad-invariants.md.

Kernels:

* ``aggregate.segment_aggregate`` — masked grouped segment reduce
"""

from . import dispatch  # noqa: F401
from .aggregate import segment_aggregate  # noqa: F401
