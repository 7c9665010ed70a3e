"""Ask the chip's compiler, without the chip.

The TPU compiler is installed here and compiles for a chip that is
DESCRIBED (``jax.experimental.topologies``, ``v5e:2x2``) and not attached.
A compile that passes is not a chip run — nothing executes — but what the
compiler refuses here it refuses on the chip, and that costs no chip time:
every Pallas kernel in the tree once passed all its interpret-mode tests
and was refused by this very lowering.

Under test, at the shapes ``chip_smoke.py`` dispatches at its default scale
(1M persons, ~45M edges, 2**20-lane materializes). Programs whose compile
takes minutes at that size (the count chain: 2-3 min at 45M edges; the
DISTINCT final hop: a minute at ANY size) run here at a reduced size, with
the full size ``slow``-marked:

* the XLA programs of the served path — the fused count chain, the counted
  expand materialize, the fused DISTINCT final hop, the bucketed join
  probe, the WCOJ range count;
* the one Pallas kernel (``tpu_custom_call`` in the compiled text), at its
  eligibility cap, and the proof that the refusals that removed the other
  four are real properties of the lowering (int64 planes);
* the four-chip programs — a ``parallel/agg.py`` shard_map aggregate and
  the sharded count chain on a ``Mesh`` of the described devices, with
  their collectives in the compiled text.

The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU library, and xdist workers all import
this file), the persistent cache is off around the compiles (a described-
device executable cannot be read back), and nothing here starts a child.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from tpu_cypher.backend.tpu import jit_ops as J
from tpu_cypher.backend.tpu.pallas import aggregate as PA
from tpu_cypher.utils.config import PALLAS_MAX_GROUPS

# chip_smoke.py's default deployment: generate_snb(scale=100)
NODES = 1_000_000
EDGES = 45_000_000
LANES = 1 << 20  # a bucketed materialize of the anchored shapes
FRONTIER = 1 << 15  # its input frontier

# (nodes, edges): the reduced size tier-1 compiles at, and the smoke's own
GRAPHS = [
    pytest.param((100_000, 4_500_000), id="scale10"),
    pytest.param((NODES, EDGES), id="scale100", marks=pytest.mark.slow),
]

I32, I64, BOOL = jnp.int32, jnp.int64, jnp.bool_


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    sharding = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    return shape


@pytest.fixture(scope="module")
def four_chips(topo):
    mesh = Mesh(np.array(topo.devices[:4]), ("rows",))

    def shape(dims, dtype, spec):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=NamedSharding(mesh, spec)
        )

    return mesh, shape


def _csr(shape, nodes=NODES, edges=EDGES):
    return shape((nodes + 1,), I32), shape((edges,), I32), shape((edges,), I64)


# ---------------------------------------------------------------------------
# the served path's XLA programs, one chip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("graph", GRAPHS)
def test_fused_count_chain_compiles(one_chip, graph):
    """``path_count_chain`` over a frontier of ids and partial labels: the
    2-hop count(*) as one program, two scatter-free SpMVs (gather, 64-bit
    prefix scan, boundary reads) over every edge."""
    nodes, edges = graph
    rp, ci, _ = _csr(one_chip, nodes, edges)
    mask = one_chip((nodes,), BOOL)
    hop = (rp, ci, None, None, None, mask)
    compiled = J.path_count_chain.lower(
        one_chip((nodes,), I64), one_chip((nodes,), I64), None, (hop, hop),
        num_nodes=nodes,
    ).compile()
    # the working set is the edge arrays, far inside one chip's 16 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


@pytest.mark.parametrize("hops", [1, 2, 3])
def test_scan_free_count_chain_compiles(one_chip, hops):
    """The chain over a whole frontier with no partial label — up to two
    hops at the FULL size, its compile is short without a scan: row_ptr
    differences, then one gather and one 64-bit sum over the edge lanes;
    the third hop brings one scan back, between the two."""
    # the third hop's scan takes minutes to compile at 45M edges
    nodes, edges = (NODES, EDGES) if hops < 3 else (100_000, 4_500_000)
    rp, ci, _ = _csr(one_chip, nodes, edges)
    hop = (rp, ci, None, None, None, None)
    compiled = J.path_count_chain.lower(
        None, None, None, (hop,) * hops, num_nodes=nodes, whole=True
    ).compile()
    text = compiled.as_text()
    assert ("gather" in text) == (hops > 1)  # one hop: no edge is read
    # XLA's TPU scan is a reduce-window; none up to two hops
    assert ("reduce-window" in text) == (hops == 3)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_windowed_scan_hop_compiles_to_one_gather_at_the_window(one_chip):
    """A ``scan`` hop whose CSR holds 2**11 rows of a 2**17-node id space:
    the prefix sums are gathered once, at the window's 2**11 + 1 row
    pointers, and the sums placed by a ``dynamic-update-slice`` — no gather
    of the id space's length is left, and no scatter."""
    nodes, edges, window = 1 << 17, 4_500_000, 1 << 11
    rp, ci, _ = _csr(one_chip, nodes, edges)
    span = (one_chip((window + 1,), I32), one_chip((), I32))
    hop = (rp, ci, None, None, None, one_chip((nodes,), BOOL), span, None)
    compiled = J.path_count_chain.lower(
        one_chip((nodes,), I64), one_chip((nodes,), I64), None, (hop,),
        num_nodes=nodes,
    ).compile()
    text = compiled.as_text()
    assert "dynamic-update-slice" in text and " scatter(" not in text
    assert f"[{window + 1}]" in text
    gathers = [l for l in text.splitlines() if " gather(" in l]
    assert gathers and all(f"[{nodes + 1}]" not in l for l in gathers)


def test_expand_materialize_counted_compiles(one_chip):
    rp, ci, eo = _csr(one_chip)
    J.expand_materialize_counted.lower(
        rp, ci, eo, one_chip((FRONTIER,), I64), one_chip((FRONTIER,), I64),
        one_chip((), I64), size=LANES,
    ).compile()


@pytest.mark.parametrize(
    "total",
    [
        pytest.param(1 << 16, id="2**16"),
        pytest.param(LANES, id="smoke-2**20", marks=pytest.mark.slow),
        pytest.param(1 << 28, id="2**28", marks=pytest.mark.slow),
    ],
)
def test_distinct_pairs_count_final_compiles(one_chip, total):
    """The fused DISTINCT final hop (materialize + packed int64 sort +
    run count) — the program an earlier round saw kill the compiler. It
    compiles, slowly (a minute at 2**16 lanes, ~100 s from 2**24 up), and
    fits: 6.5 GB of temporaries at 2**28."""
    rp, ci, _ = _csr(one_chip)
    frontier = min(total >> 5, 1 << 22)
    compiled = J.distinct_pairs_count_final.lower(
        rp, ci, one_chip((frontier,), I64), one_chip((frontier,), I64),
        one_chip((frontier,), I64), one_chip((NODES,), BOOL),
        total=total, use_a=True, use_c=True, num_nodes=NODES,
        nvalid=one_chip((), I64),
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 12 << 30


def test_join_probe_bucketed_compiles(one_chip):
    """Sort-probe join: the window's persons probed against all 1M."""
    build = NODES
    J.join_probe_bucketed.lower(
        one_chip((build,), I64), one_chip((build,), I64),
        one_chip((LANES,), I64), (one_chip((LANES,), BOOL),),
        one_chip((), I64), nvalid_cap=build, is_f64=False, is_bool=False,
    ).compile()


def test_wcoj_range_count_compiles(one_chip):
    """The WCOJ close probe: 2**20 candidate lanes searched in the 45M
    sorted edge keys."""
    J.range_count.lower(
        one_chip((EDGES,), I64), one_chip((LANES,), I64),
        one_chip((LANES,), BOOL),
    ).compile()


# the constrained count chain at LSQB's Person side of SNB SF10 (the cell
# lsqb-sf10-person.lsqb-chain under pow2 buckets): 83,179 nodes of four labels
# in 2**17 ids, 3.9M KNOWS lanes in 2**22, 65,645 persons as bit rows of
# 66,048 x 2,176 words (GraphIndex.wedge_adjacency: rows in multiples of 512,
# words in whole lanes of 128)
LSQB_IDS, LSQB_LANES, LSQB_ROWS, LSQB_WORDS = 1 << 17, 1 << 22, 66_048, 2_176


def test_wedge_close_sum_compiles_at_lsqb_sf10(one_chip):
    """The closing program: per chunk of 512 closing lanes two gathers of
    bit rows (4.5 MB each), an AND and a population count; no matrix product,
    and the two gathered chunks are all it holds beside the bit rows."""
    rp, ci = one_chip((LSQB_IDS + 1,), I32), one_chip((LSQB_LANES,), I32)
    ids32, ids64 = one_chip((LSQB_IDS,), I32), one_chip((LSQB_IDS,), I64)
    bits = (one_chip((LSQB_ROWS, LSQB_WORDS), jnp.uint32),)
    nodes = one_chip((LSQB_ROWS,), I32)
    compiled = J.wedge_close_sum.lower(
        bits, nodes, bits, nodes, one_chip((LSQB_IDS,), BOOL),
        rp, ci, ci, ids64, ids64, chunk=1 << 9,
    ).compile()
    text = compiled.as_text()
    # the bit rows stay row-major: a node's words are contiguous
    assert "u32[66048,2176]{1,0" in text
    assert "u32[512,2176]" in text and " gather(" in text and " popcnt(" in text
    assert "s8[" not in text and " dot(" not in text and "convolution" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_constrained_chain_helpers_compile_at_lsqb_sf10(one_chip):
    """The bit rows' build (a 3.9M-lane scatter into 575 MB of words), the
    per-lane back counts, the two-cycle sum and the chain's node weights."""
    rp, ci = one_chip((LSQB_IDS + 1,), I32), one_chip((LSQB_LANES,), I32)
    ids32, ids64 = one_chip((LSQB_IDS,), I32), one_chip((LSQB_IDS,), I64)
    mask = one_chip((LSQB_IDS,), BOOL)
    built = J.bit_adjacency.lower(
        rp, ci, ci, ids32, ids32, size=LSQB_ROWS, words=LSQB_WORDS, planes=1
    ).compile()
    assert "u32[66048,2176]{1,0" in built.as_text()
    J.csr_lane_rows.lower(rp, ci).compile()
    J.csr_longest_run.lower(rp, ci, ci).compile()
    J.closing_pair_rows.lower(rp, ci, ci, ids32, ids32).compile()
    J.csr_back_counts.lower(
        rp, ci, ci, one_chip((LSQB_LANES,), I64), num_nodes=LSQB_IDS
    ).compile()
    J.two_cycle_sum.lower(rp, ci, ci, ci, mask, ids64).compile()
    J.chain_node_weights.lower(
        mask, ((rp, ci, mask),), num_nodes=LSQB_IDS
    ).compile()


# ---------------------------------------------------------------------------
# the Pallas tier
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op,identity", [("sum", 0), ("min", 2**31 - 1)])
@pytest.mark.parametrize("rows", [NODES, 1 << 26], ids=["1M", "2**26"])
def test_segment_kernel_compiles_at_its_cap(one_chip, rows, op, identity):
    """The one kernel that stayed: Mosaic accepts it at the GROUP BY cap,
    and the compiled program really contains it."""
    compiled = PA._seg_reduce_pallas.lower(
        one_chip((rows,), I32), one_chip((rows,), I32),
        identity=identity, op=op, k=PALLAS_MAX_GROUPS.get(), interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["count", "min", "max"])
def test_segment_aggregate_drop_in_compiles(one_chip, name):
    """The dispatching drop-in's eligible subset, engine dtypes in (int64
    group index, bool values), kernel inside."""
    compiled = PA._segment_aggregate_pallas.lower(
        one_chip((NODES,), BOOL), one_chip((NODES,), BOOL),
        one_chip((NODES,), I64), name=name, k=7, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [5, J.SEGMENT_DENSE_MAX_GROUPS])
@pytest.mark.parametrize("name", ["min", "sum"])
def test_dense_segment_aggregate_compiles_without_a_scatter(one_chip, name, k):
    """The 64-bit aggregates the kernel cannot take: up to
    ``SEGMENT_DENSE_MAX_GROUPS`` groups the chip's compiler is handed one
    fused compare-select-reduce, no scatter and no ``k x n`` temporary."""
    compiled = J.segment_aggregate.lower(
        one_chip((NODES,), I64), one_chip((NODES,), BOOL), None,
        one_chip((NODES,), I64), name=name, kind=J.I64, k=k,
    ).compile()
    assert " scatter(" not in compiled.as_text()  # the op, not a name
    assert compiled.memory_analysis().temp_size_in_bytes < NODES * 8


@pytest.mark.parametrize("rows", [65_645, 448_626], ids=["sf10", "sf100"])
@pytest.mark.parametrize("k", [10, 1_000])
def test_order_limit_prefix_gather_compiles_at_the_cells_sizes(one_chip, rows, k):
    """ORDER BY ... LIMIT k over the Person scan of the two SNB cells: the
    gather is handed the whole permutation and a static ``k``, and what it
    reads, holds and returns is ``k`` rows of the 12 columns, never a sorted
    table. (The sort before it is ``order_permutation`` as it was: a
    64-bit multi-key sort takes minutes to compile at these sizes, which
    is why the prefix is cut here and not there.)"""
    cols = {
        f"c{i}": (one_chip((rows,), I64), one_chip((rows,), BOOL) if i % 2 else None, None)
        for i in range(12)
    }
    compiled = J.cols_take.lower(cols, one_chip((rows,), I64), first=k).compile()
    memory = compiled.memory_analysis()
    # all 18 outputs and every temporary together: under one column's bytes
    assert memory.output_size_in_bytes + memory.temp_size_in_bytes < rows * 8


def test_order_limit_top_k_compiles_where_it_is_first_chosen(one_chip):
    """``ORDER_TOPK_MIN_ROWS`` rows, two integral keys, the ranges traced
    (64-bit shifts by a traced width go through the chip's 64-bit
    lowering): one program for every range and a binade of LIMITs."""
    rows = J.ORDER_TOPK_MIN_ROWS
    pack = one_chip((2,), I64)
    compiled = J.order_topk.lower(
        (one_chip((rows,), I64), one_chip((rows,), I64)),
        (one_chip((rows,), BOOL), None),
        ascs=(False, True), los=pack, spans=pack, bits=pack, k=16,
    ).compile()
    assert compiled.memory_analysis().output_size_in_bytes < 4096


def test_sixty_four_bit_planes_are_refused(one_chip):
    """Why int64/float64 aggregates decline by eligibility instead of
    riding the kernel: a custom call's 64-bit operand cannot be lowered.
    If this ever starts compiling, the eligibility can widen."""
    with pytest.raises(Exception, match="X64|64"):
        PA._seg_reduce_pallas.lower(
            one_chip((NODES,), I64), one_chip((NODES,), I32),
            identity=0, op="sum", k=8, interpret=False,
        ).compile()


# ---------------------------------------------------------------------------
# four chips: one program across the mesh
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "k,scatters", [(7, False), (J.SEGMENT_DENSE_MAX_GROUPS + 1, True)],
    ids=["dense", "scatter"],
)
@pytest.mark.parametrize("name", ["count", "sum", "max"])
def test_sharded_segment_agg_compiles_for_four_chips(four_chips, name, k, scatters):
    """``parallel/agg.py``: per-shard segment partials combined over the
    mesh — the collective is in the compiled text, and a scatter only past
    ``SEGMENT_DENSE_MAX_GROUPS``. (``max`` once used ``lax.pmax``, which
    this lowering refuses for int64: only SUM all-reduces of 64-bit
    integers are lowered.)"""
    from tpu_cypher.parallel.agg import _agg_fn

    mesh, shape = four_chips
    rows = NODES  # divisible by 4, as ingest pads it
    fn = _agg_fn(mesh, "rows", name, False, k)
    text = fn.lower(
        shape((rows,), I64, P("rows")), shape((rows,), BOOL, P("rows")),
        shape((rows,), I64, P("rows")),
    ).compile().as_text()
    assert "all-reduce" in text
    assert (" scatter(" in text) == scatters  # the op, not a name


@pytest.mark.parametrize("graph", GRAPHS)
def test_sharded_count_chain_compiles_for_four_chips(four_chips, graph):
    """The mesh phase's 2-hop count: the explicit shard_map SpMV over the
    row-sharded edge array, psum over the mesh, per-device bytes a quarter
    of the edges plus the replicated node vectors."""
    nodes, edges = graph
    mesh, shape = four_chips
    rp = shape((nodes + 1,), I32, P())
    ci = shape((edges,), I32, P("rows"))
    mask = shape((nodes,), BOOL, P())
    hop = (rp, ci, None, None, None, mask)
    run = J.path_count_chain_on_mesh(mesh, "rows")
    compiled = run.lower(
        shape((nodes,), I64, P()), shape((nodes,), I64, P("rows")), None,
        (hop, hop), num_nodes=nodes,
    ).compile()
    assert "all-reduce" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def test_sharded_windowed_scan_sums_the_window_over_the_mesh(four_chips):
    """The sharded ``scan`` hop under a window: the ``psum`` carries the
    window's 2**11 sums, not one per node of the id space."""
    nodes, edges, window = 1 << 17, 4_500_000, 1 << 11
    mesh, shape = four_chips
    rp = shape((nodes + 1,), I32, P())
    ci = shape((edges,), I32, P("rows"))
    span = (shape((window + 1,), I32, P()), shape((), I32, P()))
    hop = (rp, ci, None, None, None, shape((nodes,), BOOL, P()), span, None)
    run = J.path_count_chain_on_mesh(mesh, "rows")
    text = run.lower(
        shape((nodes,), I64, P()), shape((nodes,), I64, P("rows")), None,
        (hop,), num_nodes=nodes,
    ).compile().as_text()
    reduces = [l for l in text.splitlines() if " all-reduce(" in l or " all-reduce-start(" in l]
    assert any(f"[{window}]" in l for l in reduces)
    assert all(f"[{nodes}]" not in l for l in reduces)


def test_sharded_scan_free_count_chain_compiles_for_four_chips(four_chips):
    """The mesh chain over a whole frontier, full size: degrees from the
    replicated row_ptr, each shard's masked sum of its own lanes, and a
    ``psum`` of one scalar — no vector of ``num_nodes`` crosses the mesh."""
    mesh, shape = four_chips
    rp = shape((NODES + 1,), I32, P())
    ci = shape((EDGES,), I32, P("rows"))
    hop = (rp, ci, None, None, None, None)
    run = J.path_count_chain_on_mesh(mesh, "rows")
    compiled = run.lower(
        None, None, None, (hop, hop), num_nodes=NODES, whole=True
    ).compile()
    text = compiled.as_text()
    reduces = [l for l in text.splitlines() if " all-reduce(" in l or " all-reduce-start(" in l]
    assert reduces and all(f"[{NODES}]" not in l for l in reduces)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
