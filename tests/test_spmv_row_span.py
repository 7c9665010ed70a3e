"""The node side of a ``scan`` hop is bounded by the rows its CSR holds
(``jit_ops._csr_spmv``'s ``span``): a label's nodes are one run of the
sorted id space, a typed CSR has rows only inside the run of the labels its
edges start from, and the index keeps that run with a window over it whose
length follows the bucket lattice (``GraphIndex.csr_row_span``). The prefix
sums are gathered once, at the window's row pointers; every row outside the
window has degree 0.

Held here: the SpMV under a window against a dense NumPy product wherever
the run lies (the start, the middle, the very end of the node space — the
clamped start — the whole of it, nowhere); an undirected hop whose two
orientations hold different rows; the count chain and the tree count with
spans against the same calls without and against NumPy, over the forms
``test_count_chain_forms`` enumerates; the index's spans against a
brute-force first and last nonempty row; and LSQB's Q1, Q4 and Q7 over
nine labels against an enumeration that builds every row, through both
sessions."""

import itertools

import numpy as np
import pytest

import jax.numpy as jnp

import test_tree_count as TT
from tpu_cypher import CypherSession
from tpu_cypher.backend.tpu import bucketing
from tpu_cypher.backend.tpu import jit_ops as J
from tpu_cypher.backend.tpu.graph_index import GraphIndex
from tpu_cypher.obs.metrics import REGISTRY

NODES = 128  # the node space, on the lattice
LIVE = 120  # the graph's own nodes: eight pad nodes close the space
LANES = 64  # every CSR's edge lanes: a pad tail of ``ci`` = -1 in each
NODE_LANES = "tpu_cypher_count_scan_node_lanes_total"


def typed_csr(rng, rows, cols, edges, loops=()):
    """A random CSR of one relationship type whose edges start in the run
    ``rows`` and end in the run ``cols`` (parallel edges as they fall;
    ``loops``: nodes with a self-loop), as the index builds it: the dense
    multiplicity matrix, int32 ``row_ptr`` over the whole node space and
    ``col_idx`` tail-padded with -1 to ``LANES``."""
    src = np.concatenate([rng.integers(*rows, edges), np.array(loops, dtype=int)])
    dst = np.concatenate([rng.integers(*cols, edges), np.array(loops, dtype=int)])
    a = np.zeros((NODES, NODES), dtype=np.int64)
    np.add.at(a, (src, dst), 1)
    return a, *csr_of(a)


def csr_of(a):
    src, dst = np.nonzero(a)
    reps = a[src, dst]
    src, dst = np.repeat(src, reps), np.repeat(dst, reps)
    rp = np.searchsorted(src, np.arange(NODES + 1)).astype(np.int32)
    ci = np.full(LANES, -1, dtype=np.int32)
    ci[: len(dst)] = dst
    return rp, ci


def window_of(rp):
    """The span as the index hands it to a program."""
    return GraphIndex._row_span(rp, jnp.asarray(rp)).window


@pytest.fixture(params=["off", "pow2"])
def bucket(request):
    bucketing.MODE.set(request.param)
    yield request.param
    bucketing.MODE.reset()


# -- the SpMV under a window -------------------------------------------------

# where the rows with an edge lie; None: the type has no edge
RUNS = {
    "at_the_start": (0, 20),
    "in_the_middle": (40, 70),
    "at_the_very_end": (101, NODES),  # the window's start is clamped
    "the_last_row_alone": (NODES - 1, NODES),
    "the_whole_space": (0, NODES),
    "longer_than_half": (3, 120),  # on the lattice: the whole space
    "no_edge": None,
}


@pytest.mark.parametrize("weights", ["small", "past_2_32"])
@pytest.mark.parametrize("run", sorted(RUNS))
def test_spmv_under_a_window_is_the_dense_product(bucket, run, weights):
    rng = np.random.default_rng(sorted(RUNS).index(run))
    if RUNS[run] is None:
        a = np.zeros((NODES, NODES), dtype=np.int64)
        rp, ci = csr_of(a)
    else:
        a, rp, ci = typed_csr(rng, RUNS[run], (0, NODES), 50)
    assert (ci[-8:] == -1).all()  # the bucket's pad tail
    w = rng.integers(0, (1 << 40) if weights == "past_2_32" else 9, NODES)
    want = a @ w
    if weights == "past_2_32" and RUNS[run] is not None:
        assert want.max() > 1 << 32

    span = GraphIndex._row_span(rp, jnp.asarray(rp))
    length = span.row_ptr.shape[0] - 1
    degs = np.diff(rp)
    held = np.flatnonzero(degs)
    if len(held):  # the brute-force first and last row with an edge
        assert (span.lo, span.hi) == (held[0], held[-1] + 1)
    else:
        assert span.lo == span.hi
    assert 0 <= span.start <= span.lo
    assert span.hi <= span.start + length <= NODES
    assert np.array_equal(
        np.asarray(span.row_ptr), rp[span.start:span.start + length + 1])
    if bucket == "pow2":  # the lattice the node space itself follows
        assert length == min(bucketing.round_size(max(span.hi - span.lo, 1)), NODES)
        assert length == NODES or length & (length - 1) == 0
    else:
        assert length == max(span.hi - span.lo, 1)

    args = jnp.asarray(rp), jnp.asarray(ci), jnp.asarray(w)
    got = J._csr_spmv(*args, span.window)
    assert got.dtype == jnp.int64 and got.shape == (NODES,)
    assert np.array_equal(np.asarray(got), want)
    assert np.array_equal(np.asarray(J._csr_spmv(*args)), want)


@pytest.mark.parametrize("start", [0, 7, 25, 40])
def test_any_window_that_covers_the_rows_is_exact(start):
    """Rows 40..55 under windows of 32 and 64 rows from four starts: every
    window that covers the run gives the product, whatever rows of degree
    0 it takes in (one that does not is never handed out by the index)."""
    rng = np.random.default_rng(5)
    a, rp, ci = typed_csr(rng, (40, 56), (0, NODES), 40)
    w = rng.integers(0, 1 << 20, NODES)
    covering = [n for n in (32, 64) if start <= 40 and 56 <= start + n]
    assert covering
    for length in covering:
        span = (jnp.asarray(rp[start:start + length + 1]), np.int32(start))
        got = J._csr_spmv(jnp.asarray(rp), jnp.asarray(ci), jnp.asarray(w), span)
        assert np.array_equal(np.asarray(got), a @ w), (start, length)


# -- hops of a count: spans against none, against NumPy ----------------------

# four runs of the id space, the neighbours overlapping (a node of the
# overlap may carry a self-loop); type j goes from run j to run j + 1
LABEL_RUNS = [(8, 30), (24, 60), (50, 90), (100, LIVE)]
TYPE_LOOPS = [(25, 26), (52, 53), ()]
EVEN = np.arange(NODES) % 2 == 0


def typed_world():
    rng = np.random.default_rng(21)
    return [
        typed_csr(rng, LABEL_RUNS[j], LABEL_RUNS[j + 1], 36, TYPE_LOOPS[j])[0]
        for j in range(3)
    ]


def hop_of(a, direction, mask, spans: bool):
    """One hop over the type ``a`` as ``_hop_arrays`` builds it, or without
    its spans (six parts). The dense matrix beside it."""
    fwd, rev = csr_of(a), csr_of(a.T)
    mask = jnp.asarray(EVEN) if mask else None
    if direction == "und":
        parts = (*map(jnp.asarray, fwd), *map(jnp.asarray, rev),
                 jnp.asarray(np.diag(a).copy()), mask)
        both = (window_of(fwd[0]), window_of(rev[0]))
        return parts + both if spans else parts, a + a.T - np.diag(np.diag(a))
    rp, ci = rev if direction == "bwd" else fwd
    parts = (jnp.asarray(rp), jnp.asarray(ci), None, None, None, mask)
    dense = a.T if direction == "bwd" else a
    return parts + (window_of(rp), None) if spans else parts, dense


def test_an_undirected_hop_reads_each_orientation_through_its_own_window(bucket):
    a = typed_world()[0]
    fwd, rev = window_of(csr_of(a)[0]), window_of(csr_of(a.T)[0])
    assert int(fwd[1]) != int(rev[1])  # two different spans
    assert np.diag(a).sum() == 2  # and self-loops, counted once
    hop, dense = hop_of(a, "und", True, spans=True)
    bare, _ = hop_of(a, "und", True, spans=False)
    dev_ids = jnp.asarray(np.arange(NODES, dtype=np.int64))
    picked = np.array([9, 25, 25, 26, 29, 57])
    want = int((dense @ EVEN.astype(np.int64))[picked].sum())
    for hops in ((hop,), (bare,)):
        got = J.path_count_chain(
            dev_ids, jnp.asarray(picked), None, hops, num_nodes=NODES)
        assert int(got) == want


CHAIN_CASES = [
    (direction, masks, whole)
    for hops in (1, 2, 3)
    for direction in ("fwd", "bwd", "und")
    for masks in itertools.product([False, True], repeat=hops)
    for whole in (False, True)
]


@pytest.mark.parametrize(
    "direction,masks,whole", CHAIN_CASES,
    ids=[f"{d}-{''.join('m' if x else '-' for x in m)}-{'whole' if w else 'ids'}"
         for d, m, w in CHAIN_CASES],
)
def test_chain_with_spans_is_the_chain_without(direction, masks, whole):
    """``path_count_chain`` over every form ``test_count_chain_forms``
    enumerates (every pattern of partial masks, a whole frontier and one of
    ids), the hops typed so that each CSR holds one run of the rows."""
    world = typed_world()
    # a pattern walked backwards meets the types in the other order
    types = world[: len(masks)] if direction != "bwd" else world[: len(masks)][::-1]
    w = np.ones(NODES, dtype=np.int64)
    spanned, bare = [], []
    for a, masked in reversed(list(zip(types, masks))):
        hop, dense = hop_of(a, direction, masked, spans=True)
        w = dense @ (EVEN * w if masked else w)
        spanned.insert(0, hop)
        bare.insert(0, hop[:6])
    dev_ids = np.arange(NODES, dtype=np.int64) * 3 + 11
    if whole:
        frontier, want = (None, None, None), int(w.sum())
    else:
        picked = np.array([9, 9, 25, 52, 55, 55, 101])
        ids = np.concatenate([dev_ids[picked], [7]])  # one id of no node
        frontier = (jnp.asarray(dev_ids), jnp.asarray(ids), None)
        want = int(w[picked].sum())
    got = [
        J.path_count_chain(*frontier, tuple(hops), num_nodes=NODES, whole=whole)
        for hops in (spanned, bare)
    ]
    assert all(g.dtype == jnp.int64 for g in got)
    assert [int(g) for g in got] == [want, want]


# trees as ``jit_ops.tree_count`` takes them: a child is (hop, optional, children)
TREES = {
    # three branches at the root
    "star": ((0, False, ()), (1, False, ()), (2, False, ())),
    "star_two_optional": ((0, False, ()), (1, True, ()), (2, True, ())),
    # two branches, one OPTIONAL, under a branch
    "deep": ((0, False, ((1, False, ()), (2, True, ()))),),
}
TREE_CASES = list(itertools.product(sorted(TREES), ("fwd", "bwd", "und"), (False, True)))


def _dense_tree(children, dense, masked):
    w = np.ones(NODES, dtype=np.int64)
    for k, optional, below in children:
        beyond = _dense_tree(below, dense, masked)
        branch = dense[k] @ (EVEN * beyond if masked else beyond)
        w = w * (np.maximum(branch, 1) if optional else branch)
    return w


@pytest.mark.parametrize(
    "shape,direction,masked", TREE_CASES,
    ids=[f"{s}-{d}-{'masked' if m else 'plain'}" for s, d, m in TREE_CASES],
)
def test_tree_count_with_spans_is_the_tree_count_without(shape, direction, masked):
    tree = TREES[shape]
    # every branch over the one type whose rows and columns overlap, so a
    # product of branches is not 0 everywhere
    a = typed_world()[0]
    built = [hop_of(a, direction, masked, spans=True) for _ in range(3)]
    spanned = tuple(h for h, _ in built)
    bare = tuple(h[:6] for h in spanned)
    dense = [d for _, d in built]
    root = _dense_tree(tree, dense, masked)
    root_weight = np.zeros(NODES, dtype=np.int64)
    root_weight[[9, 24, 25, 25, 26, 28, 29, 31]] += 1
    want = int((root * root_weight).sum())
    got = [
        J.tree_count(jnp.asarray(root_weight), np.int32(LIVE), hops,
                     tree=tree, whole=False)
        for hops in (spanned, bare)
    ]
    assert [int(g) for g in got] == [want, want]
    # every node once: the pad nodes weigh nothing, OPTIONAL or not
    whole = [
        int(J.tree_count(None, np.int32(LIVE), hops, tree=tree, whole=True))
        for hops in (spanned, bare)
    ]
    assert whole == [int(root[:LIVE].sum())] * 2


# -- the index's spans --------------------------------------------------------


def three_labels(seed, session):
    """Three labels, each one run of the sorted id space (A under B under
    C), and four types: A to B, B to C, C to C, and one without a row."""
    rng = np.random.default_rng(seed)
    a = np.sort(rng.choice(1 << 20, 40, replace=False)).astype(np.int64)
    b = np.sort(rng.choice(1 << 20, 300, replace=False)).astype(np.int64) + (1 << 30)
    c = np.sort(rng.choice(1 << 20, 90, replace=False)).astype(np.int64) + (1 << 31)
    none = np.zeros(0, dtype=np.int64)
    nodes = {"A": a, "B": b, "C": c}
    rels = {
        "AB": (rng.choice(a, 70), rng.choice(b, 70)),
        "BC": (rng.choice(b[100:200], 50), rng.choice(c, 50)),
        "CC": (rng.choice(c, 30), rng.choice(c[-5:], 30)),
        "NONE": (none, none),
    }
    return TT.make_graph(session, nodes, rels), nodes


ORIENTATIONS = list(itertools.product(("AB", "BC", "CC", "NONE"), (False, True)))


@pytest.fixture(scope="module")
def indexed():
    """Per bucket mode: two graphs of one size from two seeds, indexed."""
    out = {}
    for mode in ("off", "pow2"):
        bucketing.MODE.set(mode)
        try:
            session = CypherSession.tpu()
            graphs = [three_labels(seed, session) for seed in (1, 2)]
            ctx = session._runtime_context({})
            indexes = [GraphIndex.of(g._graph) for g, _ in graphs]
            for gi in indexes:
                gi.node_ids(ctx)
                for types, reverse in ORIENTATIONS:
                    gi.csr((types,), reverse, ctx)
            out[mode] = (indexes, ctx, [nodes for _, nodes in graphs])
        finally:
            bucketing.MODE.reset()
    return out


@pytest.mark.parametrize("mode", ["off", "pow2"])
@pytest.mark.parametrize(
    "types,reverse", ORIENTATIONS,
    ids=[f"{t}-{'reverse' if r else 'forward'}" for t, r in ORIENTATIONS],
)
def test_the_index_keeps_the_rows_a_csr_holds(indexed, mode, types, reverse):
    indexes, ctx, nodes = indexed[mode]
    windows = []
    for gi, tables in zip(indexes, nodes):
        got = gi.csr((types,), reverse, ctx)
        assert len(got) == 3  # what every caller unpacks
        rp = np.asarray(got[0])
        n = gi.num_nodes
        span = gi.csr_row_span((types,), reverse, ctx)
        length = span.row_ptr.shape[0] - 1
        held = np.flatnonzero(np.diff(rp))
        if types == "NONE":
            assert len(held) == 0 and span.lo == span.hi
            assert length == (32 if mode == "pow2" else 1)
        else:
            assert (span.lo, span.hi) == (held[0], held[-1] + 1)
            # inside the run of the label the edges start from
            label = types[1] if reverse else types[0]
            before = sum(len(ids) for name, ids in tables.items() if name < label)
            assert before <= span.lo and span.hi <= before + len(tables[label])
        assert span.start <= span.lo and span.hi <= span.start + length <= n
        assert np.array_equal(
            np.asarray(span.row_ptr), rp[span.start:span.start + length + 1])
        assert int(span.window[1]) == span.start
        windows.append(length)
    if mode == "pow2":  # two seeds of one deployment share a program
        assert windows[0] == windows[1]
        assert windows[0] < indexes[0].num_nodes or (types, reverse) == ("AB", True)


def test_a_window_as_long_as_the_node_space_is_row_ptr_itself(indexed):
    """B's run starts inside the first half of a 512-node space and ends
    in the second: AB reversed spans more than half, its window is the
    whole ``row_ptr`` — the same device array, not a copy of it."""
    (gi, _), ctx, _ = indexed["pow2"]
    rp, _, _ = gi.csr(("AB",), True, ctx)
    span = gi.csr_row_span(("AB",), True, ctx)
    assert span.row_ptr is rp and span.start == 0
    small = gi.csr_row_span(("CC",), True, ctx)
    assert small.row_ptr.shape[0] - 1 == 32 < gi.num_nodes


# -- LSQB's three tree queries over nine labels, both sessions ----------------


def lsqb_tables(seed):
    """``test_tree_count``'s graph (Person, Tag, Forum, Post and Comment as
    ``Message``) and the rest of LSQB's schema: City, Country, TagClass and
    the five types Q1 walks besides."""
    nodes, rels = TT.make_tables(seed)
    rng = np.random.default_rng(seed + 100)
    city = np.arange(700, 707, dtype=np.int64)
    country = np.arange(800, 803, dtype=np.int64)
    tagclass = np.arange(900, 904, dtype=np.int64)
    person, tag, forum = nodes["Person"], nodes["Tag"], nodes["Forum"]
    post = nodes[("Message", "Post")]
    nodes = {**nodes, "City": city, "Country": country, "TagClass": tagclass}
    rels = {
        **rels,
        "IS_PART_OF": (city, rng.choice(country, len(city))),
        "IS_LOCATED_IN": (person, rng.choice(city, len(person))),
        "HAS_MEMBER": (rng.choice(forum, 14), rng.choice(person, 14)),
        "CONTAINER_OF": (rng.choice(forum, len(post)), post),
        "HAS_TYPE": (tag, rng.choice(tagclass, len(tag))),
    }
    return nodes, rels


Q1 = (
    "MATCH (:Country)<-[:IS_PART_OF]-(:City)<-[:IS_LOCATED_IN]-(:Person)"
    "<-[:HAS_MEMBER]-(:Forum)-[:CONTAINER_OF]->(:Post)<-[:REPLY_OF]-(:Comment)"
    "-[:HAS_TAG]->(:Tag)-[:HAS_TYPE]->(:TagClass) RETURN count(*) AS count"
)
Q4 = (
    "MATCH (:Tag)<-[:HAS_TAG]-(message:Message)-[:HAS_CREATOR]->(creator:Person), "
    "(message)<-[:LIKES]-(liker:Person), "
    "(message)<-[:REPLY_OF]-(comment:Comment) RETURN count(*) AS count"
)
Q7 = (
    "MATCH (:Tag)<-[:HAS_TAG]-(message:Message)-[:HAS_CREATOR]->(creator:Person) "
    "OPTIONAL MATCH (message)<-[:LIKES]-(liker:Person) "
    "OPTIONAL MATCH (message)<-[:REPLY_OF]-(comment:Comment) "
    "RETURN count(*) AS count"
)
Q1_HOPS = [
    ("co", "ci", "IS_PART_OF", False, ("City",)),
    ("ci", "p", "IS_LOCATED_IN", False, ("Person",)),
    ("p", "f", "HAS_MEMBER", False, ("Forum",)),
    ("f", "po", "CONTAINER_OF", True, ("Post",)),
    ("po", "c", "REPLY_OF", False, ("Comment",)),
    ("c", "t", "HAS_TAG", True, ("Tag",)),
    ("t", "tc", "HAS_TYPE", True, ("TagClass",)),
]
LSQB = {
    "q1": (Q1, ("co", ("Country",)), [(False, Q1_HOPS)]),
    "q4": (Q4, ("t", ("Tag",)), [(False, TT.HEAD_HOPS + [TT.LIKES, TT.REPLY])]),
    "q7": (Q7, ("t", ("Tag",)),
           [(False, TT.HEAD_HOPS), (True, [TT.LIKES]), (True, [TT.REPLY])]),
}


@pytest.fixture(scope="module", params=[11, 12])
def lsqb_world(request):
    nodes, rels = lsqb_tables(request.param)
    graphs = {
        name: TT.make_graph(session, nodes, rels)
        for name, session in (("local", CypherSession.local()),
                              ("tpu", CypherSession.tpu()))
    }
    return nodes, rels, graphs


@pytest.mark.parametrize("session", ["local", "tpu"])
@pytest.mark.parametrize("query", sorted(LSQB))
def test_lsqb_tree_queries_answer_as_the_enumeration(lsqb_world, query, session):
    nodes, rels, graphs = lsqb_world
    text, start, clauses = LSQB[query]
    want = len(TT.enumerate_rows(nodes, rels, start, clauses))
    assert want > 0
    before = REGISTRY.flat().get(NODE_LANES, 0.0)
    got = graphs[session].cypher(text).records.collect()
    assert [dict(r) for r in got] == [{"count": want}]
    moved = REGISTRY.flat().get(NODE_LANES, 0.0) - before
    # the engine's count scans under windows; the oracle builds its rows
    assert (moved > 0) == (session == "tpu")
