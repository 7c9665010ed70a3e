"""The fused count chain takes each hop in the cheapest form its algebra
allows — ``degree`` (row_ptr differences), ``reduce`` (one gather and one
sum over the edges), ``scan`` (the prefix-scan SpMV) — chosen from what the
code can observe alone: are the weights still constant, is the label mask
all-true, does the frontier hold every node once.

Every case holds the chain's count to three witnesses — a dense NumPy path
count, the host oracle's count (the classic join cascade) and the number of
rows the TPU backend materializes for the same pattern — and the forms the
counter reports to the rule as the issue states it.
"""

import itertools

import numpy as np
import pytest

from tpu_cypher import CypherSession
from tpu_cypher.api import types as T
from tpu_cypher.api.mapping import NodeMapping, RelationshipMapping
from tpu_cypher.api.schema import PropertyGraphSchema
from tpu_cypher.backend.local.table import LocalTable
from tpu_cypher.backend.tpu import bucketing
from tpu_cypher.backend.tpu import jit_ops as J
from tpu_cypher.obs import trace as obs_trace
from tpu_cypher.obs.metrics import REGISTRY
from tpu_cypher.relational.graphs import ElementTable, ScanGraph
from tpu_cypher.relational.session import PropertyGraph

FORMS = ("degree", "reduce", "scan")
TYPES = ("T1", "T2", "T3")  # one per hop: no uniqueness pair between hops
N = 13  # pow2 pads the node space to 16: three pad nodes
EDGES = (21, 19, 23)  # each pads to 32 lanes: pad edges in every CSR


def _graph(session, make_table, nodes, rels):
    """``nodes``: (labels, ids, i) per node table; ``rels``: (type, src,
    dst). Every node carries the property ``i``."""
    tables, schema = [], PropertyGraphSchema.empty()
    props = {"i": T.CTInteger.nullable}
    for labels, ids, i in nodes:
        tables.append(ElementTable(
            NodeMapping(id_key="id", implied_labels=frozenset(labels),
                        property_mapping=(("i", "i"),)),
            make_table({"id": ids, "i": i}),
        ))
        schema = schema.with_node_combination(frozenset(labels), props)
    for k, (rtype, src, dst) in enumerate(rels):
        tables.append(ElementTable(
            RelationshipMapping(id_key="id", source_key="source",
                                target_key="target", rel_type=rtype),
            make_table({
                "id": np.arange(len(src), dtype=np.int64) + ((k + 1) << 40),
                "source": src, "target": dst,
            }),
        ))
        schema = schema.with_relationship_type(rtype, {})
    return PropertyGraph(session, ScanGraph(tables, schema))


def _tpu_graph(nodes, rels):
    s = CypherSession.tpu()
    return _graph(s, s.table_cls.from_arrays, nodes, rels)


def _local_graph(nodes, rels):
    return _graph(
        CypherSession.local(),
        lambda cols: LocalTable.from_columns(
            {k: v.tolist() for k, v in cols.items()}),
        nodes, rels,
    )


def _small():
    """13 nodes with sparse 64-bit ids — all carry ``V``, the even ones
    ``H`` too — and three relationship types with self-loops and parallel
    edges."""
    rng = np.random.default_rng(11)
    i = np.arange(N, dtype=np.int64)
    ids = i * 7 + (1 << 41)
    even = i % 2 == 0
    nodes = [(("V", "H"), ids[even], i[even]), (("V",), ids[~even], i[~even])]
    rels, adj = [], {}
    for rtype, e in zip(TYPES, EDGES):
        src, dst = rng.integers(0, N, e), rng.integers(0, N, e)
        src[:2], dst[:2] = (3, 4), (3, 4)  # two self-loops
        src[2:4], dst[2:4] = (5, 5), (6, 6)  # a parallel pair
        rels.append((rtype, ids[src], ids[dst]))
        a = np.zeros((N, N), dtype=np.int64)
        np.add.at(a, (src, dst), 1)
        adj[rtype] = a
    return nodes, rels, adj, even


@pytest.fixture(scope="module")
def small():
    nodes, rels, adj, even = _small()
    graphs = {}
    for m in ("off", "pow2"):
        bucketing.MODE.set(m)
        try:
            graphs[m] = _tpu_graph(nodes, rels)
        finally:
            bucketing.MODE.reset()
    carries = {"": np.ones(N, dtype=np.int64), "V": np.ones(N, dtype=np.int64),
               "H": even.astype(np.int64)}
    return {"tpu": graphs, "local": _local_graph(nodes, rels), "adj": adj,
            "carries": carries, "witness": {}}


NODE_LANES = "tpu_cypher_count_scan_node_lanes_total"


def _forms_of(run):
    """What ``run`` gives and its hops by form; on the way: row pointers
    are gathered at by the ``scan`` hops alone."""
    before = {f: obs_trace.COUNT_CHAIN_HOPS.value(form=f) for f in FORMS}
    lanes = REGISTRY.flat().get(NODE_LANES, 0.0)
    out = run()
    forms = tuple(
        int(obs_trace.COUNT_CHAIN_HOPS.value(form=f) - before[f]) for f in FORMS
    )
    assert (REGISTRY.flat()[NODE_LANES] > lanes) == (forms[2] > 0)
    return out, forms


def _lbl(label):
    return f":{label}" if label else ""


REPEATED = [0, 0, 3, 5, 5, 5, 8, 99]  # 99: no such node


def _match(hops, direction, label, frontier):
    left, right = {"fwd": ("-", "->"), "bwd": ("<-", "-"), "und": ("-", "-")}[direction]
    path = "".join(
        f"{left}[:{TYPES[j]}]{right}(n{j + 1}{_lbl(label)})" for j in range(hops)
    )
    head = {
        "whole": f"MATCH (n0{_lbl(label)})",
        "filtered": f"MATCH (n0{_lbl(label)}) WHERE n0.i % 3 <> 0 WITH n0 MATCH (n0)",
        "repeated": f"UNWIND {REPEATED} AS x MATCH (n0{_lbl(label)} {{i: x}}) WITH n0 MATCH (n0)",
    }[frontier]
    return head + path


def _numpy_count(small, hops, direction, label, frontier):
    w = np.ones(N, dtype=np.int64)
    for j in reversed(range(hops)):
        a = small["adj"][TYPES[j]]
        m = {"fwd": a, "bwd": a.T, "und": a + a.T - np.diag(np.diag(a))}[direction]
        w = m @ (small["carries"][label] * w)
    i = np.arange(N)
    f = {
        "whole": np.ones(N, dtype=np.int64),
        "filtered": (i % 3 != 0).astype(np.int64),
        "repeated": np.bincount([x for x in REPEATED if x < N], minlength=N),
    }[frontier] * small["carries"][label]
    return int(f @ w)


def _expected_forms(far_labels, whole):
    """The issue's rule, written out over the far labels in the order the
    hops are executed (the far end first): the deepest hop is the degree
    vector unless a partial label masks its weights; the hop next to a whole
    frontier is a gather and a sum; everything else scans."""
    out = []
    for j, label in enumerate(far_labels):
        if j == 0 and label != "H":
            out.append("degree")
        elif whole and j == len(far_labels) - 1:
            out.append("reduce")
        else:
            out.append("scan")
    return tuple(out.count(f) for f in FORMS)


# the label every pattern node carries; one label on every node leaves the
# planner no cheaper end, so the chain starts at n0 (a pattern labelled at
# one end only may be walked from the middle: test_chain_program has those)
LABELS = {"no_label": "", "label_on_all": "V", "label_on_half": "H"}

CASES = list(itertools.product(
    (1, 2, 3), ("fwd", "bwd", "und"), LABELS, ("whole", "filtered", "repeated"),
    ("off", "pow2"),
))


@pytest.mark.parametrize(
    "hops,direction,labels,frontier,bucket", CASES,
    ids=["-".join(map(str, c)) for c in CASES],
)
def test_chain_count_and_forms(small, hops, direction, labels, frontier, bucket):
    label = LABELS[labels]
    match = _match(hops, direction, label, frontier)
    want = _numpy_count(small, hops, direction, label, frontier)
    key = (hops, direction, labels, frontier)
    if key not in small["witness"]:  # the same for both bucket modes
        oracle = small["local"].cypher(match + " RETURN count(*) AS c")
        rows = small["tpu"]["off"].cypher(
            match + f" RETURN id(n0) AS a, id(n{hops}) AS z")
        small["witness"][key] = (
            oracle.records.collect()[0]["c"], len(rows.records.collect()))
    assert small["witness"][key] == (want, want)

    bucketing.MODE.set(bucket)
    try:
        result = small["tpu"][bucket].cypher(match + " RETURN count(*) AS c")
        got, forms = _forms_of(lambda: result.records.collect()[0]["c"])
    finally:
        bucketing.MODE.reset()
    assert got == want
    whole = frontier == "whole" and label != "H"
    assert forms == _expected_forms([label] * hops, whole), match


def _csr(a, lanes):
    """Dense multiplicity matrix -> (row_ptr, col_idx) as the index builds
    them: int32, rows past the logical nodes empty, ``col_idx`` tail-padded
    with -1 to ``lanes``."""
    n_pad = 16
    src, dst = np.nonzero(a)
    reps = a[src, dst]
    src, dst = np.repeat(src, reps), np.repeat(dst, reps)
    rp = np.searchsorted(src, np.arange(n_pad + 1)).astype(np.int32)
    ci = np.full(lanes, -1, dtype=np.int32)
    ci[: len(dst)] = dst
    return rp, ci


PROGRAM_CASES = [
    (direction, masks, whole)
    for hops in (1, 2, 3)
    for direction in ("fwd", "bwd", "und")
    for masks in itertools.product([False, True], repeat=hops)
    for whole in (False, True)
]


@pytest.mark.parametrize(
    "direction,masks,whole", PROGRAM_CASES,
    ids=[f"{d}-{''.join('m' if x else '-' for x in m)}-{'whole' if w else 'ids'}"
         for d, m, w in PROGRAM_CASES],
)
def test_chain_program(direction, masks, whole):
    """``path_count_chain`` itself over every pattern of partial masks (in
    pattern order, the frontier's hop first), with pad nodes and pad edges:
    what no query reaches deterministically — a whole frontier under
    partial far labels — is reached here."""
    import jax.numpy as jnp

    _, _, adj, even = _small()
    n_pad = 16
    label = np.zeros(n_pad, dtype=bool)
    label[:N] = even
    hops, w = [], np.ones(N, dtype=np.int64)
    for j in reversed(range(len(masks))):
        a = adj[TYPES[j]]
        m = {"fwd": a, "bwd": a.T, "und": a + a.T - np.diag(np.diag(a))}[direction]
        w = m @ (even * w if masks[j] else w)
        mask = jnp.asarray(label) if masks[j] else None
        if direction == "und":
            loops = np.zeros(n_pad, dtype=np.int64)
            loops[:N] = np.diag(a)
            hop = (*map(jnp.asarray, _csr(a, 32)), *map(jnp.asarray, _csr(a.T, 32)),
                   jnp.asarray(loops), mask)
        else:
            hop = (*map(jnp.asarray, _csr(m, 32)), None, None, None, mask)
        hops.insert(0, hop)
    dev_ids = np.full(n_pad, (1 << 62) - 1, dtype=np.int64)
    dev_ids[:N] = np.arange(N) * 7 + (1 << 41)
    if whole:
        got = J.path_count_chain(None, None, None, tuple(hops), num_nodes=n_pad, whole=True)
        want = int(w.sum())
    else:
        picked = np.array([0, 0, 3, 5, 5, 5, 8])
        ids = np.concatenate([dev_ids[picked], [12345]])  # one id of no node
        got = J.path_count_chain(
            jnp.asarray(dev_ids), jnp.asarray(ids), None, tuple(hops), num_nodes=n_pad)
        want = int(w[picked].sum())
    assert got.dtype == jnp.int64 and int(got) == want


@pytest.mark.parametrize("bucket", ["off", "pow2"])
def test_empty_graph_counts_zero(bucket):
    none = np.zeros(0, dtype=np.int64)
    bucketing.MODE.set(bucket)
    try:
        g = _tpu_graph([(("V",), none, none)], [("T1", none, none), ("T2", none, none)])
        for q in (
            "MATCH (a:V)-[:T1]->(b:V) RETURN count(*) AS c",
            "MATCH (a:V)-[:T1]->(b:V)-[:T2]->(c:V) RETURN count(*) AS c",
            "MATCH (a)-[:T1]-(b)-[:T2]-(c) RETURN count(*) AS c",
        ):
            assert g.cypher(q).records.collect()[0]["c"] == 0
    finally:
        bucketing.MODE.reset()


@pytest.mark.parametrize("bucket", ["off", "pow2"])
def test_star_two_hop_count_needs_64_bits(bucket):
    """300,001 spokes into a hub and as many out of it: 300,001**2 two-hop
    paths, past 2**32 — every lane of the chain is 32-bit (degrees), the
    sum is not. 600,002 edge lanes (2**20 under ``pow2``) also make the
    gather-and-sum walk its lanes in steps and stop at the last real edge."""
    m = 300_001
    assert 2 * m > 2 * J._EDGE_CHUNK
    ids = np.arange(2 * m + 1, dtype=np.int64) + (1 << 33)
    hub = np.full(m, ids[0])
    bucketing.MODE.set(bucket)
    try:
        g = _tpu_graph(
            [(("V",), ids, ids - ids[0])],
            [("T1", np.concatenate([ids[1:m + 1], hub]),
              np.concatenate([hub, ids[m + 1:]]))],
        )
        got, forms = _forms_of(
            lambda: g.cypher(
                "MATCH (a:V)-[:T1]->(b:V)-[:T1]->(c:V) RETURN count(*) AS c"
            ).records.collect()[0]["c"])
    finally:
        bucketing.MODE.reset()
    assert got == m * m > 1 << 32
    assert forms == (1, 1, 0)  # degree + reduce, no scan


@pytest.fixture
def small_steps(monkeypatch):
    monkeypatch.setattr(J, "_EDGE_CHUNK", 8)


@pytest.mark.parametrize("real", [0, 1, 7, 8, 9, 16, 27, 32, 37])
def test_edge_sum_stops_with_the_real_edges(small_steps, real):
    """``_edge_sum`` over 37 lanes in steps of 8 (four steps and a tail of
    5): the same sum wherever the real edges end, inside a step, on its
    boundary, nowhere, or past the steps in the tail."""
    import jax.numpy as jnp

    rng = np.random.default_rng(real)
    w = rng.integers(0, 1 << 31, 16).astype(np.int32)
    ci = np.full(37, -1, dtype=np.int32)
    ci[:real] = rng.integers(0, 16, real)
    got = J._edge_sum(jnp.asarray(ci), jnp.asarray(w), jnp.int32(real))
    assert got.dtype == jnp.int64
    assert int(got) == int(w[ci[:real]].astype(np.int64).sum())


@pytest.mark.parametrize("real", [0, 5, 32, 33, 100, 128])
def test_sharded_edge_sum_stops_in_every_shard(small_steps, real):
    """The mesh form: four shards of 32 lanes, each walking its own lanes
    in steps of 8 up to its own last real edge (none at all in a shard
    past the edges), one scalar summed over the mesh."""
    import jax
    import jax.numpy as jnp

    from tpu_cypher.parallel.mesh import make_row_mesh, shard_rows, use_mesh

    mesh = make_row_mesh(jax.devices()[:4])
    rng = np.random.default_rng(real)
    w = rng.integers(0, 1 << 31, 16).astype(np.int32)
    ci = np.full(128, -1, dtype=np.int32)
    ci[:real] = rng.integers(0, 16, real)
    rp = np.full(17, real, dtype=np.int32)
    rp[0] = 0
    with use_mesh(mesh):
        ci_sharded = shard_rows(jnp.asarray(ci))
    got = J._sharded_edge_sum(mesh, mesh.axis_names[0])(
        jnp.asarray(rp), ci_sharded, jnp.asarray(w))
    assert int(got) == int(w[ci[:real]].astype(np.int64).sum())


@pytest.mark.parametrize("whole", [False, True])
@pytest.mark.parametrize(
    "masked", list(itertools.product([False, True], repeat=3)),
    ids=lambda m: "".join("m" if x else "-" for x in m),
)
def test_chain_forms_rule(masked, whole):
    """``jit_ops.chain_forms`` itself, over every mask pattern of three
    hops: a scan-free chain needs a whole frontier and at most two hops."""
    forms = J.chain_forms(masked, whole)
    assert len(forms) == 3
    assert (forms[0] == "degree") == (not masked[0])
    assert forms[1] == "scan"
    assert forms[2] == ("reduce" if whole else "scan")
    assert J.chain_forms(masked[:2], True)[1] == "reduce"
    assert J.chain_forms(masked[:1], True)[0] == ("reduce" if masked[0] else "degree")
