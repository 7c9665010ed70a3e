"""One algorithm per count shape on every platform.

The DISTINCT-endpoints count, the cycle-closing ``count(*)`` and the
var-length ``count(*)`` answer on the CPU through the programs the chip runs:
the values-only sort (``jit_ops.distinct_pairs_count_final``), the two binary
searches over the sorted edge keys (``jit_ops.into_close_count`` and its
``_unique`` form) and the var-expand frontier loop (``jit_ops.varlen_hop`` /
``varlen_emit``). Each case is differential against ``backend/local`` and
spies the dispatch. The graphs have no self-loop, so relationship uniqueness
is dropped by proof wherever the proof reaches. A last test keeps the
platform forks of the count paths counted."""

import ast
import contextlib
import pathlib

import numpy as np
import pytest

import tpu_cypher
from tpu_cypher import CypherSession
from tpu_cypher.backend.tpu import jit_ops as J

SPIED = (
    "distinct_pairs_count_final",
    "distinct_pairs_count_final_unique",
    "into_close_count",
    "into_close_count_unique",
    "into_probe",
    "varlen_hop",
    "varlen_emit",
    "mxu_close_count",
    "mxu_distinct_pairs",
)

# where each closing-probe program takes the edge keys it searches
KEYS_ARG = {"into_close_count": 6, "into_close_count_unique": 7}


def _create(seed, n, e, acyclic):
    """``n`` :P nodes, every third also :Q, ``e`` random :K edges without a
    self-loop; ``acyclic`` keeps an unbounded walk finite."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n - 1, e)
    if acyclic:
        dst = src + 1 + rng.integers(0, n, e) % (n - 1 - src)
    else:
        dst = (src + 1 + rng.integers(0, n - 1, e)) % n
    parts = [f"(n{i}:P:Q)" if i % 3 == 0 else f"(n{i}:P)" for i in range(n)]
    parts += [f"(n{s})-[:K]->(n{d})" for s, d in zip(src, dst)]
    return "CREATE " + ", ".join(parts)


CYCLIC = _create(5, 24, 90, acyclic=False)
DAG = _create(6, 16, 30, acyclic=True)

# name -> (graph, query with the far node's label left open, the spied
# programs that must have run)
SHAPES = {
    "with_distinct_pair": (
        CYCLIC,
        "MATCH (a:P)-[:K]->(b)-[:K]->(c{far}) WITH DISTINCT a, c "
        "RETURN count(*) AS n",
        {"distinct_pairs_count_final"},
    ),
    "with_distinct_far": (
        CYCLIC,
        "MATCH (a:P)-[:K]->(b)-[:K]->(c{far}) WITH DISTINCT c "
        "RETURN count(*) AS n",
        {"distinct_pairs_count_final"},
    ),
    "one_hop_distinct_pair": (
        CYCLIC,
        "MATCH (a:P)-[:K]->(c{far}) WITH DISTINCT a, c RETURN count(*) AS n",
        {"distinct_pairs_count_final"},
    ),
    "triangle": (
        CYCLIC,
        "MATCH (a:P)-[:K]->(b)-[:K]->(c{far})-[:K]->(a) RETURN count(*) AS n",
        {"into_close_count"},
    ),
    "two_cycle": (
        CYCLIC,
        "MATCH (a:P)-[:K]->(b{far})-[:K]->(a) RETURN count(*) AS n",
        {"into_close_count"},
    ),
    # r0 and r2 of a 4-cycle may bind one edge of a 2-cycle without any
    # self-loop: that uniqueness stays, enforced inside the program
    "four_cycle": (
        CYCLIC,
        "MATCH (a:P)-[:K]->(b)-[:K]->(c)-[:K]->(d{far})-[:K]->(a) "
        "RETURN count(*) AS n",
        {"into_close_count_unique"},
    ),
    "var_length_bounded": (
        DAG,
        "MATCH (a:P)-[:K*1..3]->(c{far}) RETURN count(*) AS n",
        {"varlen_hop", "varlen_emit"},
    ),
    "var_length_unbounded": (
        DAG,
        "MATCH (a:P)-[:K*]->(c{far}) RETURN count(*) AS n",
        {"varlen_hop", "varlen_emit"},
    ),
}


@contextlib.contextmanager
def spied():
    """Dispatches of the count programs while the block runs, and the
    ``keys`` each closing probe searched."""
    calls = {name: 0 for name in SPIED}
    probed = []
    saved = {name: getattr(J, name) for name in SPIED}

    def counting(name, fn):
        def wrapper(*a, **k):
            calls[name] += 1
            if name in KEYS_ARG:
                probed.append(np.asarray(a[KEYS_ARG[name]]))
            return fn(*a, **k)

        return wrapper

    for name, fn in saved.items():
        setattr(J, name, counting(name, fn))
    try:
        yield calls, probed
    finally:
        for name, fn in saved.items():
            setattr(J, name, fn)


def _rows(graph, query):
    return [dict(r) for r in graph.cypher(query).records.collect()]


@pytest.mark.parametrize("far", ["", ":Q"], ids=["any_far", "far_label"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_count_shape_runs_the_chips_program(shape, far):
    create, query, programs = SHAPES[shape]
    query = query.format(far=far)
    want = _rows(CypherSession.local().create_graph_from_create_query(create), query)
    assert want[0]["n"] > 0  # a graph that answers 0 pins nothing
    graph = CypherSession.tpu().create_graph_from_create_query(create)
    with spied() as (calls, probed):
        assert _rows(graph, query) == want
    ran = {name for name, n in calls.items() if n}
    assert ran == programs, calls
    if programs & set(KEYS_ARG):
        # one fused final hop, probing the sorted (src*N + dst) edge keys
        assert sum(calls[name] for name in KEYS_ARG) == 1
        (keys,) = probed
        assert keys.dtype == np.int64 and np.all(keys[1:] >= keys[:-1])
    elif "varlen_hop" in programs:
        # lower bound 1: every level of the frontier loop emits
        assert calls["varlen_emit"] == calls["varlen_hop"] >= 2
    else:
        assert calls["distinct_pairs_count_final"] == 1


# the functions of the device backend and of the sharded tiers that may ask
# which platform they run on: the dense MXU tier's default and the Pallas
# dispatcher's. A new name here is a new fork between what the tests run
# and what the chip runs
PLATFORM_FORKS = {
    "backend/tpu/expand_op.py:_mxu_dense_mode",
    "backend/tpu/pallas/dispatch.py:_backend_is_tpu",
}


def test_only_the_named_functions_ask_for_the_platform():
    root = pathlib.Path(tpu_cypher.__file__).parent
    files = sorted((root / "backend" / "tpu").rglob("*.py"))
    files += sorted((root / "parallel").glob("*.py"))
    assert len(files) >= 20
    found = set()

    def visit(node, where, scope):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scope = node.name
        # ``jax.default_backend`` or the bare name after a from-import
        if "default_backend" in (getattr(node, "attr", None), getattr(node, "id", None)):
            found.add(f"{where}:{scope}")
        for child in ast.iter_child_nodes(node):
            visit(child, where, scope)

    for path in files:
        where = path.relative_to(root).as_posix()
        visit(ast.parse(path.read_text()), where, "<module>")
    assert found == PLATFORM_FORKS
