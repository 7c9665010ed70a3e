"""The four-chip deployment (``chipbench/configs/snb-sf100-mesh4.json``) at a
small size, on four of the CPU's virtual devices: LDBC's Person/KNOWS from
``chipbench/gen_snb.py`` through ``CypherSession.tpu(mesh=4)`` behind a
``QueryServer``, the seven shapes of the mix ``analytic-mesh`` over the wire.

Each answer has to equal its shape file's NumPy ``reference`` and the
``backend/local`` oracle; the columns and the CSR have to lie on four
devices; every sharded tier has to run and be counted, nothing may be handed
back to the global path uncounted, and a join whose keys are so skewed that a
bucket of the exchange overflows is counted as a decline and still answers
right.

The size is a tenth of SF10's, not the 2% of the other rehearsals: the value
join takes the exchange (``hash_repartition_join``) only where its build
side, the whole Person table, is over the broadcast window's 4,096 rows, as
it is at the cell's own size. The oracle materialises every path, which for
the two-hop count is minutes even at 2% (9.3M rows): that one shape meets
the oracle on a graph of 300 persons, loaded into the same mesh session.
"""

import asyncio
import contextlib
import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIPBENCH = os.path.join(ROOT, "chipbench")

CHIPS = 4
PERSONS, KNOWS = 6_564, 193_851  # a tenth of LDBC SNB SF10
SMALL = (300, 3_000)  # where the oracle can walk every two-hop path
ORACLE_ON_SMALL = ("two_hop_count",)
SEED = 2_800_000_007

# the counter that has to move when the shape runs on its sharded tier
TIER_OF = {
    "two_hop_count": "tpu_cypher_mesh_expand_total",
    "one_hop_count": "tpu_cypher_mesh_expand_total",
    "grouped_aggregate": "tpu_cypher_mesh_agg_total",
    "scan_filter": None,  # compaction and the sort run under GSPMD: no tier
    "order_by_limit": None,
    "distinct_values": "tpu_cypher_mesh_distinct_total",
    "sort_probe_join": 'tpu_cypher_mesh_join_total{tier=shuffle}',
}
DECLINES = "tpu_cypher_mesh_declines_total"
# the ungrouped count(*) of these shapes is answered by the operator under
# it without its rows (``count_pushdowns.analytic`` reads 3 a pass here:
# it does not read the ``tree`` series)
PUSHDOWN = "tpu_cypher_count_pushdown_total"
PUSHDOWN_OF = {
    # the linked chain is the tree count's case without a branch (PR 34):
    # asked through ``tree_count``, answered by the chain's sharded program
    "two_hop_count": "tree",
    "one_hop_count": "tree",
    "scan_filter": "filter",
    "distinct_values": "distinct",
    "sort_probe_join": "join",
}

# every person's birthday lies in one 10**12 ms, so this key is the same
# for all of them: every row of both sides goes to one shard's bucket
SKEWED_JOIN = (
    "MATCH (a:Person) WHERE a.id < 64 WITH a MATCH (b:Person) "
    "WHERE b.birthday / 1000000000000 = a.birthday / 1000000000000 "
    "RETURN count(*) AS c"
)


def _chipbench(name):
    if CHIPBENCH not in sys.path:
        sys.path.insert(0, CHIPBENCH)
    return importlib.import_module(name)


@pytest.fixture(scope="module")
def deployment():
    import jax

    from tpu_cypher import CypherSession
    from tpu_cypher.backend.tpu import bucketing
    from tpu_cypher.parallel import mesh as PM
    from tpu_cypher.relational.session import PropertyGraph

    assert len(jax.devices()) >= CHIPS  # conftest forces eight
    with open(os.path.join(CHIPBENCH, "configs", "snb-sf100-mesh4.json")) as f:
        config = json.load(f)
    assert config["session"] == {"mesh": CHIPS} and config["chips"] == CHIPS
    snb_arrays = _chipbench("gen_snb").snb_arrays
    arrays, small = snb_arrays(PERSONS, KNOWS, SEED), snb_arrays(*SMALL, SEED)
    load = _chipbench("load_snb").load
    mix = _chipbench("client").Mix.load("analytic-mesh")
    local = CypherSession.local()
    before = PM.current_mesh()
    # the deployment's own settings: the bucket lattice and the mesh
    bucketing.MODE.set(config["env"]["TPU_CYPHER_BUCKET"])
    session = CypherSession.tpu(**config["session"])
    try:
        session.record_fallbacks = True
        scan_graph = load(session, arrays)
        yield {
            "session": session,
            "scan_graph": scan_graph,
            "graph": PropertyGraph(session, scan_graph),
            "oracle": PropertyGraph(local, load(local, arrays)),
            "ref": _chipbench("reference").Reference(arrays),
            "small": PropertyGraph(session, load(session, small)),
            "small_oracle": PropertyGraph(local, load(local, small)),
            "shapes": mix.shapes,
        }
    finally:
        PM.activate_mesh(before)  # the mesh is the process's, not the session's
        bucketing.MODE.reset()


def _moved(before, after, prefix):
    return sum(
        v - before.get(k, 0.0) for k, v in after.items() if k.startswith(prefix)
    )


def _over_the_wire(dep, query, graph="graph"):
    """(rows, the terminal message, the registry before and after) of one
    request sent to a ``QueryServer`` over the mesh session, by the
    benchmark's own wire client."""
    from tpu_cypher.obs.metrics import REGISTRY
    from tpu_cypher.serve import QueryServer

    client = _chipbench("client")

    async def run():
        server = QueryServer(dep["session"], port=0, cache_bytes=0)
        server.register_graph("snb", dep[graph])
        async with server, client.Connection(
            server.host, server.port, "snb", contextlib.nullcontext
        ) as conn:
            before = REGISTRY.flat()
            req = await asyncio.wait_for(
                conn.send(client.Request(0, "q", 0, "q"), query, {}), 300
            )
            return req.rows, req.done, before, REGISTRY.flat()

    return asyncio.run(run())


def _oracle_rows(dep, query, graph="oracle"):
    return [dict(r) for r in dep[graph].cypher(query).records.collect()]


def test_columns_and_csr_lie_on_four_devices(deployment):
    import jax

    from tpu_cypher.backend.tpu.graph_index import GraphIndex

    for scan in deployment["scan_graph"].scans:
        for name, col in scan.table._cols.items():
            assert len(col.data.sharding.device_set) == CHIPS, (name, col.data.sharding)
            rows = {s.data.shape[0] for s in col.data.addressable_shards}
            assert rows == {len(col) // CHIPS}, (name, rows)
    gi = GraphIndex.of(deployment["scan_graph"])
    ctx = deployment["session"]._runtime_context({})
    gi.node_ids(ctx)
    row_ptr, col_idx, edge_orig = gi.csr(("KNOWS",), False, ctx)
    jax.block_until_ready(col_idx)
    for what, arr in (("col_idx", col_idx), ("edge_orig", edge_orig)):
        assert len(arr.sharding.device_set) == CHIPS, (what, arr.sharding)
        assert not arr.sharding.is_fully_replicated, what
    # node-indexed vectors are every chip's own
    assert row_ptr.sharding.is_fully_replicated


@pytest.mark.parametrize("shape", list(TIER_OF))
def test_shape_over_the_wire_on_its_sharded_tier(deployment, shape):
    module = deployment["shapes"][shape]
    rows, done, before, after = _over_the_wire(deployment, module.QUERY)
    assert done["type"] == "done", done
    assert done["rungs"] == ["device"] and not done["degraded"], done
    assert rows == module.reference(deployment["ref"], {})
    if shape in ORACLE_ON_SMALL:
        small, *_ = _over_the_wire(deployment, module.QUERY, "small")
        assert small == _oracle_rows(deployment, module.QUERY, "small_oracle")
    else:
        assert rows == _oracle_rows(deployment, module.QUERY)
    assert _moved(before, after, "tpu_cypher_fallbacks_total") == 0
    assert _moved(before, after, DECLINES) == 0, {
        k: v for k, v in after.items() if k.startswith(DECLINES) and v
    }
    if TIER_OF[shape] is not None:
        assert _moved(before, after, TIER_OF[shape]) >= 1
        assert _moved(before, after, "tpu_cypher_mesh_exchange_bytes_total") > 0 \
            or shape == "one_hop_count"  # a sum of degrees: nothing to exchange
    # every host read of the mesh path is one that obs.trace.sync counts
    assert _moved(before, after, "tpu_cypher_host_syncs_total") >= 1
    asked = {
        k[len(PUSHDOWN):]: v - before.get(k, 0.0)
        for k, v in after.items()
        if k.startswith(PUSHDOWN) and v != before.get(k, 0.0)
    }
    op = PUSHDOWN_OF.get(shape)
    assert asked == ({"{op=%s,outcome=count}" % op: 1.0} if op else {})


def test_overflowing_join_is_counted_and_still_right(deployment):
    rows, done, before, after = _over_the_wire(deployment, SKEWED_JOIN)
    assert done["type"] == "done" and done["rungs"] == ["device"], done
    assert rows == _oracle_rows(deployment, SKEWED_JOIN)
    assert rows[0]["c"] > 0
    assert _moved(
        before, after, DECLINES + "{op=join,reason=overflow}"
    ) == 1
    assert _moved(before, after, "tpu_cypher_mesh_join_total") == 0
    assert _moved(before, after, "tpu_cypher_fallbacks_total") == 0
    # the one-device probe counts after the decline: still no rows
    assert _moved(before, after, PUSHDOWN + "{op=join,outcome=count}") == 1


def test_healthy_declines_are_exported_as_zeros():
    """``mesh_declines.analytic`` has to read 0, not nothing, in a sound
    run: the series exist before anything has declined."""
    import tpu_cypher.parallel.mesh  # noqa: F401  (declares the series)
    from tpu_cypher.obs.metrics import REGISTRY

    text = REGISTRY.prometheus_text()
    assert 'tpu_cypher_mesh_declines_total{op="distinct",reason="overflow"}' in text
