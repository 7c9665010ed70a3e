"""Fault-isolated multi-process serving: ``ClusterServer``.

``QueryServer`` (PR 6) multiplexes tenants onto one warm engine in ONE
process — one native device abort (libtpu takes the process down, no
Python unwinding) and every tenant is gone. ``ClusterServer`` keeps the
entire front half of that server — protocol, admission scheduling,
micro-batching, HTTP observability — and swaps exactly one method:
``_execute_payload`` routes to a supervised engine-worker PROCESS
(``serve/worker.py``) through the router instead of running in-process.

The blast radius of a crash becomes one worker's in-flight queries, and
even those are transparently retried on a surviving replica
(``serve/router.py``; rung ``"replica"`` in the execution log). What
stays shared across workers is exactly what is safe to share: the
persistent XLA compile cache on disk — N processes, one set of compile
artifacts, so worker N's warmup (and every crash restart) loads instead
of recompiling.

Graphs are REPLICATED, not shared: ``register_graph`` takes the CREATE
query text and every worker builds its own copy (device buffers cannot
cross process boundaries; the text is the portable form). The front end
keeps a replica too, for cost estimation and batching keys — on the HOST
backend: a chip belongs to one process, and it belongs to a worker, so the
router process never initialises the device. The same
deferral applies to ``warmup``: the corpus is recorded and each worker
runs it at boot — readiness is warmup-gated per worker.

Sizing: each worker is its own engine with ``lanes`` execution lanes, so
the cluster's admission ceiling defaults to ``max_concurrent x workers``
— the scheduler admits what the fleet can actually run.
"""

from __future__ import annotations

import asyncio
import time
from typing import Any, Dict, List, Optional

from ..backend.tpu import bucketing
from ..relational.session import CypherSession
from ..storage.wal import wal_directory
from ..utils.config import (
    SERVE_DRAIN_TIMEOUT_S,
    SERVE_MAX_CONCURRENT,
    SERVE_WORKERS,
)
from . import wire
from .router import Router
from .server import PAGE_ROWS, QueryServer, _Ticket
from .supervisor import SubprocessLauncher, Supervisor


class ClusterServer(QueryServer):  # shared-by: loop
    """The router front end over N supervised engine-worker processes."""

    def __init__(
        self,
        workers: Optional[int] = None,
        host: str = "127.0.0.1",
        port: Optional[int] = None,
        max_concurrent: Optional[int] = None,
        batch_window_ms: Optional[float] = None,
        tenant_quota: Optional[int] = None,
        launcher=None,
        retry_max: Optional[int] = None,
        hedge_ms: Optional[float] = None,
        lanes: int = 4,
        cache_bytes: Optional[int] = None,
        wal_dir: Optional[str] = None,
    ):
        self.n_workers = max(
            int(workers if workers is not None else SERVE_WORKERS.get()), 1
        )
        if max_concurrent is None:
            # the fleet runs n_workers engines; admit what it can execute
            max_concurrent = int(SERVE_MAX_CONCURRENT.get()) * self.n_workers
        # the router never executes queries, and never holds a chip: its
        # replica session is the host backend (see the module docstring)
        super().__init__(
            session=CypherSession.local(),
            host=host, port=port, max_concurrent=max_concurrent,
            batch_window_ms=batch_window_ms, tenant_quota=tenant_quota,
            cache_bytes=cache_bytes,
        )
        # compiles nothing here: only resolves the cache directory the
        # workers will share, for the WAL default below
        bucketing.enable_persistent_cache()
        self.lanes = int(lanes)
        # where worker WAL files live (one per mutable graph); defaults to
        # 'wal/' beside the compile cache every worker shares — durability
        # artifacts ride next to the compile artifacts a restarted worker
        # re-warms from (storage.wal.wal_directory resolution)
        self.wal_dir = wal_directory(
            wal_dir, bucketing.persistent_cache_dir()
        )
        self._graph_specs: Dict[str, str] = {}
        self._mutable_graphs: set = set()
        self._warmup_specs: Dict[str, List[str]] = {}
        self._launcher = launcher
        self._retry_max = retry_max
        self._hedge_ms = hedge_ms
        self.supervisor: Optional[Supervisor] = None
        self.router: Optional[Router] = None

    # -- graphs: replicated by CREATE text -------------------------------

    def register_graph(
        self, name: str, create_query: str, mutable: bool = False
    ) -> None:  # type: ignore[override]
        """Mount a graph cluster-wide from its CREATE query text. The
        front end builds a host-backend replica too (cost estimation,
        batching keys, and the single-process protocol surface all need a
        real graph object); workers each build theirs at boot. ``mutable``
        graphs boot on the workers as delta-CSR stores sharing one WAL
        file under ``wal_dir`` — the front-end replica stays immutable
        (it never executes queries; its fingerprint is refreshed from
        each write payload)."""
        self._graph_specs[name] = create_query
        if mutable:
            self._mutable_graphs.add(name)
        graph = self.session.create_graph_from_create_query(create_query)
        super().register_graph(name, graph)

    def warmup(self, queries, graph_name: str,
               parameters: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:  # type: ignore[override]
        """Record the warmup corpus for the workers (each runs it at boot,
        gating its own readiness). The front end does NOT execute it — the
        router never executes queries locally."""
        qs = list(queries)
        self._warmup_specs.setdefault(graph_name, []).extend(qs)
        return {"queries": len(qs), "deferred": True}

    # -- lifecycle -------------------------------------------------------

    async def start(self) -> None:
        if self._launcher is None:
            self._launcher = SubprocessLauncher(
                self._graph_specs, self._warmup_specs,
                host=self.host, lanes=self.lanes,
                mutable=sorted(self._mutable_graphs),
                wal_dir=self.wal_dir,
                assign_chips=self.n_workers > 1,
            )
        canary = None
        if self._graph_specs:
            # a cheap known-good read on the first mounted graph: what the
            # supervisor executes to PROVE a worker ready (breaker close,
            # restart completion)
            first = sorted(self._graph_specs)[0]
            canary = (first, "MATCH (n) RETURN count(n) AS n")
        self.supervisor = Supervisor(
            self._launcher, self.n_workers, canary=canary
        )
        self.router = Router(
            self.supervisor, retry_max=self._retry_max,
            hedge_ms=self._hedge_ms,
        )
        await self.supervisor.start()
        await super().start()

    async def stop(self) -> None:
        await super().stop()
        if self.supervisor is not None:
            await self.supervisor.stop()

    async def drain(self, timeout: Optional[float] = None) -> None:
        """Graceful cluster drain: stop admitting (typed rejections), let
        in-flight queries finish, then ask every worker to exit."""
        budget = float(
            timeout if timeout is not None else SERVE_DRAIN_TIMEOUT_S.get()
        )
        await super().drain(budget)
        if self.supervisor is not None:
            await self.supervisor.drain(budget)

    # -- the execution hook ----------------------------------------------

    async def _execute_payload(self, t: _Ticket, graph) -> Dict[str, Any]:
        t0 = time.perf_counter()
        payload = await self.router.submit(
            graph=t.graph_name, query=t.query, parameters=t.parameters,
            tenant=t.tenant, deadline_s=self._remaining_s(t), faults=t.faults,
            qid=t.qid,
        )
        # ONE ``route`` span round the whole trip to the worker and back.
        # The engine ran in another process on another perf_counter: its
        # rendered tree hangs under the span with its own offsets, and no
        # clock is claimed across the two processes
        route = self._stage(t.dispatch, "route", t0)
        remote = (payload.get("profile") or {}).get("root")
        if remote:
            route.remote = {**remote, "clock": "worker"}
        return payload

    async def _open_stream(self, t: _Ticket, graph):
        """Cursor streaming over the cluster: route the query like any
        other (retry/hedging/breakers all apply), then page the payload
        the worker necessarily returned whole — the worker wire protocol
        is one-shot. The cursor protocol stays identical to the
        single-process server; only the front-end memory ceiling differs
        (one full payload instead of one chunk)."""
        payload = await self._execute_payload(t, graph)
        rows = payload.pop("rows", [])
        meta = dict(payload)
        meta["total_rows"] = len(rows)
        return meta, wire.ListPages(rows, page_rows=PAGE_ROWS)

    async def _flush_caches(self) -> int:
        """Flush the front-end cache AND every reachable worker's — the
        ``/cache/flush`` endpoint must leave no replica serving stale
        results."""
        dropped = self.cache.flush()
        workers = list(self.supervisor.workers) if self.supervisor else []
        for w in workers:
            if not w.available:
                continue
            try:
                reply = await wire.request(
                    w.host, w.port, {"op": "cache_flush"}, timeout=5.0
                )
                dropped += int(reply.get("flushed") or 0)
            except (OSError, EOFError, asyncio.TimeoutError):
                pass  # fault-ok: a dead worker's cache dies with it
        return dropped
