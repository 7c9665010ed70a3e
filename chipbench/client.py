"""The wire client and the one general load generator.

A traffic mix is data (``traffic/<mix>.json``); this file turns it and a
seed into requests and sends them over ``QueryServer``'s newline-JSON
protocol from ``clients`` connections, one request in flight on each (a
closed loop). Every seed gives the same multiset of work in another order:
a client walks shuffled cycles of the mix's shapes, each as often as its
weight says, and each shape's pool of parameter sets round and round.

``order``:
  ``weighted`` — the window closes at ``--seconds``; requests in flight are
  waited for (they belong to the window, and so does the wait).
  ``pass`` — the shapes in the order listed are one pass; the window closes
  when the pass in flight at ``--seconds`` ends, so it holds whole passes.
"""

from __future__ import annotations

import asyncio
import importlib.util
import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ANSWER_WAIT_S = 60.0  # how long past the close an answer is waited for


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module of its own."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Request:
    client: int
    shape: str
    slot: int  # position in the shape's pool
    qid: str
    submitted: float = 0.0  # host clock, seconds
    finished: Optional[float] = None
    rows: Optional[List[dict]] = None
    done: Optional[Dict[str, Any]] = None  # the terminal message
    right: bool = False  # set by the comparison: equal to the reference

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.finished is None else self.finished - self.submitted


@dataclass
class Mix:
    """A traffic file, its shapes loaded and its parameter pools drawn."""

    spec: Dict[str, Any]
    shapes: Dict[str, Any] = field(default_factory=dict)
    pools: Dict[str, List[dict]] = field(default_factory=dict)

    @classmethod
    def load(cls, name: str) -> "Mix":
        with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
            spec = json.load(f)
        mix = cls(spec)
        for entry in spec["shapes"]:
            mix.shapes[entry["shape"]] = load_module("shapes", entry["shape"])
        return mix

    @property
    def clients(self) -> int:
        return int(self.spec["clients"])

    @property
    def in_passes(self) -> bool:
        return self.spec["order"] == "pass"

    def draw(self, ref, seed: int) -> None:
        """The pools of parameter sets, from ``--seed``."""
        for k, entry in enumerate(self.spec["shapes"]):
            rng = np.random.default_rng([seed, k])
            self.pools[entry["shape"]] = [
                self.shapes[entry["shape"]].draw_params(
                    ref, rng, **entry.get("draw", {})
                )
                for _ in range(int(entry.get("pool", 1)))
            ]

    def every_request(self) -> List[tuple]:
        """Each (shape, slot) once, shapes interleaved: the warm-up's list."""
        longest = max(len(p) for p in self.pools.values())
        return [
            (e["shape"], slot)
            for slot in range(longest)
            for e in self.spec["shapes"]
            if slot < len(self.pools[e["shape"]])
        ]

    def schedule(self, seed: int, client: int) -> Iterator[tuple]:
        """The endless (shape, slot) sequence of one client."""
        entries = self.spec["shapes"]
        names = [e["shape"] for e in entries]
        rng = np.random.default_rng([seed, 1_000_003, client])
        cursor = {
            n: int(rng.integers(len(self.pools[n]))) for n in names
        }
        cycle = [n for e, n in zip(entries, names) for _ in range(int(e.get("weight", 1)))]
        while True:
            order = cycle if self.in_passes else list(rng.permutation(cycle))
            for name in order:
                slot = cursor[name]
                cursor[name] = (slot + 1) % len(self.pools[name])
                yield name, slot


class Connection:
    """One client connection that stays open, one request at a time."""

    def __init__(self, host: str, port: int, graph: str, annotate: Callable):
        self.host, self.port, self.graph = host, port, graph
        self.annotate = annotate
        self.reader = self.writer = None

    async def __aenter__(self) -> "Connection":
        self.reader, self.writer = await asyncio.open_connection(
            self.host, self.port, limit=1 << 26
        )
        return self

    async def __aexit__(self, *exc) -> None:
        self.writer.close()
        try:
            await self.writer.wait_closed()
        except (ConnectionError, OSError):
            pass

    async def send(self, req: Request, query: str, params: dict) -> Request:
        msg = {"op": "submit", "id": req.qid, "graph": self.graph,
               "query": query, "parameters": params, "tenant": "chipbench"}
        data = (json.dumps(msg) + "\n").encode()
        with self.annotate(f"q:{req.shape}"):
            req.submitted = time.perf_counter()
            self.writer.write(data)
            await self.writer.drain()
            rows: List[dict] = []
            while True:
                line = await self.reader.readline()
                if not line:  # the server closed: this answer never comes
                    return req
                m = json.loads(line)
                if m["type"] == "rows":
                    rows.extend(m["rows"])
                elif m["type"] in ("done", "error", "cancelled"):
                    req.finished = time.perf_counter()
                    req.rows, req.done = rows, m
                    return req


async def http_get(host: str, port: int, path: str) -> str:
    reader, writer = await asyncio.open_connection(host, port, limit=1 << 26)
    try:
        writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
        await writer.drain()
        data = await reader.read()
    finally:
        writer.close()
        await writer.wait_closed()
    head, _, body = data.partition(b"\r\n\r\n")
    if b" 200 " not in head.split(b"\r\n", 1)[0]:
        raise RuntimeError(f"GET {path}: {head[:80]!r}")
    return body.decode()


def parse_counters(text: str) -> Dict[str, float]:
    """{series with labels: value} of a Prometheus text page."""
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, _, val = line.rpartition(" ")
            try:
                out[key] = float(val)
            except ValueError:
                pass
    return out


async def warm_up(mix: Mix, host, port, graph, annotate) -> List[Request]:
    """Every (shape, slot) once: first one of each shape, one after the
    other (lazily built indexes are built once, not by every lane at the
    same time), then the rest from the mix's own number of connections."""
    todo = mix.every_request()
    first = [t for t in todo if t[1] == 0]
    rest = [t for t in todo if t[1] != 0]
    sent: List[Request] = []

    async def worker(i: int, items: List[tuple]):
        async with Connection(host, port, graph, annotate) as conn:
            for k, (shape, slot) in enumerate(items):
                req = Request(i, shape, slot, f"warm-{i}-{shape}-{slot}-{k}")
                sent.append(req)
                await conn.send(
                    req, mix.shapes[shape].QUERY, mix.pools[shape][slot]
                )

    await worker(0, first)
    n = mix.clients
    await asyncio.gather(*(worker(i + 1, rest[i::n]) for i in range(n)))
    return sent


async def run_window(
    mix: Mix, seed: int, seconds: float, host, port, graph, annotate,
    on_pass: Optional[Callable] = None,
) -> tuple:
    """Drive the mix for ``seconds``; returns (requests, window seconds,
    whole passes or None). ``on_pass(k)`` is awaited before pass ``k`` of a
    one-client pass mix (the harness starts and stops its trace there)."""
    requests: List[Request] = []
    per_pass = len(mix.spec["shapes"])
    passes = [0] * mix.clients
    t0 = time.perf_counter()

    async def client(i: int):
        async with Connection(host, port, graph, annotate) as conn:
            for k, (shape, slot) in enumerate(mix.schedule(seed, i)):
                if mix.in_passes and k % per_pass == 0:
                    passes[i] = k // per_pass
                    if on_pass is not None:
                        await on_pass(passes[i])
                may_close = not mix.in_passes or k % per_pass == 0
                if may_close and time.perf_counter() - t0 >= seconds:
                    return
                req = Request(i, shape, slot, f"w-{i}-{k}")
                requests.append(req)
                try:
                    await asyncio.wait_for(
                        conn.send(req, mix.shapes[shape].QUERY,
                                  mix.pools[shape][slot]),
                        timeout=seconds + ANSWER_WAIT_S,
                    )
                except asyncio.TimeoutError:
                    return  # never came: the comparison counts it
                if req.finished is None:
                    return

    await asyncio.gather(*(client(i) for i in range(mix.clients)))
    window_s = time.perf_counter() - t0
    return requests, window_s, (min(passes) if mix.in_passes else None)
