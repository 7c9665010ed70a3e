#!/usr/bin/env python3
"""chipbench — the on-chip benchmark of tpu-cypher's served read path.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that holds the chip. It makes the cell's data from ``--seed``
(the configuration's generator), loads it through the program's public
ingest (the configuration's loader), starts
``QueryServer`` on an ephemeral port, warms exactly the cell's own requests
over the wire, then drives the cell's traffic for ``--seconds`` and compares
every answer of that window with the NumPy reference (``reference.py``).
The last line of stdout is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``breakdown`` when traced) and, last,
``compared``: each number compared beside its limit. They are also the last
lines of stderr.

Everything that belongs to one cell is data found by name: the cell in
``BENCHMARK.json`` names its configuration (``configs/``) and its traffic
(``traffic/``), the traffic its shapes (``shapes/``); each metric of
``BENCHMARK.json`` has ``metrics/<name>.json``, which names its reader
(``readers/``). This file holds no cell's, mix's, shape's or metric's name.

Off the chip it fails: exit code 3 and no result line. ``--rehearse-cpu
SHARE`` is the rehearsal (tests, the sandbox): the CPU, at that share of the
configuration's persons and knows; it names the CPU and prints no metric at
all. ``--control NAME`` (the builder's
runs and the tests, never the driver's) puts that control of the reference
in the program's place after a real window and reports what the comparison
says of it; the window's metrics are the program's own.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python lets us

import argparse
import asyncio
import contextlib
import glob
import importlib
import json
import os
import shutil
import sys
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

import client as wire_client  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402

OUT_DIR = os.path.join(HERE, "out")
GRAPH = "snb"
RECORDS_SAMPLED = 256  # /queries keeps the last 512 records


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts: str) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclass
class Window:
    """What a metric's reader may read: one measured window."""

    requests: List[wire_client.Request]
    seconds: float
    passes: Optional[int]
    answered_right: int
    stages: Dict[str, float]  # QueryServer.stages, the window's difference
    counters: Dict[str, float]  # /metrics, the window's difference
    trace: Optional[trace_reduce.Trace]
    memory_peak_bytes: Optional[int]
    setup_s: float
    config: Dict[str, Any]
    sizes: Dict[str, int]
    device_kind: str

    def peaks(self) -> Dict[str, float]:
        table = load_json(HERE, "peaks.json")["peaks"]
        if self.device_kind not in table:
            raise KeyError(
                f"no peak table entry for device_kind {self.device_kind!r} "
                f"(known: {sorted(table)}): add its published peaks with "
                "their source to chipbench/peaks.json"
            )
        return table[self.device_kind]

    def roofline(self, name: str):
        return wire_client.load_module("rooflines", name)


def find_cell(bench: dict, workload: str):
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"chipbench: no workload {workload!r} in BENCHMARK.json "
            f"(have: {sorted(cells)})"
        )
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, load_json(ROOT, config["file"])


def metrics_of(bench: dict, kind: str, workload: str) -> List[dict]:
    return [
        m for m in bench[kind]
        if "workloads" not in m or workload in m["workloads"]
    ]


def claim_device(chips: int, rehearse: bool) -> dict:
    """What JAX found. No accelerator, or fewer chips than the cell asks
    for, ends the run here: no fallback, no result."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if rehearse:
        if dev.platform != "cpu":
            raise SystemExit("chipbench: a rehearsal runs on the CPU only")
    elif dev.platform != "tpu" or len(devices) < chips:
        sys.stderr.write(
            f"chipbench: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} device(s) of platform {dev.platform!r}. This is "
            "a chip benchmark and has no CPU fallback\n"
        )
        raise SystemExit(3)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak(chips: int) -> Optional[int]:
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()[:chips]
    ]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def difference(after: Dict[str, float], before: Dict[str, float]):
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


class Tracer:
    """Traces one slice of the window into a directory of the checkout and
    reduces it once the window has closed."""

    def __init__(self, spec: dict, workload: str, keep: bool = False):
        self.spec, self.keep = spec, keep
        self.dir = os.path.join(OUT_DIR, "trace", workload)
        self._slice = None

    async def _start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(
            None, lambda: jax.profiler.start_trace(
                self.dir, profiler_options=options)
        )
        self._slice = jax.profiler.TraceAnnotation(trace_reduce.SLICE_NAME)
        self._slice.__enter__()

    async def _stop(self) -> None:
        import jax

        self._slice.__exit__(None, None, None)
        self._slice = None
        await asyncio.get_running_loop().run_in_executor(
            None, jax.profiler.stop_trace
        )

    async def on_pass(self, k: int) -> None:
        """Pass mixes: trace ``passes`` whole passes after ``skip_passes``."""
        skip = int(self.spec.get("skip_passes", 1))
        if k == skip:
            await self._start()
        elif k == skip + int(self.spec.get("passes", 1)):
            await self._stop()

    async def by_the_clock(self) -> None:
        """Other mixes: ``seconds`` of the window from ``start_s`` on."""
        await asyncio.sleep(float(self.spec.get("start_s", 2.0)))
        await self._start()
        await asyncio.sleep(float(self.spec.get("seconds", 5.0)))
        await self._stop()

    def reduce(self) -> Optional[trace_reduce.Trace]:
        found = glob.glob(
            os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb")
        )
        try:
            return trace_reduce.reduce_trace(found[0]) if found else None
        finally:
            if not self.keep:
                shutil.rmtree(self.dir, ignore_errors=True)


def compare(mix, ref, requests, counters) -> Dict[str, int]:
    """Every answer of the window against the reference, one by one."""
    memo: Dict[tuple, list] = {}
    wrong = unanswered = off_device = 0
    for r in requests:
        if r.done is None or r.done.get("type") != "done":
            unanswered += 1
            continue
        key = (r.shape, r.slot)
        if key not in memo:
            memo[key] = mix.shapes[r.shape].reference(
                ref, mix.pools[r.shape][r.slot]
            )
        r.right = r.rows == memo[key]
        wrong += not r.right
        off_device += (
            r.done.get("rungs") != ["device"] or bool(r.done.get("degraded"))
            or bool(r.done.get("cached"))
        )
    fallbacks = int(sum(
        v for k, v in counters.items()
        if k.startswith("tpu_cypher_fallbacks_total")
    ))
    return {"wrong_answers": wrong, "unanswered": unanswered,
            "off_device_rung": off_device, "host_fallbacks": fallbacks}


def check_records(records: List[dict]) -> int:
    """The sampled /queries records: how many were not the device rung
    alone, had a fallback, or ran a Pallas kernel in the interpreter."""
    bad = 0
    for rec in records:
        log = rec.get("execution_log") or []
        ok = len(log) == 1 and log[0].get("ok") and log[0].get("rung") == "device"
        ok = ok and not rec.get("fallbacks")
        ok = ok and "pallas-interpret" not in json.dumps(rec.get("profile"))
        bad += not ok
    return bad


async def serve_and_measure(args, cell, mix, session, graph):
    """Server up, warm-up, the window, server down. Returns what the
    comparison and the readers need."""
    import jax

    from tpu_cypher.serve import QueryServer

    annotate = jax.profiler.TraceAnnotation
    tracer = Tracer(mix.spec.get("trace_slice", {}), cell["name"],
                    args.keep_trace) if args.trace else None
    server = QueryServer(session, port=0, **mix.spec.get("server", {}))
    server.register_graph(GRAPH, graph)
    async with server:
        host, port = server.host, server.port
        t0 = time.perf_counter()
        warm = await wire_client.warm_up(mix, host, port, GRAPH, annotate)
        cold = [r for r in warm if r.done is None or r.done["type"] != "done"]
        if cold:
            raise SystemExit(
                f"chipbench: {len(cold)} warm-up requests failed, first: "
                f"{cold[0].shape} {cold[0].done}"
            )
        say(f"warm-up: {len(warm)} requests in "
            f"{time.perf_counter() - t0:.1f}s")

        before = wire_client.parse_counters(
            await wire_client.http_get(host, port, "/metrics"))
        stages_before = dict(server.stages)
        setup_s = time.perf_counter() - T_START
        clock = None
        if tracer and not mix.in_passes:
            clock = asyncio.ensure_future(tracer.by_the_clock())
        requests, window_s, passes = await wire_client.run_window(
            mix, args.seed, args.seconds, host, port, GRAPH, annotate,
            on_pass=tracer.on_pass if tracer and mix.in_passes else None,
        )
        if clock is not None:
            await clock
        stages = difference(dict(server.stages), stages_before)
        counters = difference(
            wire_client.parse_counters(
                await wire_client.http_get(host, port, "/metrics")),
            before,
        )
        peak = memory_peak(cell["chips"])
        records = []
        for r in requests[-RECORDS_SAMPLED:]:
            if r.done is not None and r.done.get("type") == "done":
                records.append(json.loads(await wire_client.http_get(
                    host, port, f"/queries/{r.qid}")))
    return dict(requests=requests, seconds=window_s, passes=passes,
                stages=stages, counters=counters, memory_peak_bytes=peak,
                setup_s=setup_s, records=records, tracer=tracer)


def run(args) -> int:
    bench = load_json(ROOT, "BENCHMARK.json")
    cell, config = find_cell(bench, args.workload)
    mix = wire_client.Mix.load(cell["traffic"])
    rehearse = args.rehearse_cpu is not None

    for key, value in config.get("env", {}).items():
        os.environ[key] = str(value)
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    else:
        os.environ.setdefault(
            "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache")
        )
    device = claim_device(cell["chips"], rehearse)
    say(f"device: {device}")

    from tpu_cypher import CypherSession
    from tpu_cypher.relational.session import PropertyGraph

    t0 = time.perf_counter()
    share = args.rehearse_cpu if rehearse else 1.0
    persons = max(int(config["persons"] * share), 64)
    knows = int(config["knows"] * share)
    arrays = importlib.import_module(config["generator"]).snb_arrays(
        persons, knows, args.seed)
    sizes = {"persons": len(arrays["ids"]), "edges": len(arrays["src"])}
    t_gen = time.perf_counter() - t0
    session = CypherSession.tpu(**config.get("session", {}))
    session.record_fallbacks = True
    graph = PropertyGraph(
        session, importlib.import_module(config["loader"]).load(session, arrays)
    )
    say(f"data: {sizes}, seed {args.seed}; generate {t_gen:.1f}s, ingest "
        f"{time.perf_counter() - t0 - t_gen:.1f}s")
    ref = reference.Reference(arrays)
    mix.draw(ref, args.seed)

    got = asyncio.run(
        serve_and_measure(args, cell, mix, session, graph)
    )
    done_at = sorted(r.finished for r in got["requests"] if r.finished)
    stall = max((b - a for a, b in zip(done_at, done_at[1:])), default=0.0)
    moved = {k: v for k, v in got["counters"].items()
             if v and "compile" in k or "persistent_cache" in k and v}
    say(f"window: {got['seconds']:.2f}s, {len(done_at)} answers, longest "
        f"time with none {stall:.2f}s; compile counters moved: {moved}")
    tracer = got.pop("tracer")
    records = got.pop("records")
    trace = tracer.reduce() if tracer else None
    del session, graph  # the program's state goes before the reference runs

    t0 = time.perf_counter()
    requests = got["requests"]
    records_off_device = check_records(records)

    def compared_now() -> Dict[str, int]:
        return {**compare(mix, ref, requests, got["counters"]),
                "records_off_device": records_off_device}

    compared = compared_now()
    say(f"reference: {len(requests)} answers compared in "
        f"{time.perf_counter() - t0:.1f}s")
    out: Dict[str, Any] = {}
    if args.control:  # each control's answers in the program's place
        out.update(program_compared=compared, controls={})
        answered = [r for r in requests
                    if r.done is not None and r.done.get("type") == "done"]
        for name in reversed(args.control):  # the first named is judged last
            weak = reference.Reference(arrays, **reference.CONTROLS[name])
            for r in answered:
                r.rows = weak.held(mix.shapes[r.shape].reference(
                    weak, mix.pools[r.shape][r.slot]))
            compared = compared_now()
            out["controls"][name] = compared
            say(f"control {name}: {compared}")

    window = Window(
        answered_right=sum(r.right for r in requests),
        trace=trace, config=config, sizes=sizes, device_kind=device["kind"],
        **got,
    )
    metrics: Dict[str, dict] = {}
    kind = "per_layer" if args.trace else "end_to_end"
    for m in metrics_of(bench, kind, cell["name"]):
        spec = load_json(HERE, "metrics", f"{m['name']}.json")
        reader = wire_client.load_module("readers", spec["reader"])
        value = reader.read(window, **spec.get("args", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if rehearse:  # a CPU's numbers never go under a device metric's name
        say(f"rehearsal: {len(metrics)} metrics read and withheld")
        metrics = {}

    if window.memory_peak_bytes is not None:
        device["memory_peak_bytes"] = window.memory_peak_bytes
    if trace is not None:
        device["busy_s"], device["window_s"] = trace.busy_s, trace.window_s
        out["breakdown"] = trace.breakdown()
    failed = (compared["wrong_answers"] + compared["unanswered"]
              + compared["off_device_rung"])
    result = {
        "correct": all(v == 0 for v in compared.values()),
        "attempted": len(requests),
        "failed": failed,
        "metrics": metrics,
        "device": device,
        **out,
    }
    if rehearse:
        result["rehearsal"] = True
    result["compared"] = {
        k: {"value": v, "limit": 0} for k, v in compared.items()
    }
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{cell['name']}.last.json"), "w") as f:
        json.dump({"args": vars(args), "result": result,
                   "stages": window.stages, "passes": window.passes,
                   "window_s": window.seconds, "setup_s": window.setup_s}, f)
    for k, v in result["compared"].items():
        sys.stderr.write(f"compared {k}: {v['value']} (limit {v['limit']})\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", type=float, default=None, metavar="SHARE")
    ap.add_argument("--control", choices=sorted(reference.CONTROLS),
                    action="append", default=None,
                    help="may be given more than once; the result line's "
                         "verdict is the first one's")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the .xplane.pb under chipbench/out/trace/ "
                         "(how tests/recorded.xplane.pb was made)")
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
