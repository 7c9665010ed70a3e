"""Fault-isolated multi-process serving (``tpu_cypher/serve/cluster.py``
and friends): breaker, backoff, replica retry, drain, shed, hedging.

Two tiers of coverage:

* **fake-worker units** — ``Supervisor``/``Router`` run against in-process
  asyncio TCP servers that speak the worker wire protocol with scriptable
  behavior (die mid-query, reply slowly, reply a typed error). Everything
  above the transport interface — breaker transitions, backoff restarts,
  replica retry with the ``"replica"`` rung, hedged dispatch — is
  exercised with zero subprocess/JAX boot cost.
* **one real-subprocess end-to-end** — a ``ClusterServer`` over actual
  ``python -m tpu_cypher.serve.worker`` children: goldens match serial
  execution, an injected ``crash@site`` kills a real worker mid-query and
  the client still gets its (non-duplicated) rows, SIGKILL recovery.
"""

import asyncio
import collections
import time
import zlib

import pytest

from tpu_cypher import errors as ERR
from tpu_cypher.runtime import faults as F
from tpu_cypher.runtime import guard as G
from tpu_cypher.serve import wire
from tpu_cypher.serve.router import Router
from tpu_cypher.serve.scheduler import AdmissionScheduler
from tpu_cypher.serve.supervisor import CircuitBreaker, Supervisor
from tpu_cypher.utils import config

# ---------------------------------------------------------------------------
# fake workers: in-process asyncio servers speaking the worker protocol
# ---------------------------------------------------------------------------


def _payload(rows=({"n": 16},)):
    rows = [dict(r) for r in rows]
    cols = list(rows[0]) if rows else []
    return {
        "rows": rows, "columns": cols, "seconds": 0.001,
        "execution_log": [{"rung": "device", "ok": True}],
        "rungs": ["device"], "degraded": False,
        "compile_stats": {}, "profile": {},
    }


class FakeWorkerTransport:
    """Duck-types ``SubprocessTransport``: pid/poll/kill/wait_ready/
    wait_exit, backed by an in-process server. Behavior per ``execute`` is
    scripted by the launcher ("ok" | "die" | "slow:<s>" | "error:<Type>");
    the script list is SHARED across respawns of the same worker id, so a
    ``["die"]`` script means die once, behave ever after."""

    def __init__(self, owner, worker_id):
        self.owner = owner
        self.worker_id = worker_id
        self.host = "127.0.0.1"
        self.port = 0
        self._dead = None
        self._server = None

    @property
    def pid(self):
        return 4242

    def poll(self):
        return self._dead

    def kill(self):
        self._die(137)

    terminate = kill

    def _die(self, code):
        if self._dead is None:
            self._dead = code
            if self._server is not None:
                self._server.close()

    async def wait_exit(self, timeout=None):
        while self._dead is None:
            await asyncio.sleep(0.005)

    async def wait_ready(self, timeout):
        if self.owner.boot_fail.get(self.worker_id, 0) > 0:
            self.owner.boot_fail[self.worker_id] -= 1
            self._dead = 1
            raise EOFError(f"fake worker {self.worker_id}: scripted boot crash")
        self._server = await asyncio.start_server(
            self._handle, "127.0.0.1", 0
        )
        self.port = self._server.sockets[0].getsockname()[1]
        return {"ready": True, "port": self.port, "pid": self.pid,
                "worker": self.worker_id, "warmup": {"compiles": 0},
                "device": self.owner.devices.get(
                    self.worker_id, {"platform": "cpu", "count": 1}
                )}

    async def _handle(self, reader, writer):
        try:
            while True:
                try:
                    msg = await wire.read_msg(reader)
                except (EOFError, ConnectionError, OSError):
                    return
                if self._dead is not None:
                    return
                op = msg.get("op")
                if op == "ping":
                    await wire.send_msg(
                        writer, {"ok": True, "pong": True,
                                 "worker": self.worker_id}
                    )
                    continue
                if op == "drain":
                    await wire.send_msg(writer, {"ok": True, "draining": True})
                    self._die(0)
                    return
                # execute
                script = self.owner.scripts.get(self.worker_id)
                action = script.pop(0) if script else "ok"
                if action == "die":
                    self._die(137)
                    return  # abrupt EOF mid-conversation, like a real abort
                if action.startswith("slow:"):
                    await asyncio.sleep(float(action.split(":", 1)[1]))
                    action = "ok"
                if action.startswith("error:"):
                    await wire.send_msg(
                        writer,
                        {"id": msg.get("id"), "ok": False,
                         "error": action.split(":", 1)[1],
                         "message": "scripted failure"},
                    )
                    continue
                self.owner.executes[self.worker_id].append(msg)
                await wire.send_msg(
                    writer,
                    {"id": msg.get("id"), "ok": True,
                     "worker": self.worker_id, "payload": _payload()},
                )
        finally:
            writer.close()


class FakeLauncher:
    def __init__(self, scripts=None, boot_fail=None):
        self.scripts = scripts or {}
        self.boot_fail = boot_fail or {}
        self.devices = {}  # worker id -> the device its READY line reports
        self.spawns = collections.Counter()
        self.live = {}
        self.executes = collections.defaultdict(list)

    async def spawn(self, worker_id):
        self.spawns[worker_id] += 1
        t = FakeWorkerTransport(self, worker_id)
        self.live[worker_id] = t
        return t


def _supervisor(launcher, n=2, **kw):
    kw.setdefault("canary", ("g", "MATCH (n) RETURN count(n) AS n"))
    kw.setdefault("health_interval_s", 0.03)
    kw.setdefault("backoff_s", 0.01)
    kw.setdefault("backoff_max_s", 0.08)
    return Supervisor(launcher, n, **kw)


async def _until(cond, timeout=5.0, what="condition"):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() >= deadline:
            raise AssertionError(f"timed out waiting for {what}")
        await asyncio.sleep(0.01)


# ---------------------------------------------------------------------------
# one process per chip: assignment, and a start that fails loudly
# ---------------------------------------------------------------------------


def test_chip_environment_gives_each_worker_its_own_chip():
    from tpu_cypher.serve.supervisor import chip_environment

    envs = [chip_environment(f"w{i}") for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
    ports = {e["TPU_MESH_CONTROLLER_PORT"] for e in envs}
    assert len(ports) == 4, "each process runs its own mesh controller"


@pytest.mark.parametrize("assign", [False, True])
def test_launcher_assigns_chips_only_to_a_fleet(monkeypatch, assign):
    """A fleet's workers get one chip each IN THE CHILD'S ENVIRONMENT; a
    single worker keeps the host's default view. Nothing the launcher
    passes overrides where the environment places the compile cache."""
    from tpu_cypher.serve import supervisor as S

    seen = {}

    class _Proc:
        pid, returncode = 1, None

        class stdin:
            write = staticmethod(lambda data: seen.setdefault("cfg", data))

            @staticmethod
            async def drain():
                pass

    async def fake_exec(*cmd, env=None, **kw):
        seen["env"] = env
        return _Proc()

    monkeypatch.setattr(asyncio, "create_subprocess_exec", fake_exec)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    launcher = S.SubprocessLauncher({}, {}, assign_chips=assign)
    asyncio.run(launcher.spawn("w2"))
    env = seen["env"]
    assert env.get("TPU_VISIBLE_CHIPS") == ("2" if assign else None)
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/x"
    assert b"cache" not in seen["cfg"]


def _transport_fed(lines):
    """A ``SubprocessTransport`` over a fake child whose stdout holds
    ``lines`` and then ends."""
    from tpu_cypher.serve.supervisor import SubprocessTransport

    class _Proc:
        pid, returncode = 4242, 1

        async def wait(self):
            return self.returncode

    proc = _Proc()
    proc.stdout = asyncio.StreamReader()
    for line in lines:
        proc.stdout.feed_data(line.encode() + b"\n")
    proc.stdout.feed_eof()
    return SubprocessTransport(proc, "127.0.0.1")


def test_worker_that_reports_its_failure_surfaces_typed():
    """``{"ready": false}``: the worker's own account (its chip is held by
    another process) reaches the caller as a typed ``WorkerLost``."""

    async def run():
        t = _transport_fed([
            "libtpu noise, not JSON",
            '{"ready": false, "worker": "w1", "error": "DeviceLost", '
            '"message": "UNAVAILABLE: TPU is already in use by pid 7"}',
        ])
        with pytest.raises(ERR.WorkerLost, match="already in use") as info:
            await t.wait_ready(5.0)
        assert info.value.worker == "w1"

    asyncio.run(run())


def test_worker_that_dies_before_ready_surfaces_typed():
    async def run():
        t = _transport_fed(["some noise"])
        with pytest.raises(ERR.WorkerLost, match="exited before READY"):
            await t.wait_ready(5.0)

    asyncio.run(run())


def test_start_refuses_a_fleet_on_mixed_platforms():
    """A worker that came up on another platform than its peers (it could
    not have its chip and fell back) fails the start — typed, and with no
    child left running."""

    async def run():
        launcher = FakeLauncher()
        launcher.devices = {
            "w0": {"platform": "tpu", "count": 1},
            "w1": {"platform": "cpu", "count": 1},
        }
        sup = _supervisor(launcher, n=2)
        with pytest.raises(ERR.WorkerLost, match="different platforms"):
            await sup.start()
        assert all(t.poll() is not None for t in launcher.live.values())
        assert sup.ready_workers == [] or all(
            w.transport.poll() is not None for w in sup.workers
        )

    asyncio.run(run())


def test_start_records_the_device_each_worker_holds():
    async def run():
        launcher = FakeLauncher()
        launcher.devices = {
            "w0": {"platform": "tpu", "count": 1, "chip": "0"},
            "w1": {"platform": "tpu", "count": 1, "chip": "1"},
        }
        sup = _supervisor(launcher, n=2)
        await sup.start()
        assert [w.device["chip"] for w in sup.workers] == ["0", "1"]
        await sup.stop()

    asyncio.run(run())


def test_boot_failure_at_start_stops_the_whole_fleet():
    """A first boot that fails raises out of ``start()`` and leaves no
    sibling running (a cluster that cannot start whole says so)."""

    async def run():
        launcher = FakeLauncher(boot_fail={"w1": 1})
        sup = _supervisor(launcher, n=2)
        with pytest.raises(EOFError):
            await sup.start()
        await _until(
            lambda: all(t.poll() is not None for t in launcher.live.values()),
            what="every spawned worker stopped",
        )

    asyncio.run(run())


# ---------------------------------------------------------------------------
# circuit breaker (pure, fake clock)
# ---------------------------------------------------------------------------


def test_breaker_transitions():
    """closed -> open at the threshold -> half-open after the cooldown ->
    re-open on a failed probe -> closed on a successful one."""
    now = [0.0]
    b = CircuitBreaker(threshold=2, cooldown_s=10.0, clock=lambda: now[0])
    assert b.state == "closed" and b.allow()
    b.record_failure()
    assert b.state == "closed", "below threshold stays closed"
    b.record_failure()
    assert b.state == "open" and not b.allow()
    now[0] = 9.9
    assert b.state == "open", "cooldown not yet elapsed"
    now[0] = 10.0
    assert b.state == "half-open" and b.allow()
    b.record_failure()  # the probe failed
    assert b.state == "open", "failed probe re-opens"
    now[0] = 20.0
    assert b.state == "half-open"
    b.record_success()
    assert b.state == "closed" and b.allow()


def test_breaker_success_resets_failure_count():
    b = CircuitBreaker(threshold=3, cooldown_s=1.0)
    b.record_failure()
    b.record_failure()
    b.record_success()
    b.record_failure()
    b.record_failure()
    assert b.state == "closed", "the streak must restart after a success"


def test_breaker_state_change_hook():
    seen = []
    now = [0.0]
    b = CircuitBreaker(
        threshold=1, cooldown_s=5.0, clock=lambda: now[0],
        on_change=seen.append,
    )
    b.record_failure()
    b.record_success()
    assert seen == ["open", "closed"]


# ---------------------------------------------------------------------------
# supervisor backoff
# ---------------------------------------------------------------------------


def test_backoff_delay_doubles_and_caps():
    sup = _supervisor(FakeLauncher(), backoff_s=0.25, backoff_max_s=5.0)
    delays = [sup.backoff_delay(a) for a in range(8)]
    assert delays[:5] == [0.25, 0.5, 1.0, 2.0, 4.0]
    assert delays[5:] == [5.0, 5.0, 5.0], "capped at the configured max"


def test_supervisor_restarts_through_boot_crashes():
    """A worker that dies on arrival keeps backing off (the attempt
    counter survives failed spawns) and comes back once boots succeed;
    only the canary pass resets the attempt counter."""

    async def run():
        launcher = FakeLauncher(boot_fail={"w0": 2})
        # boot_fail only applies to RE-spawns: let the cold start succeed
        launcher.boot_fail = {}
        sup = _supervisor(launcher, n=2)
        await sup.start()
        assert len(sup.ready_workers) == 2
        launcher.boot_fail = {"w0": 2}
        launcher.live["w0"].kill()
        w0 = sup.workers[0]
        await _until(
            lambda: w0.restarts == 1 and w0.restart_attempt == 0,
            what="w0 recovery through 2 boot crashes",
        )
        assert launcher.spawns["w0"] == 4  # cold start + 2 failed + 1 good
        assert sup.total_restarts == 1
        await sup.stop()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# router: replica retry, idempotence, hedging
# ---------------------------------------------------------------------------


def test_router_replica_retry_stamps_rung_and_restarts_worker():
    """A worker dying mid-query is invisible to the client: the read
    re-dispatches to the surviving replica, rows arrive exactly once, the
    failed attempt is stamped rung="replica", and the supervisor restarts
    the corpse."""

    async def run():
        launcher = FakeLauncher()
        sup = _supervisor(launcher, n=2)
        await sup.start()
        router = Router(sup, retry_max=2, hedge_ms=0)
        victim = router._pick("tenant-a").worker_id
        launcher.scripts[victim] = ["die"]
        payload = await router.submit(
            graph="g", query="MATCH (a:P) RETURN count(a) AS n",
            tenant="tenant-a", qid="q1",
        )
        assert payload["rows"] == [{"n": 16}], "exactly once, no duplicates"
        assert payload["replica_retries"] == 1
        assert payload["worker"] != victim
        assert payload["execution_log"][0]["rung"] == G.RUNG_REPLICA
        assert payload["execution_log"][0]["worker"] == victim
        assert payload["rungs"][0] == G.RUNG_REPLICA
        assert payload["rungs"][-1] == G.RUNG_DEVICE
        # the survivor executed it exactly once
        survivor = payload["worker"]
        assert len(launcher.executes[survivor]) == 1
        assert launcher.executes[victim] == []
        await _until(
            lambda: sup.workers[int(victim[1:])].restarts == 1,
            what="victim restart",
        )
        await sup.stop()

    asyncio.run(run())


def test_router_strips_fault_spec_on_retry():
    """The chaos schedule dies with the worker it killed: the replica
    retry must NOT replay it (replaying would deterministically kill
    every replica in turn)."""

    async def run():
        launcher = FakeLauncher()
        sup = _supervisor(launcher, n=2)
        await sup.start()
        router = Router(sup, retry_max=2, hedge_ms=0)
        victim = router._pick("t").worker_id
        launcher.scripts[victim] = ["die"]
        payload = await router.submit(
            graph="g", query="q", tenant="t", faults="crash@expand:1",
        )
        survivor = payload["worker"]
        assert launcher.executes[survivor][0]["faults"] is None
        await sup.stop()

    asyncio.run(run())


def test_router_exhausted_retries_raises_worker_lost():
    async def run():
        launcher = FakeLauncher(
            scripts={"w0": ["die", "die"], "w1": ["die", "die"]}
        )
        sup = _supervisor(launcher, n=2, backoff_s=5.0)  # no quick revival
        await sup.start()
        router = Router(sup, retry_max=1, hedge_ms=0)
        with pytest.raises(ERR.WorkerLost):
            await router.submit(graph="g", query="q", tenant="t")
        await sup.stop()

    asyncio.run(run())


def test_refused_connection_restarts_unreaped_worker():
    """Right after a SIGKILL the child is not reaped: ``poll()`` is still
    None. A ConnectionRefusedError must count as dead anyway — otherwise
    the worker sits stale-READY, keeps getting picked, and burns the whole
    retry budget on one corpse."""

    async def run():
        launcher = FakeLauncher()
        sup = _supervisor(launcher, n=2)
        await sup.start()
        router = Router(sup, retry_max=2, hedge_ms=0, ready_wait_s=5.0)
        victim = sup.workers[0]
        # listener gone, process unreaped: poll() stays None
        victim.transport._server.close()
        assert victim.transport.poll() is None
        tenant = "t"
        # steer the tenant onto the corpse so the first attempt hits it
        while router._pick(tenant).worker_id != victim.worker_id:
            tenant += "x"
        payload = await router.submit(graph="g", query="q", tenant=tenant)
        assert payload["rows"] == [{"n": 16}]
        assert payload["replica_retries"] >= 1
        await _until(
            lambda: launcher.spawns[victim.worker_id] >= 2,
            what="victim respawn",
        )
        await sup.stop()

    asyncio.run(run())


def test_router_waits_out_momentarily_empty_fleet():
    """A correlated double-death (EVERY worker dead at pick time) becomes
    latency, not an error: the retry attempt waits (bounded) for the
    supervisor to bring a replica back instead of failing typed."""

    async def run():
        launcher = FakeLauncher()
        sup = _supervisor(launcher, n=2)
        await sup.start()
        router = Router(sup, retry_max=2, hedge_ms=0, ready_wait_s=5.0)
        for w in sup.workers:
            w.transport._die(1)  # both at once; respawn is ticks away
        payload = await router.submit(graph="g", query="q", tenant="t")
        assert payload["rows"] == [{"n": 16}]
        assert payload["replica_retries"] >= 1
        assert G.RUNG_REPLICA in payload["rungs"]
        await sup.stop()

    asyncio.run(run())


def test_router_typed_worker_errors_pass_through():
    """A worker replying a typed error is NOT a transport failure: no
    retry, no breaker charge — the engine error reaches the caller."""

    async def run():
        launcher = FakeLauncher()
        sup = _supervisor(launcher, n=1)
        await sup.start()
        launcher.scripts["w0"] = ["error:QueryTimeout"]
        router = Router(sup, retry_max=2, hedge_ms=0)
        with pytest.raises(ERR.QueryTimeout):
            await router.submit(graph="g", query="q", tenant="t")
        assert sup.workers[0].breaker.state == "closed"
        await sup.stop()

    asyncio.run(run())


def test_hedged_dispatch_second_replica_wins():
    """With hedging on, a slow primary gets duplicated after the hedge
    delay and the fast backup's reply wins well before the primary
    finishes."""

    async def run():
        launcher = FakeLauncher()
        sup = _supervisor(launcher, n=2)
        await sup.start()
        router = Router(sup, retry_max=1, hedge_ms=20.0)
        primary = router._pick("tenant-h").worker_id
        launcher.scripts[primary] = ["slow:1.5"]
        t0 = time.monotonic()
        payload = await router.submit(
            graph="g", query="q", tenant="tenant-h",
        )
        elapsed = time.monotonic() - t0
        assert payload["worker"] != primary
        assert elapsed < 1.0, f"hedge should beat the slow primary ({elapsed=})"
        await sup.stop()

    asyncio.run(run())


def test_hedging_skipped_for_faulted_queries():
    async def run():
        sup = _supervisor(FakeLauncher(), n=2)
        await sup.start()
        router = Router(sup, retry_max=1, hedge_ms=20.0)
        assert router._should_hedge(None, None)
        assert not router._should_hedge("oom@join:1", None), (
            "a chaos schedule must fire exactly once — never hedged"
        )
        await sup.stop()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# scheduler: drain + shed
# ---------------------------------------------------------------------------


def test_scheduler_drain_rejects_new_and_quiesces():
    """begin_drain: in-flight work completes and is waited for; new
    submits reject typed."""

    async def run():
        s = AdmissionScheduler(max_concurrent=2)
        await s.acquire(1, "t")
        s.begin_drain()
        with pytest.raises(ERR.AdmissionRejected):
            await s.acquire(1, "t")

        async def finish():
            await asyncio.sleep(0.05)
            s.release("t")

        task = asyncio.ensure_future(finish())
        t0 = time.monotonic()
        await s.quiesce(5.0)
        assert s.running == 0 and s.queued == 0
        assert time.monotonic() - t0 >= 0.04, "quiesce waited for in-flight"
        await task

    asyncio.run(run())


def test_scheduler_queue_high_sheds_typed():
    async def run():
        s = AdmissionScheduler(max_concurrent=1, queue_high=1)
        await s.acquire(1, "t")  # slot taken
        waiter = asyncio.ensure_future(s.acquire(1, "t"))  # queue depth 1
        await asyncio.sleep(0.01)
        with pytest.raises(ERR.AdmissionRejected) as e:
            await s.acquire(1, "t")  # at the watermark: shed
        assert "watermark" in str(e.value)
        s.release("t")
        await waiter
        s.release("t")

    asyncio.run(run())


# ---------------------------------------------------------------------------
# typed plumbing: classify, crash kind, config registry
# ---------------------------------------------------------------------------


def test_classify_worker_disconnects():
    for exc in (
        ConnectionResetError("peer reset"),
        BrokenPipeError("gone"),
        asyncio.IncompleteReadError(b"", 1),
    ):
        typed = ERR.classify(exc)
        assert isinstance(typed, ERR.WorkerLost), exc
        assert isinstance(typed, ERR.DeviceLost), "retryable like DeviceLost"
        assert typed.retryable
    assert ERR.classify(ValueError("not a fault")) is None


def test_crash_kind_parses_and_is_disarmed_outside_workers():
    """``crash@site`` in a non-worker process must degrade to a raised
    lost-style fault (never ``os._exit`` of the test runner)."""
    assert F.parse_spec("crash@expand:1") == {"expand": [("crash", 1, 1)]}
    assert not F.crash_armed()
    with F.scoped_spec("crash@somewhere:1"):
        with pytest.raises(F.InjectedFault) as e:
            F.fault_point("somewhere")
    typed = ERR.classify(e.value)
    assert isinstance(typed, ERR.DeviceLost)


def test_serve_cluster_knobs_declared_in_registry():
    for name in (
        "TPU_CYPHER_SERVE_WORKERS",
        "TPU_CYPHER_SERVE_BREAKER_THRESHOLD",
        "TPU_CYPHER_SERVE_BREAKER_COOLDOWN_S",
        "TPU_CYPHER_SERVE_RESTART_BACKOFF_S",
        "TPU_CYPHER_SERVE_RESTART_BACKOFF_MAX_S",
        "TPU_CYPHER_SERVE_HEALTH_INTERVAL_S",
        "TPU_CYPHER_SERVE_DRAIN_TIMEOUT_S",
        "TPU_CYPHER_SERVE_HEDGE_MS",
        "TPU_CYPHER_SERVE_QUEUE_HIGH",
        "TPU_CYPHER_SERVE_RETRY_MAX",
    ):
        assert name in config.REGISTRY, name
        assert config.REGISTRY[name].help, f"{name} needs a help string"


def test_tenant_pick_is_stable_and_salt_free():
    """Per-tenant affinity must survive process restarts: the pick hash
    cannot be Python's salted ``hash()``."""

    async def run():
        sup = _supervisor(FakeLauncher(), n=4)
        await sup.start()
        router = Router(sup, retry_max=0, hedge_ms=0)
        picks = {router._pick("tenant-x").worker_id for _ in range(10)}
        assert len(picks) == 1
        expected = zlib.crc32(b"tenant-x") % 4
        assert picks == {f"w{expected}"}
        await sup.stop()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# real-subprocess end-to-end: ClusterServer over actual engine workers
# ---------------------------------------------------------------------------

import json  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402

_N = 8
CREATE_Q = "CREATE " + ", ".join(
    [f"(n{i}:P {{id: {i}}})" for i in range(_N)]
    + [f"(n{i})-[:K]->(n{(i + 1) % _N})" for i in range(_N)]
    + [f"(n{i})-[:K]->(n{(i + 3) % _N})" for i in range(_N)]
)
COUNT_Q = "MATCH (a:P) RETURN count(a) AS n"
HOP_Q = "MATCH (a:P)-[:K]->(b:P) RETURN count(b) AS n"
ROWS_Q = "MATCH (a:P {id: 3})-[:K]->(b:P) RETURN b.id AS id ORDER BY id"


async def _client(host, port, lines, want=None):
    reader, writer = await asyncio.open_connection(host, port)
    for line in lines:
        writer.write((json.dumps(line) + "\n").encode())
    await writer.drain()
    if want is None:
        want = sum(1 for l in lines if l.get("op") == "submit")
    out, done = [], 0
    while done < want:
        raw = await asyncio.wait_for(reader.readline(), 60)
        if not raw:
            break
        msg = json.loads(raw)
        out.append(msg)
        if msg.get("type") in ("done", "error", "cancelled"):
            done += 1
    writer.close()
    return out


def _rows_of(msgs, qid):
    rows = []
    for m in msgs:
        if m["type"] == "rows" and m["id"] == qid:
            rows.extend(m["rows"])
    return rows


def _done_of(msgs, qid):
    for m in msgs:
        if m.get("id") == qid and m["type"] in ("done", "error"):
            return m
    raise AssertionError(f"no terminal for {qid}: {msgs}")


def test_cluster_e2e_crash_sigkill_drain(tmp_path, monkeypatch):
    """The acceptance scenario against REAL worker processes: rows match
    serial execution; an injected ``crash@expand`` kills a worker
    mid-query and the client still gets its exact rows (rung "replica" in
    the done message); SIGKILL of a worker mid-traffic yields zero
    client-visible failures and a supervisor restart; drain rejects new
    submits typed."""
    from tpu_cypher.serve.cluster import ClusterServer

    # this test opts in to the persistent cache: the workers inherit the
    # environment, and the environment alone places their shared cache
    monkeypatch.setenv("JAX_ENABLE_COMPILATION_CACHE", "true")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))

    async def run():
        server = ClusterServer(
            workers=2, port=0, batch_window_ms=0, lanes=2,
        )
        server.register_graph("g", CREATE_Q)
        server.warmup([COUNT_Q, HOP_Q, ROWS_Q], "g")
        await server.start()
        try:
            sup = server.supervisor
            assert len(sup.ready_workers) == 2
            # each READY line says what the worker holds; a fleet's
            # workers were each given a chip of their own
            assert [w.device["platform"] for w in sup.workers] == ["cpu"] * 2
            assert [w.device["chip"] for w in sup.workers] == ["0", "1"]

            # serial goldens from the front end's own replica
            golden = {}
            for q in (COUNT_Q, HOP_Q, ROWS_Q):
                res = server.session.cypher(q, {}, graph=server._graphs["g"])
                golden[q] = wire.encode_rows(
                    res.records.collect(), list(res.records.columns)
                )

            # 1) plain queries: byte-identical to serial execution
            msgs = await _client(server.host, server.port, [
                {"op": "submit", "id": f"p{i}", "graph": "g", "query": q,
                 "tenant": f"t{i}"}
                for i, q in enumerate((COUNT_Q, HOP_Q, ROWS_Q))
            ])
            for i, q in enumerate((COUNT_Q, HOP_Q, ROWS_Q)):
                assert _done_of(msgs, f"p{i}")["type"] == "done"
                assert _rows_of(msgs, f"p{i}") == golden[q], q

            # 2) injected crash kills a real worker mid-query: the client
            # still gets exact rows, and the retry is stamped "replica"
            msgs = await _client(server.host, server.port, [
                {"op": "submit", "id": "boom", "graph": "g", "query": HOP_Q,
                 "tenant": "chaos-tenant", "faults": "crash@expand:1"},
            ])
            done = _done_of(msgs, "boom")
            assert done["type"] == "done", done
            assert _rows_of(msgs, "boom") == golden[HOP_Q], "exact rows, once"
            assert G.RUNG_REPLICA in done["rungs"], done
            await _until(
                lambda: len(sup.ready_workers) == 2
                and sup.total_restarts >= 1,
                timeout=60.0, what="crash recovery to 2 ready workers",
            )

            # 3) SIGKILL mid-traffic: zero client-visible failures
            os.kill(sup.workers[0].transport.pid, signal.SIGKILL)
            msgs = await _client(server.host, server.port, [
                {"op": "submit", "id": f"k{i}", "graph": "g",
                 "query": COUNT_Q, "tenant": f"kt{i}"}
                for i in range(6)
            ])
            for i in range(6):
                assert _done_of(msgs, f"k{i}")["type"] == "done", (
                    "a client saw a failure after SIGKILL"
                )
                assert _rows_of(msgs, f"k{i}") == golden[COUNT_Q]
            await _until(
                lambda: len(sup.ready_workers) == 2
                and sup.total_restarts >= 2,
                timeout=60.0, what="SIGKILL recovery to 2 ready workers",
            )

            # 4) drain: new submits reject typed
            await server.drain(timeout=15.0)
            msgs = await _client(server.host, server.port, [
                {"op": "submit", "id": "late", "graph": "g",
                 "query": COUNT_Q},
            ])
            late = _done_of(msgs, "late")
            assert late["type"] == "error"
            assert late["error"] == "AdmissionRejected", late
        finally:
            await server.stop()

    asyncio.run(run())
    # the workers wrote their compiled programs where the environment said
    assert os.listdir(tmp_path / "cache"), "workers never used the cache dir"
