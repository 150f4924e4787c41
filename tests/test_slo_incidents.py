"""SLO engine + burn-rate sentinel + incident capsules (ISSUE 20).

Three layers of coverage:

* **Unit**: the ``--slo-file`` loader's validation surface, the
  fast/slow burn three-state machine under a fake clock (exactly one
  ``on_burning`` per excursion), the per-kind burn math on real
  :class:`Metrics` series, and the incident recorder's debounce +
  bounded on-disk ring.
* **Byte pin**: with the engine off (the default) the observable
  surface is byte for byte the pre-SLO server — minimal ``/healthz``
  body, no ``wql_slo`` gauge, 404 on both debug routes.
* **Forced breach, end to end**: a ``backend.collect=delay`` failpoint
  on a real-socket server drives ``frame.e2e_ms`` past its objective —
  the strict-parsed ``slo`` gauge walks OK→BURNING→OK, ``/healthz``
  degrades and recovers, and exactly ONE capsule lands within the
  cooldown carrying every subsystem section plus the burn trajectory.
  The cluster variant burns the federated ``cluster.e2e_ms`` under a
  ring-delay failpoint and asserts the router's fleet capsule embeds
  sections from BOTH shard processes (distinct pids prove it).
"""

import asyncio
import json
import time
import urllib.error
import urllib.request
import uuid as uuid_mod

import pytest

from tests.client_util import ZmqClient, free_port
from tests.prom_parser import validate_exposition
from worldql_server_tpu.engine.config import Config
from worldql_server_tpu.engine.metrics import LATENCY_BUCKETS_MS, Metrics
from worldql_server_tpu.engine.server import WorldQLServer
from worldql_server_tpu.observability.incidents import (
    IncidentRecorder,
    capsule_sections,
)
from worldql_server_tpu.observability.slo import (
    BURNING,
    DEFAULT_OBJECTIVES,
    EVAL_INTERVAL_S,
    OK,
    WARN,
    SloEngine,
    _Objective,
    _over_target_index,
    load_objectives,
)
from worldql_server_tpu.protocol import Instruction, Message
from worldql_server_tpu.protocol.types import Vector3
from worldql_server_tpu.robustness import failpoints


def run(coro, timeout=240):
    return asyncio.run(asyncio.wait_for(coro, timeout))


@pytest.fixture(autouse=True)
def clean_global_registry():
    """The failpoint registry is process-global; the breach tests arm
    it mid-flight, so every test starts and ends disarmed."""
    failpoints.registry.reset()
    yield
    failpoints.registry.reset()


#: every capsule from an engine process carries exactly these sections
#: (disabled subsystems report ``enabled: False`` rather than vanish)
SECTION_KEYS = {
    "flight_recorder", "governor", "placement", "interest",
    "device", "loop_health", "failpoints",
}

_GOOD = {
    "name": "x", "series": "s.ms", "kind": "latency_p99",
    "target_ms": 10.0, "budget": 0.1, "fast_s": 1.0, "slow_s": 2.0,
}


# ---------------------------------------------------------------------------
# unit: loader + validation


def test_load_objectives_defaults_are_copies():
    interval, objectives = load_objectives(None)
    assert interval == EVAL_INTERVAL_S == 1.0
    assert [o["name"] for o in objectives] == [
        o["name"] for o in DEFAULT_OBJECTIVES
    ]
    # mutating the loaded registry must never reach the module literal
    objectives[0]["target_ms"] = 1e9
    assert DEFAULT_OBJECTIVES[0]["target_ms"] == 5.0


def test_default_latency_targets_sit_on_bucket_edges():
    """Exact burn accounting depends on it: an over-target count is
    a bucket-suffix sum only when the target IS a bucket bound."""
    for obj in DEFAULT_OBJECTIVES:
        if obj["kind"] == "latency_p99":
            assert obj["target_ms"] in LATENCY_BUCKETS_MS, obj["name"]
    # and the cut is exclusive: exactly-at-target observations are good
    assert LATENCY_BUCKETS_MS[_over_target_index(5.0)] == 10.0


def test_load_objectives_file_forms(tmp_path):
    as_list = tmp_path / "list.json"
    as_list.write_text(json.dumps([_GOOD]))
    interval, objs = load_objectives(str(as_list))
    assert interval == EVAL_INTERVAL_S
    assert objs == [_GOOD]

    as_doc = tmp_path / "doc.json"
    as_doc.write_text(json.dumps(
        {"eval_interval_s": 0.25, "objectives": [_GOOD]}
    ))
    interval, objs = load_objectives(str(as_doc))
    assert interval == 0.25
    assert objs == [_GOOD]


def test_load_objectives_rejects_malformed(tmp_path):
    def reject(doc, match):
        path = tmp_path / "f.json"
        path.write_text(json.dumps(doc))
        with pytest.raises(ValueError, match=match):
            load_objectives(str(path))

    reject([_GOOD, _GOOD], "duplicate slo objective")
    reject([], "declares no objectives")
    reject({"objectives": "x"}, "objectives")
    reject({"eval_interval_s": 0, "objectives": [_GOOD]},
           "eval_interval_s")
    reject("nope", "list or object")
    reject([{**_GOOD, "kind": "p50"}], "kind")
    reject([{**_GOOD, "name": "bad name"}], "must be")
    reject([{**_GOOD, "name": ""}], "missing 'name'")
    reject([{**_GOOD, "series": ""}], "missing 'series'")
    reject([{**_GOOD, "fast_s": 5.0, "slow_s": 1.0}], "fast_s")
    reject([{**_GOOD, "slow_s": 0}], "slow_s")
    reject([{**_GOOD, "target_ms": 0}], "target_ms")
    reject([{**_GOOD, "budget": 2.0}], "budget")
    reject([{"name": "r", "series": "s", "kind": "rate"}], "max_per_s")
    reject([{"name": "g", "series": "s", "kind": "gauge_floor"}],
           "floor")


def test_config_slo_validation(tmp_path):
    Config(store_url="memory://").validate()  # defaults stay valid

    cfg = Config(store_url="memory://", slo="on")
    cfg.validate()
    assert cfg.slo_enabled

    good = tmp_path / "slo.json"
    good.write_text(json.dumps([_GOOD]))
    cfg = Config(store_url="memory://", slo_file=str(good))
    cfg.validate()
    assert cfg.slo_enabled  # a file implies the engine on

    with pytest.raises(ValueError, match="incident_dir requires"):
        Config(store_url="memory://",
               incident_dir=str(tmp_path)).validate()
    with pytest.raises(ValueError, match="slo must be"):
        Config(store_url="memory://", slo="maybe").validate()
    with pytest.raises(ValueError, match="incident_keep"):
        Config(store_url="memory://", slo="on",
               incident_keep=0).validate()
    with pytest.raises(ValueError, match="incident_cooldown"):
        Config(store_url="memory://", slo="on",
               incident_cooldown=-1).validate()
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"objectives": []}))
    with pytest.raises(ValueError, match="slo_file"):
        Config(store_url="memory://", slo_file=str(bad)).validate()


# ---------------------------------------------------------------------------
# unit: the burn state machine under a fake clock


def _engine(spec, clock, interval=1.0):
    metrics = Metrics()
    eng = SloEngine(
        metrics, [spec], eval_interval_s=interval,
        clock=lambda: clock[0],
    )
    return metrics, eng


def test_burn_state_machine_fires_on_burning_exactly_once():
    clock = [0.0]
    metrics, eng = _engine(
        {"name": "evs", "series": "test.events", "kind": "rate",
         "max_per_s": 1.0, "fast_s": 2.0, "slow_s": 4.0},
        clock,
    )
    fired = []
    eng.on_burning = fired.append
    obj = eng.objectives[0]

    eng.evaluate()  # t=0 baseline
    assert obj.level == OK and not fired

    metrics.inc("test.events", 100)
    clock[0] = 1.0
    eng.evaluate()  # both windows see 100 ev/s against a 1/s objective
    assert obj.level == BURNING
    assert [o.name for o in fired] == ["evs"]
    assert obj.burn_fast >= 1.0 and obj.burn_slow >= 1.0
    assert eng.healthz() == {"state": "burning", "burning": ["evs"]}
    assert eng.gauge() == {"evs": BURNING, "worst": BURNING}

    clock[0] = 2.0
    eng.evaluate()  # still burning — the hook must NOT re-fire
    assert obj.level == BURNING
    assert len(fired) == 1

    # no new events: recovery drains BURNING -> WARN -> OK as the
    # fast window clears first, then the slow one
    levels = []
    for t in (3.0, 4.0, 5.0):
        clock[0] = t
        eng.evaluate()
        levels.append(obj.level)
    assert levels == [WARN, WARN, OK]
    assert len(fired) == 1  # one excursion, one trigger
    assert obj.transitions == 3  # ok->burning->warn->ok
    assert eng.worst_level == OK
    assert eng.healthz() == {"state": "ok", "burning": []}

    # trajectory records every evaluation with its burn pair
    traj = eng.trajectory("evs")
    assert len(traj) == eng.evals == 6
    assert {"t", "burn_fast", "burn_slow", "level"} == set(traj[0])
    assert max(e["level"] for e in traj) == BURNING
    assert eng.trajectory("nope") == []


def test_latency_objective_burns_on_over_target_fraction():
    clock = [0.0]
    metrics, eng = _engine(
        {"name": "lat", "series": "test.ms", "kind": "latency_p99",
         "target_ms": 5.0, "budget": 0.5, "fast_s": 2.0, "slow_s": 4.0},
        clock,
    )
    obj = eng.objectives[0]
    eng.evaluate()  # baseline

    for _ in range(9):
        metrics.observe_ms("test.ms", 1.0)
    metrics.observe_ms("test.ms", 100.0)
    clock[0] = 1.0
    eng.evaluate()
    # 1 of 10 over target: fraction 0.1 against a 0.5 budget
    assert obj.value == 0.1
    assert obj.burn_fast == 0.2 and obj.level == OK

    for _ in range(10):
        metrics.observe_ms("test.ms", 100.0)
    clock[0] = 2.0
    eng.evaluate()
    # windows diff against t=0: 11 of 20 bad -> burn 1.1 on both
    assert obj.burn_fast == 1.1 and obj.burn_slow == 1.1
    assert obj.level == BURNING
    status = obj.status()
    assert status["target_ms"] == 5.0 and status["budget"] == 0.5
    assert status["bad_fraction"] == 0.55
    assert status["budget_remaining"] == 0.0


def test_gauge_floor_objective_ignores_unmeasured_samples():
    clock = [0.0]
    value = [0.0]
    metrics = Metrics()
    metrics.gauge("test.capacity", lambda: value[0])
    eng = SloEngine(
        metrics,
        [{"name": "floor", "series": "test.capacity",
          "kind": "gauge_floor", "floor": 100.0,
          "fast_s": 2.0, "slow_s": 4.0}],
        eval_interval_s=1.0, clock=lambda: clock[0],
    )
    obj = eng.objectives[0]
    eng.evaluate()  # gauge still 0: warming up, judges nothing
    assert obj.level == OK and obj.burn_fast == 0.0

    value[0] = 50.0
    clock[0] = 1.0
    eng.evaluate()  # half the floor -> burn 2.0 on the live sample
    assert obj.level == BURNING
    assert obj.burn_fast == 2.0
    assert obj.status()["value"] == 50.0

    value[0] = 200.0
    clock[0] = 2.0
    eng.evaluate()  # back above the floor
    assert obj.level == OK and obj.burn_fast == 0.0


def test_default_set_is_judged_whole_and_a_healthy_registry_burns_nothing():
    """The registry a server boots with, on the series a healthy
    server feeds: every objective gets a verdict at every evaluation
    (a latency objective on live observations, not an empty series)
    and none enters BURNING."""
    clock = [0.0]
    interval, specs = load_objectives(None)
    metrics = Metrics()
    metrics.gauge("deliveries_per_s_per_core", lambda: 50_000.0)
    eng = SloEngine(
        metrics, specs, eval_interval_s=interval, clock=lambda: clock[0],
    )
    latencies = [
        o["series"] for o in specs if o["kind"] == "latency_p99"
    ]
    evals = 8
    for t in range(evals):
        clock[0] = float(t)
        for series in latencies:
            for _ in range(20):
                metrics.observe_ms(series, 1.0)
        eng.evaluate()
    status = eng.status()
    assert status["evals"] == evals and status["state"] == "ok"
    assert set(status["objectives"]) == {
        o["name"] for o in DEFAULT_OBJECTIVES
    }
    for name, verdict in status["objectives"].items():
        traj = eng.trajectory(name)
        assert len(traj) == evals, name
        assert all(e["level"] == OK for e in traj), (name, traj)
        if verdict["kind"] == "latency_p99":
            assert verdict["bad_fraction"] == 0.0, name
    assert eng.healthz() == {"state": "ok", "burning": []}


# ---------------------------------------------------------------------------
# unit: incident recorder debounce + bounded ring


def _rate_objective():
    obj = _Objective({
        "name": "evs", "series": "t.e", "kind": "rate",
        "max_per_s": 1.0,
    })
    obj.trajectory.append(
        {"t": 1.0, "burn_fast": 2.0, "burn_slow": 2.0, "level": 2}
    )
    return obj


def test_incident_recorder_debounce_ring_and_introspection(tmp_path):
    inc_dir = tmp_path / "inc"

    async def scenario():
        clock = [100.0]
        rec = IncidentRecorder(
            str(inc_dir), cooldown_s=10.0, keep=2,
            clock=lambda: clock[0],
        )

        async def collect():
            return {"pid": 4242, "sections": {"a": 1, "b": 2, "c": 3}}

        rec.collect = collect
        obj = _rate_objective()

        assert rec.trigger(obj, {"state": "burning"}) is True
        clock[0] += 1.0
        # inside the cooldown window: suppressed, not written
        assert rec.trigger(obj, {"state": "burning"}) is False
        await rec.drain()
        assert sorted(p.name for p in inc_dir.iterdir()) == [
            "incident-0001-evs.json"
        ]

        for _ in range(2):
            clock[0] += 11.0
            assert rec.trigger(obj, {"state": "burning"}) is True
            await rec.drain()
        # bounded ring: keep=2 pruned the oldest capsule
        assert sorted(p.name for p in inc_dir.iterdir()) == [
            "incident-0002-evs.json", "incident-0003-evs.json"
        ]

        index = rec.list()
        assert [e["id"] for e in index] == [
            "incident-0002-evs", "incident-0003-evs"
        ]
        assert all(e["objective"] == "evs" for e in index)
        assert all(e["bytes"] > 0 for e in index)

        capsule = rec.load("incident-0003-evs")
        assert capsule["id"] == "incident-0003-evs"
        assert capsule["objective"]["name"] == "evs"
        assert capsule["pid"] == 4242
        assert capsule["sections"] == {"a": 1, "b": 2, "c": 3}
        assert capsule["trajectory"] == list(obj.trajectory)
        assert capsule["slo"] == {"state": "burning"}
        assert rec.load("incident-9999-evs") is None
        assert rec.load("../../etc/passwd") is None

        assert rec.stats() == {
            "captured": 3, "suppressed": 1, "errors": 0,
            "cooldown_s": 10.0, "keep": 2, "on_disk": 2,
        }

        # a fresh recorder over the same dir resumes the sequence —
        # restart can never overwrite an existing capsule
        rec2 = IncidentRecorder(
            str(inc_dir), cooldown_s=0.0, keep=2,
            clock=lambda: clock[0],
        )
        rec2.collect = collect
        assert rec2.trigger(obj, {"state": "burning"}) is True
        await rec2.drain()
        assert (inc_dir / "incident-0004-evs.json").exists()

    run(scenario())


def test_incident_capsule_survives_collect_failure(tmp_path):
    async def scenario():
        rec = IncidentRecorder(str(tmp_path / "i"), cooldown_s=0.0)

        async def boom():
            raise RuntimeError("pull failed")

        rec.collect = boom
        assert rec.trigger(_rate_objective(), {"state": "burning"})
        await rec.drain()
        # the trigger envelope still lands, flagged — losing the body
        # must not lose the incident
        assert rec.captured == 1 and rec.errors == 1
        capsule = rec.load(rec.list()[0]["id"])
        assert capsule["collect_error"] is True
        assert "sections" not in capsule

    run(scenario())


def test_capsule_sections_stable_shape_when_everything_off():
    class Bare:
        pass

    sections = capsule_sections(Bare())
    assert set(sections) == SECTION_KEYS
    for key in SECTION_KEYS - {"failpoints"}:
        assert sections[key]["enabled"] is False
    assert sections["placement"]["epoch"] == 0
    assert sections["failpoints"] == {}


# ---------------------------------------------------------------------------
# end to end: off-by-default byte pin


def _http_raw(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as resp:
        return resp.read()


def _http_json(port, path):
    return json.loads(_http_raw(port, path))


def _http_status(port, path):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10
        ) as resp:
            return resp.status
    except urllib.error.HTTPError as exc:
        return exc.code


def test_slo_off_surface_stays_reference_shaped():
    async def scenario():
        http_port = free_port()
        server = WorldQLServer(Config(
            store_url="memory://", http_port=http_port,
            ws_enabled=False, zmq_enabled=False,
        ))
        assert server.slo is None and server.incidents is None
        await server.start()
        try:
            # byte-for-byte minimal body: no slo block rides healthz
            raw = await asyncio.to_thread(_http_raw, http_port, "/healthz")
            assert raw == b'{"status": "ok"}'
            for path in ("/debug/slo", "/debug/incidents"):
                code = await asyncio.to_thread(_http_status, http_port, path)
                assert code == 404, path
            text = (
                await asyncio.to_thread(_http_raw, http_port, "/metrics")
            ).decode()
            validate_exposition(text)
            assert "wql_slo" not in text
            assert "wql_incidents" not in text
        finally:
            await server.stop()

    run(scenario())


# ---------------------------------------------------------------------------
# end to end: forced breach on a real-socket server

#: one objective replaces the whole registry, so nothing else can
#: trigger a capsule first. Target 100ms sits on a bucket edge; budget
#: 0.34 tolerates loaded-runner stragglers while the 300ms injected
#: delay (every frame bad) burns at ~3x on both windows.
_BREACH_SLO = {
    "eval_interval_s": 0.1,
    "objectives": [{
        "name": "frame_e2e_p99",
        "series": "frame.e2e_ms",
        "kind": "latency_p99",
        "target_ms": 100.0,
        "budget": 0.34,
        "fast_s": 0.5,
        "slow_s": 1.0,
    }],
}


async def _poll(pred, what, timeout_s=90.0, interval_s=0.2):
    deadline = time.monotonic() + timeout_s
    while True:
        got = await pred()
        if got:
            return got
        assert time.monotonic() < deadline, f"timed out waiting: {what}"
        await asyncio.sleep(interval_s)


def test_single_process_breach_one_capsule_then_recovery(tmp_path):
    slo_file = tmp_path / "slo.json"
    slo_file.write_text(json.dumps(_BREACH_SLO))
    inc_dir = tmp_path / "incidents"

    async def scenario():
        http_port = free_port()
        server = WorldQLServer(Config(
            store_url="memory://",
            http_port=http_port, ws_enabled=False,
            zmq_server_host="127.0.0.1", zmq_server_port=free_port(),
            spatial_backend="tpu", tick_interval=0.03,
            precompile_tiers=False,
            trace=True,                  # a real flight-recorder section
            resilience="on",             # the backend.collect failpoint site
            slo_file=str(slo_file),
            incident_dir=str(inc_dir),
            incident_cooldown=600.0,     # flapping may retrigger; one capture
        ))
        await server.start()
        clients = []
        stop = asyncio.Event()
        tasks = []
        try:
            port = server.config.zmq_server_port
            rx = await ZmqClient.connect(port)
            tx = await ZmqClient.connect(port)
            clients += [rx, tx]
            pos = Vector3(1.0, 2.0, 3.0)
            await rx.send(Message(
                instruction=Instruction.AREA_SUBSCRIBE,
                world_name="w", position=pos,
            ))

            async def traffic():
                i = 0
                while not stop.is_set():
                    await tx.send(Message(
                        instruction=Instruction.LOCAL_MESSAGE,
                        world_name="w", position=pos,
                        parameter=f"m-{i}",
                    ))
                    i += 1
                    await asyncio.sleep(0.05)

            tasks.append(asyncio.create_task(traffic()))
            # delivery live before judging anything
            await rx.recv_until(Instruction.LOCAL_MESSAGE, 30)

            # phase 1 — clean traffic; wait until warmup (jit compiles
            # can blow the target) has aged out of both windows
            async def clean():
                st = await asyncio.to_thread(
                    _http_json, http_port, "/debug/slo"
                )
                obj = st["objectives"]["frame_e2e_p99"]
                return st if (
                    st["evals"] >= 12 and obj["state"] == "ok"
                ) else None

            st = await _poll(clean, "slo state never settled ok")
            assert st["state"] == "ok"
            assert set(st["objectives"]) == {"frame_e2e_p99"}
            assert st["eval_interval_s"] == 0.1

            text = (
                await asyncio.to_thread(_http_raw, http_port, "/metrics")
            ).decode()
            types, samples = validate_exposition(text)
            flat = {n: v for n, labels, v in samples if not labels}
            assert types["wql_slo_frame_e2e_p99"] == "gauge"
            assert flat["wql_slo_frame_e2e_p99"] == 0.0
            assert flat["wql_slo_worst"] == 0.0

            # phase 2 — the breach: every tick's collect sleeps 300ms,
            # so every delivered frame's e2e blows the 100ms target
            failpoints.registry.set("backend.collect", "delay:300ms")

            async def burning():
                health = await asyncio.to_thread(
                    _http_json, http_port, "/healthz"
                )
                slo = health.get("slo")
                return health if (
                    health["status"] == "degraded"
                    and slo is not None
                    and slo["state"] == "burning"
                    and "frame_e2e_p99" in slo["burning"]
                ) else None

            await _poll(burning, "/healthz never degraded on the burn")

            async def gauge_burning():
                text = (
                    await asyncio.to_thread(
                        _http_raw, http_port, "/metrics"
                    )
                ).decode()
                _, samples = validate_exposition(text)
                flat = {n: v for n, labels, v in samples if not labels}
                return flat if (
                    flat.get("wql_slo_frame_e2e_p99") == 2.0
                ) else None

            flat = await _poll(gauge_burning, "slo gauge never hit 2")
            assert flat["wql_slo_worst"] == 2.0

            async def captured():
                body = await asyncio.to_thread(
                    _http_json, http_port, "/debug/incidents"
                )
                return body if body["stats"]["captured"] >= 1 else None

            body = await _poll(captured, "no incident capsule captured")
            # exactly one within the cooldown, however often it flapped
            assert body["stats"]["captured"] == 1
            assert len(body["incidents"]) == 1
            entry = body["incidents"][0]
            assert entry["objective"] == "frame_e2e_p99"

            capsule = await asyncio.to_thread(
                _http_json, http_port,
                f"/debug/incidents?id={entry['id']}",
            )
            assert capsule["id"] == entry["id"]
            assert capsule["objective"]["name"] == "frame_e2e_p99"
            assert capsule["objective"]["state"] == "burning"
            assert capsule["trajectory"], "burn trajectory missing"
            last = capsule["trajectory"][-1]
            assert last["level"] == BURNING
            assert last["burn_fast"] >= 1.0 and last["burn_slow"] >= 1.0
            # every subsystem section, correlated in ONE bundle
            assert set(capsule["sections"]) >= SECTION_KEYS
            assert "stats" in capsule["sections"]["flight_recorder"]
            fired = capsule["sections"]["failpoints"]
            assert fired.get("backend.collect", 0) >= 1
            slo_at_capture = capsule["slo"]["objectives"]["frame_e2e_p99"]
            assert slo_at_capture["state"] == "burning"
            # the same capsule sits in the bounded on-disk ring
            assert (inc_dir / f"{entry['id']}.json").exists()

            # phase 3 — recovery: clear the fault; clean frames drain
            # the windows and the gauge walks back to OK
            failpoints.registry.clear("backend.collect")

            async def recovered():
                health = await asyncio.to_thread(
                    _http_json, http_port, "/healthz"
                )
                slo = health["slo"]
                return health if (
                    health["status"] == "ok"
                    and slo["state"] == "ok"
                    and slo["burning"] == []
                ) else None

            await _poll(recovered, "/healthz never recovered")

            async def gauge_ok():
                text = (
                    await asyncio.to_thread(
                        _http_raw, http_port, "/metrics"
                    )
                ).decode()
                _, samples = validate_exposition(text)
                flat = {n: v for n, labels, v in samples if not labels}
                return flat if (
                    flat.get("wql_slo_frame_e2e_p99") == 0.0
                    and flat.get("wql_slo_worst") == 0.0
                ) else None

            await _poll(gauge_ok, "slo gauge never drained to 0")

            # still exactly one capsule: the cooldown held
            body = await asyncio.to_thread(
                _http_json, http_port, "/debug/incidents"
            )
            assert body["stats"]["captured"] == 1
            assert len(body["incidents"]) == 1
        finally:
            stop.set()
            for task in tasks:
                task.cancel()
            for task in tasks:
                try:
                    await task
                except (asyncio.CancelledError, Exception):
                    pass
            for client in clients:
                await client.close()
            await server.stop()

    run(scenario())


# ---------------------------------------------------------------------------
# end to end: cluster fleet capsule from BOTH shard processes

_CLUSTER_SLO = {
    "eval_interval_s": 0.2,
    "objectives": [{
        "name": "cluster_e2e_p99",
        "series": "cluster.e2e_ms",
        "kind": "latency_p99",
        "target_ms": 25.0,
        "budget": 0.34,
        "fast_s": 1.0,
        "slow_s": 2.0,
    }],
}


def test_cluster_breach_capsule_embeds_both_shard_processes(tmp_path):
    """Ring-delay failpoint inflates cross-shard ``cluster.e2e_ms``
    past the objective; the shards' series federate into the router's
    registry, its engine burns, and the fleet capsule pulls subsystem
    sections from the router AND both shard subprocesses over the
    shared chunked control path."""
    slo_file = tmp_path / "slo.json"
    slo_file.write_text(json.dumps(_CLUSTER_SLO))
    inc_dir = tmp_path / "incidents"

    async def scenario():
        from worldql_server_tpu.cluster import ClusterRuntime, WorldMap
        from worldql_server_tpu.scenarios.client import (
            ZmqPeer, free_port_block,
        )

        base = free_port_block(5)
        http_port = base + 3
        config = Config(
            store_url="memory://",
            http_enabled=True, http_host="127.0.0.1",
            http_port=http_port,
            ws_enabled=False,
            zmq_server_host="127.0.0.1", zmq_server_port=base,
            spatial_backend="cpu", tick_interval=0.02,
            trace=True,
            # every ring drain sleeps 60ms: each cross-shard frame's
            # e2e blows the 25ms objective deterministically
            failpoints="cluster.ring_deliver=delay:60ms",
            cluster_shards=2,
            slo_file=str(slo_file),       # shards inherit via shard_argv
            incident_dir=str(inc_dir),    # router-only: the fleet capsule
            incident_cooldown=600.0,
        )
        world_map = WorldMap(2)

        def world_for(shard):
            for i in range(10_000):
                if world_map.shard_of_world(f"slo{i}") == shard:
                    return f"slo{i}"
            raise AssertionError

        def uuid_for(shard):
            while True:
                u = uuid_mod.uuid4()
                if world_map.shard_of_peer(u) == shard:
                    return u

        w1 = world_for(1)
        pos = Vector3(5.0, 5.0, 5.0)
        runtime = ClusterRuntime(config)
        await runtime.start()
        peers = []
        stop = asyncio.Event()
        tasks = []
        try:
            async def connect(peer_uuid):
                last = None
                for _ in range(100):
                    try:
                        peer = await ZmqPeer.connect(
                            config.zmq_server_port, peer_uuid=peer_uuid
                        )
                        peers.append(peer)
                        return peer
                    except Exception as exc:
                        last = exc
                        await asyncio.sleep(0.05)
                raise AssertionError(f"connect failed: {last!r}")

            rx = await connect(uuid_for(0))   # homed on shard 0
            tx = await connect(uuid_for(1))   # homed on shard 1
            for c in (rx, tx):
                await c.send(Message(
                    instruction=Instruction.AREA_SUBSCRIBE,
                    world_name=w1, position=pos,
                ))
            await asyncio.sleep(0.5)

            async def traffic():
                i = 0
                while not stop.is_set():
                    await tx.send(Message(
                        instruction=Instruction.LOCAL_MESSAGE,
                        world_name=w1, position=pos,
                        parameter=f"burn-{i}",
                    ))
                    i += 1
                    await asyncio.sleep(0.1)

            tasks.append(asyncio.create_task(traffic()))
            # the 1→0 ring crossing is live (and paying the delay)
            got = await rx.recv_until(Instruction.LOCAL_MESSAGE, 60)
            assert got.parameter and got.parameter.startswith("burn-")

            # the shards' piggybacked compliance reaches the router
            async def federated():
                st = await asyncio.to_thread(
                    _http_json, http_port, "/debug/slo"
                )
                shards = st.get("shards", {})
                return st if {"0", "1"} <= set(shards) else None

            st = await _poll(federated, "shard compliance never federated",
                             timeout_s=60)
            assert set(st["objectives"]) == {"cluster_e2e_p99"}
            for shard in ("0", "1"):
                assert "cluster_e2e_p99" in st["shards"][shard]["levels"]

            # the federated aggregate burns at the router -> capsule
            async def captured():
                body = await asyncio.to_thread(
                    _http_json, http_port, "/debug/incidents"
                )
                return body if body["stats"]["captured"] >= 1 else None

            body = await _poll(captured, "no fleet capsule captured",
                               timeout_s=150)
            assert body["stats"]["captured"] == 1
            assert len(body["incidents"]) == 1
            entry = body["incidents"][0]
            assert entry["objective"] == "cluster_e2e_p99"

            capsule = await asyncio.to_thread(
                _http_json, http_port,
                f"/debug/incidents?id={entry['id']}",
            )
            assert capsule["objective"]["name"] == "cluster_e2e_p99"
            assert capsule["trajectory"]
            # router's own sections (its subsystems differ from an
            # engine process: placement/federation/shed mirror)
            assert set(capsule["sections"]) >= {
                "placement", "federation", "shed_mirror", "cluster",
                "failpoints", "flight_recorder",
            }
            # ...and BOTH shard subprocesses' sections, pulled over the
            # same chunked control path /debug/cluster uses
            assert set(capsule["shards"]) == {"0", "1"}
            pids = {capsule["pid"]}
            for shard in ("0", "1"):
                dump = capsule["shards"][shard]
                assert set(dump["sections"]) >= SECTION_KEYS
                assert "stats" in dump["sections"]["flight_recorder"]
                pids.add(dump["pid"])
            # three DISTINCT processes contributed to one capsule
            assert len(pids) == 3
            # the chaos the capsule must attribute is in its evidence:
            # the ring-delay fires in the shard processes
            assert any(
                capsule["shards"][s]["sections"]["failpoints"].get(
                    "cluster.ring_deliver", 0
                ) >= 1
                for s in ("0", "1")
            )
        finally:
            stop.set()
            for task in tasks:
                task.cancel()
            for task in tasks:
                try:
                    await task
                except (asyncio.CancelledError, Exception):
                    pass
            for peer in peers:
                try:
                    peer.close()
                except Exception:
                    pass
            await runtime.stop()

    run(scenario())
