"""Fleet control plane (apex_tpu/fleet): registry machine, heartbeats,
park-and-rejoin, the chaos harness, the restricted wire, and the host
supervisor.

Everything here is tier-1: deterministic (fake clocks / seeded schedules)
and fast (the socket tests run whole learner-death dramas in-process on
localhost with sub-second thresholds).  The multi-process SIGKILL soak
lives in ``tests/test_fleet_rejoin.py`` behind ``-m slow``.
"""

from __future__ import annotations

import dataclasses
import socket
import threading
import time

import pytest

from apex_tpu.config import CommsConfig
from apex_tpu.fleet.chaos import (ChaosChunkSender, ChaosConfig,
                                  ChaosParamPublisher, chaos_from_env)
from apex_tpu.fleet.heartbeat import Heartbeat, HeartbeatEmitter
from apex_tpu.fleet.park import ParkController
from apex_tpu.fleet.registry import (ALIVE, DEAD, JOINING, SUSPECT,
                                     FleetRegistry, FleetStatusServer,
                                     format_fleet_table, status_request)
from apex_tpu.fleet.supervise import supervise


def _free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _comms(**overrides) -> CommsConfig:
    batch, param, barrier, status = _free_ports(4)
    return CommsConfig(batch_port=batch, param_port=param,
                       barrier_port=barrier, status_port=status,
                       **overrides)


# -- registry state machine -------------------------------------------------

def test_registry_state_machine_and_rejoin_accounting():
    """JOINING -> ALIVE -> SUSPECT -> DEAD -> ALIVE under a fake clock;
    DEAD->ALIVE counts as a rejoin, SUSPECT->ALIVE recovery does not."""
    t = [0.0]
    comms = CommsConfig(suspect_after_s=2.0, dead_after_s=5.0)
    reg = FleetRegistry(comms, clock=lambda: t[0])

    reg.observe(Heartbeat("actor-0", fps=100.0, param_version=3))
    assert reg.peers["actor-0"].state == ALIVE
    assert ("actor-0", JOINING, ALIVE) in reg.tick()

    t[0] = 3.0                              # silent past suspect_after_s
    assert ("actor-0", ALIVE, SUSPECT) in reg.tick()
    reg.observe(Heartbeat("actor-0"))       # recovery: NOT a rejoin
    assert reg.peers["actor-0"].state == ALIVE
    assert reg.metrics()["rejoins"] == 0

    t[0] = 10.0                             # silent past dead_after_s
    trans = reg.tick()
    assert ("actor-0", ALIVE, SUSPECT) in trans
    assert ("actor-0", SUSPECT, DEAD) in trans
    assert reg.metrics()["dead"] == 1 and reg.metrics()["deaths"] == 1

    reg.observe(Heartbeat("actor-0"))       # back from the dead: a rejoin
    m = reg.metrics()
    assert m["alive"] == 1 and m["dead_to_alive"] == 1 and m["rejoins"] == 1


def test_registry_forgives_the_span_its_observer_was_blind():
    """A learner loop blocked inside a dispatch (a first compile is tens
    of seconds on the chip) watches nobody, and in-host workers blocked
    on the full chunk queue cannot beat meanwhile: that span is not
    silence.  Forgiven, only watched time ages a peer — and a peer that
    really died still goes SUSPECT -> DEAD on schedule afterwards."""
    t = [0.0]
    comms = CommsConfig(suspect_after_s=2.0, dead_after_s=5.0)
    reg = FleetRegistry(comms, clock=lambda: t[0])
    reg.observe(Heartbeat("actor-0"))
    reg.observe(Heartbeat("actor-1"))
    reg.tick()

    t[0] = 40.0                             # the observer's 40 s compile
    reg.forgive(40.0)
    assert reg.tick() == []
    assert reg.metrics()["alive"] == 2 and reg.metrics()["deaths"] == 0

    t[0] = 43.0                             # actor-0 resumed; actor-1
    reg.observe(Heartbeat("actor-0"))       # died during the stall
    assert reg.tick() == [("actor-1", ALIVE, SUSPECT)]
    t[0] = 46.0
    reg.observe(Heartbeat("actor-0"))
    assert reg.tick() == [("actor-1", SUSPECT, DEAD)]
    assert reg.peers["actor-0"].state == ALIVE


def test_registry_merges_self_reported_rejoins_and_seen_liveness():
    """fleet_rejoins survives a learner restart: a FRESH registry credits
    the fleet's self-reported park->resume cycles; chunk-arrival times
    (observe_seen) keep a stat-dropping peer alive."""
    t = [0.0]
    comms = CommsConfig(suspect_after_s=2.0, dead_after_s=5.0)
    reg = FleetRegistry(comms, clock=lambda: t[0])
    reg.observe(Heartbeat("actor-0", rejoins=1))
    reg.observe(Heartbeat("actor-1", rejoins=1))
    assert reg.rejoins() == 2               # no DEAD->ALIVE seen here

    # chunks keep flowing while heartbeats drop: stays ALIVE
    t[0] = 4.0
    reg.observe_seen({"actor-0": 3.9})
    trans = reg.tick()
    assert ("actor-1", ALIVE, SUSPECT) in trans
    assert reg.peers["actor-0"].state == ALIVE

    # a DEAD peer revived by message arrival also counts as a rejoin
    t[0] = 20.0
    reg.tick()
    assert reg.peers["actor-0"].state == DEAD
    reg.observe_seen({"actor-0": 20.0})
    assert reg.peers["actor-0"].state == ALIVE
    assert reg.rejoins() == 3


def test_registry_gap_percentiles_and_table():
    t = [0.0]
    reg = FleetRegistry(CommsConfig(), clock=lambda: t[0])
    for i in range(1, 11):
        t[0] = float(i)
        reg.observe(Heartbeat("actor-0", fps=50.0))
    m = reg.metrics()
    assert m["hb_gap_p50_s"] == pytest.approx(1.0)
    assert m["hb_gap_p99_s"] == pytest.approx(1.0)
    table = format_fleet_table(reg.snapshot())
    assert "actor-0" in table and "ALIVE" in table and "rejoins" in table


def test_heartbeat_emitter_cadence_and_hooks():
    t = [0.0]
    beats = []
    em = HeartbeatEmitter(
        "actor-7", role="actor", interval_s=2.0,
        counters_fn=lambda: {"chunks_sent": 42, "acks_received": 40},
        park_fn=lambda: (True, 3), clock=lambda: t[0])
    assert em.maybe_beat(1) is None         # not due yet
    t[0] = 2.5
    em.tick(50)
    hb = em.maybe_beat(9)
    assert hb is not None and hb.identity == "actor-7"
    assert hb.param_version == 9 and hb.chunks_sent == 42
    assert hb.parked and hb.rejoins == 3
    assert hb.fps == pytest.approx(50 / 2.5, rel=0.01)
    assert em.maybe_beat(9) is None         # window reset
    beats.append(hb)


# -- restricted wire --------------------------------------------------------

def test_wire_roundtrips_every_message_type():
    import numpy as np

    from apex_tpu.actors.pool import ActorTimingStat, EpisodeStat
    from apex_tpu.runtime import wire

    msgs = [
        ("chunk", {"payload": {"frames": np.zeros((4, 3), np.uint8)},
                   "priorities": np.ones(4, np.float32), "n_trans": 4}),
        ("stat", EpisodeStat(1, 2.5, 30, 7)),
        ("stat", ActorTimingStat(0, 100.0, .1, .2, .3, .4, 256, True)),
        ("stat", Heartbeat("actor-0", fps=12.5, chunks_sent=3)),
        (5, {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}),
        np.float32(1.5),
    ]
    for msg in msgs:
        got = wire.restricted_loads(wire.dumps(msg))
        assert type(got) is type(msg)


def test_wire_rejects_non_allowlisted_globals():
    import os
    import pickle

    from apex_tpu.runtime import wire

    class Evil:
        def __reduce__(self):
            return (os.system, ("true",))

    with pytest.raises(wire.WireRejected):
        wire.restricted_loads(pickle.dumps(Evil()))
    # even benign-but-unlisted classes are rejected: allowlist, not
    # blocklist
    with pytest.raises(wire.WireRejected):
        wire.restricted_loads(pickle.dumps(CommsConfig()))


def test_receiver_counts_and_drops_rejected_payloads():
    """A hostile payload on the chunk socket costs one message (counted),
    earns no ack, and the pipe keeps working for honest peers."""
    import pickle

    import zmq

    from apex_tpu.runtime.transport import ChunkReceiver, ChunkSender

    comms = _comms()
    recv = ChunkReceiver(comms, queue_depth=8)
    recv.start()
    try:
        evil = zmq.Context.instance().socket(zmq.DEALER)
        evil.setsockopt(zmq.IDENTITY, b"mallory")
        evil.connect(f"tcp://127.0.0.1:{comms.batch_port}")

        class Evil:
            def __reduce__(self):
                import os
                return (os.system, ("true",))

        evil.send(pickle.dumps(("chunk", Evil())))
        deadline = time.monotonic() + 10
        while recv.rejected == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert recv.rejected == 1
        evil.close(linger=0)

        s = ChunkSender(comms, "actor-0")
        assert s.send_chunk({"n": 1})
        assert recv.chunks.get(timeout=5.0) == {"n": 1}
        s.close()
    finally:
        recv.stop()


# -- chaos harness ----------------------------------------------------------

class _StubSender:
    def __init__(self):
        self.sent = []
        self.chunks_sent = 0
        self.acks_received = 0

    def send_chunk(self, msg, stop_event=None, max_wait_s=None):
        self.sent.append(msg)
        self.chunks_sent += 1
        return True

    def send_stat(self, stat):
        pass

    def reset_credits(self):
        pass

    def close(self, *a, **kw):
        pass


def test_chaos_schedule_is_deterministic_per_identity():
    """Same seed + identity -> the same per-message drop/delay decisions,
    run after run; a different identity draws a different stream."""
    spec = {"drop_frac": 0.3, "delay_frac": 0.2, "delay_s": 0.0}

    def fates(identity, seed=7):
        plan = ChaosConfig(seed, spec).plan_for(identity)
        inner = _StubSender()
        cs = ChaosChunkSender(inner, plan, sleep=lambda s: None)
        fate = []
        for i in range(200):
            before = len(inner.sent)
            delayed_before = cs.delayed
            cs.send_chunk({"i": i})
            fate.append(("drop" if len(inner.sent) == before else
                         "delay" if cs.delayed > delayed_before else "send"))
        return fate

    a1, a2 = fates("actor-0"), fates("actor-0")
    assert a1 == a2
    assert fates("actor-1") != a1
    assert 30 < a1.count("drop") < 90          # ~0.3 of 200


def test_chaos_kill_disarms_on_respawned_lives(monkeypatch):
    """APEX_RESPAWN_COUNT>0 (exported by the supervisor) disarms kill
    entries so a deterministic kill-at-N cannot become a kill loop;
    drop/delay schedules stay live."""
    monkeypatch.setenv("CHAOS_SEED", "3")
    monkeypatch.setenv("CHAOS_SPEC",
                       '{"kill": {"actor-0": 5}, "drop_frac": 0.5}')
    cfg = chaos_from_env()
    assert cfg.plan_for("actor-0").kill_at == 5
    assert cfg.plan_for("actor-1").kill_at is None

    monkeypatch.setenv("APEX_RESPAWN_COUNT", "1")
    cfg = chaos_from_env()
    assert cfg.plan_for("actor-0").kill_at is None
    assert cfg.plan_for("actor-0").drop_frac == 0.5

    monkeypatch.setenv("CHAOS_SEED", "")      # empty string = chaos off
    assert chaos_from_env() is None


def test_chaos_publisher_stall_schedule():
    class _StubPub:
        def __init__(self):
            self.published = []

        def publish(self, version, params):
            self.published.append(version)

        def close(self):
            pass

    slept = []
    plan = ChaosConfig(1, {"stall_at": 2, "stall_s": 1.5}).plan_for(
        "learner")
    pub = ChaosParamPublisher(_StubPub(), plan, sleep=slept.append)
    for v in range(5):
        pub.publish(v, None)
    assert pub.inner.published == [0, 1, 2, 3, 4]   # stall delays, never drops
    assert slept == [1.5] and pub.stalls == 1


def test_chaos_drop_frac_over_real_sockets():
    """Dropped chunks consume no credit: with drop_frac=0.5 a
    window-of-3 sender still completes 40 sends, and the receiver gets
    exactly the non-dropped ones."""
    from apex_tpu.runtime.transport import ChunkReceiver, ChunkSender

    comms = _comms()
    recv = ChunkReceiver(comms, queue_depth=64)
    recv.start()
    try:
        plan = ChaosConfig(11, {"drop_frac": 0.5}).plan_for("actor-0")
        cs = ChaosChunkSender(ChunkSender(comms, "actor-0"), plan)
        for i in range(40):
            assert cs.send_chunk({"i": i})
        assert 5 < cs.dropped < 35
        expected = 40 - cs.dropped
        got = []
        deadline = time.monotonic() + 15
        while len(got) < expected and time.monotonic() < deadline:
            try:
                got.append(recv.chunks.get(timeout=0.5))
            except Exception:
                pass
        assert len(got) == expected
        cs.close()
    finally:
        recv.stop()


# -- park-and-rejoin --------------------------------------------------------

def test_park_controller_parks_and_rejoins_respawned_learner():
    """The whole drama in-process: params flow, the 'learner' dies (stops
    publishing), the actor parks; a 'respawned learner' re-releases the
    barrier and publishes — the parked actor reattaches in under a
    second, its credit window reset, rejoins counted."""
    from apex_tpu.runtime.transport import (ChunkSender, ParamPublisher,
                                            ParamSubscriber,
                                            barrier_release)

    comms = _comms(park_after_s=0.3, rejoin_backoff_s=0.05,
                   rejoin_backoff_max_s=0.2, rejoin_attempt_s=0.5)
    stop = threading.Event()
    sub = ParamSubscriber(comms)
    sender = ChunkSender(comms, "actor-0")
    park = ParkController(comms, "actor-0", stop, sub=sub, sender=sender)

    pub1 = ParamPublisher(comms)
    try:
        time.sleep(0.2)                       # SUB connect (slow joiner)
        pub1.publish(1, {"w": 1})
        deadline = time.monotonic() + 5
        got = None
        while got is None and time.monotonic() < deadline:
            got = sub.poll(100)
        assert got is not None and got[0] == 1
        park.note_params()
        pub1.close()                          # learner dies

        # wedge the window as an in-flight send would leave it
        sender._in_flight = sender.max_outstanding

        result = {}

        def parked_actor():
            result["got"] = park.park_and_rejoin()

        t = threading.Thread(target=parked_actor, daemon=True)
        time.sleep(0.4)                       # past park_after_s
        assert park.stale()
        t.start()
        time.sleep(0.3)
        assert park.parked

        # respawned learner: barrier for 1 peer, then first publish
        released = {}

        def learner2():
            released["n"] = barrier_release(comms, 1, timeout_s=10)
            pub2 = ParamPublisher(comms)
            try:
                end = time.monotonic() + 5
                while not result and time.monotonic() < end:
                    pub2.publish(2, {"w": 2})
                    time.sleep(0.05)
            finally:
                pub2.close()

        lt = threading.Thread(target=learner2, daemon=True)
        lt.start()
        t.join(timeout=15)
        lt.join(timeout=15)
        assert not t.is_alive(), "actor never rejoined"
        assert released["n"] == 1, "rejoin hello never reached the barrier"
        assert result["got"] is not None and result["got"][0] >= 2
        assert park.rejoins == 1 and not park.parked
        assert sender._in_flight == 0, "credit window not reset on rejoin"
        # the rejoin stashed the params for the adapter's next poll
        assert park.take_pending() is not None
        assert park.take_pending() is None
    finally:
        stop.set()
        sender.close(drain_s=0)
        sub.close()


def test_park_controller_does_not_park_while_params_flow():
    """Wedge-path false alarm guard: a backpressured-but-alive learner
    keeps publishing, so park_and_rejoin probes, stashes the params, and
    returns without parking or resetting credits."""
    from apex_tpu.runtime.transport import ParamPublisher, ParamSubscriber

    comms = _comms(park_after_s=0.2)
    stop = threading.Event()
    sub = ParamSubscriber(comms)
    park = ParkController(comms, "actor-0", stop, sub=sub)
    pub = ParamPublisher(comms)
    try:
        time.sleep(0.2)
        pub.publish(5, {"w": 5})
        time.sleep(0.3)                       # stale by clock, but a
        got = park.park_and_rejoin()          # publish is waiting
        assert got is not None and got[0] == 5
        assert park.parks == 0 and park.rejoins == 0
    finally:
        stop.set()
        pub.close()
        sub.close()


# -- status surface ---------------------------------------------------------

def test_status_server_round_trip():
    comms = _comms()
    reg = FleetRegistry(comms)
    reg.observe(Heartbeat("actor-0", fps=123.0, param_version=4))
    srv = FleetStatusServer(comms, reg)
    srv.start()
    try:
        snap = status_request(comms, learner_ip="127.0.0.1", timeout_s=5)
        assert snap is not None
        assert snap["peers"][0]["identity"] == "actor-0"
        assert snap["peers"][0]["fps"] == 123.0
        assert snap["metrics"]["alive"] == 1
    finally:
        srv.stop()


def test_status_cli_prints_fleet_table(capsys):
    from apex_tpu.runtime import cli

    comms = _comms()
    reg = FleetRegistry(comms)
    reg.observe(Heartbeat("actor-2", role="actor", fps=55.0))
    srv = FleetStatusServer(comms, reg)
    srv.start()
    try:
        rc = cli.main(["--role", "status",
                       "--status-port", str(comms.status_port)])
        out = capsys.readouterr().out
        assert rc in (0, None)
        assert "actor-2" in out and "ALIVE" in out
    finally:
        srv.stop()


def test_metrics_scrape_round_trip():
    """The Prometheus surface on the SAME status REP socket: b"metrics"
    returns text exposition (trainer-provided metrics_fn, or the
    registry-only fallback), and the pickled snapshot path keeps working
    beside it."""
    from apex_tpu.obs.metrics import metrics_request

    comms = _comms()
    reg = FleetRegistry(comms)
    reg.observe(Heartbeat("actor-0", role="actor", fps=88.0, wall_ts=1.0))

    calls = []

    def metrics_fn():
        calls.append(1)
        return ("# TYPE apex_fleet_alive gauge\n"
                "apex_fleet_alive 1.0\n"
                "apex_custom_gauge 42.0\n")

    srv = FleetStatusServer(comms, reg, metrics_fn=metrics_fn)
    srv.start()
    try:
        text = metrics_request(comms, learner_ip="127.0.0.1", timeout_s=5)
        assert text is not None and calls == [1]
        assert "apex_fleet_alive 1.0" in text
        assert "apex_custom_gauge 42.0" in text
        # the snapshot request still answers on the same socket
        snap = status_request(comms, learner_ip="127.0.0.1", timeout_s=5)
        assert snap is not None
        assert snap["peers"][0]["identity"] == "actor-0"
        assert snap["peers"][0]["clock_offset_s"] is not None
    finally:
        srv.stop()


def test_metrics_scrape_registry_fallback_and_cli(capsys):
    """Without a metrics_fn the server renders a fleet-only exposition
    from the registry; `--role status --metrics` prints it."""
    from apex_tpu.runtime import cli

    comms = _comms()
    reg = FleetRegistry(comms)
    reg.observe(Heartbeat("actor-3", role="actor", fps=12.0))
    srv = FleetStatusServer(comms, reg)
    srv.start()
    try:
        rc = cli.main(["--role", "status", "--metrics",
                       "--status-port", str(comms.status_port)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "# TYPE apex_fleet_alive gauge" in out
        assert 'apex_fleet_peer_fps{identity="actor-3"} 12.0' in out
    finally:
        srv.stop()


# -- host supervisor --------------------------------------------------------

def test_supervisor_respawn_budget_and_backoff():
    """ActorPool semantics at process scale: short-lived crashes double
    the backoff and burn budget; exhausting the budget halts with rc=1;
    the respawn count is exported to each life."""
    t = [0.0]
    sleeps = []
    lives = []

    def fake_run(cmd, env):
        lives.append(int(env["APEX_RESPAWN_COUNT"]))
        t[0] += 1.0                     # every life dies after 1s
        return 9

    rc = supervise(["role"], max_respawns=3, window_s=600, min_uptime_s=60,
                   backoff_s=1.0, backoff_max_s=4.0,
                   sleep=sleeps.append, clock=lambda: t[0], run=fake_run)
    assert rc == 1
    assert lives == [0, 1, 2, 3]        # initial life + 3 budgeted respawns
    assert len(sleeps) == 3
    # exponential with jitter in [0.5, 1.5) of the doubling base
    assert 1.0 <= sleeps[0] / 1.0 + 0.5 and sleeps[1] >= sleeps[0] * 0.5


def test_supervisor_clean_exit_and_budget_refresh():
    t = [0.0]

    def run_clean(cmd, env):
        t[0] += 120.0
        return 0

    assert supervise(["role"], run=run_clean, clock=lambda: t[0],
                     sleep=lambda s: None) == 0

    # long-lived lives never exhaust the budget: the window refreshes
    calls = []

    def run_long_then_clean(cmd, env):
        calls.append(1)
        t[0] += 700.0                   # outlives the window every time
        return 0 if len(calls) >= 6 else 5

    rc = supervise(["role"], max_respawns=2, window_s=600,
                   min_uptime_s=60, run=run_long_then_clean,
                   clock=lambda: t[0], sleep=lambda s: None)
    assert rc == 0 and len(calls) == 6


def test_supervisor_cli_subprocess_end_to_end():
    """The real module entry: a child that always exits nonzero exhausts
    a budget of 1 quickly; the supervisor reports and exits 1."""
    import subprocess
    import sys

    p = subprocess.run(
        [sys.executable, "-m", "apex_tpu.fleet.supervise",
         "--max-respawns", "1", "--min-uptime", "0.01",
         "--backoff", "0.01", "--backoff-max", "0.02", "--",
         sys.executable, "-c", "import sys; sys.exit(3)"],
        capture_output=True, text=True, timeout=60)
    assert p.returncode == 1
    assert "crash loop" in p.stdout


def test_supervisor_sigterm_terminates_child():
    """Killing a supervisor must take its child with it (PR 8 fix): the
    un-forwarded child used to survive as an orphan still bound to its
    role's ports, shadowing the next fleet on the same host."""
    import signal
    import subprocess
    import sys

    marker = "apex_supervise_child_marker"
    p = subprocess.Popen(
        [sys.executable, "-m", "apex_tpu.fleet.supervise", "--",
         sys.executable, "-c",
         f"import time; {marker} = 1; time.sleep(120)"])
    try:
        deadline = time.monotonic() + 30
        child_pid = None
        while child_pid is None and time.monotonic() < deadline:
            probe = subprocess.run(["pgrep", "-f", marker],
                                   capture_output=True, text=True)
            pids = [int(x) for x in probe.stdout.split()
                    if int(x) != p.pid]
            child_pid = pids[0] if pids else None
            time.sleep(0.1)
        assert child_pid is not None, "child never came up"
        p.send_signal(signal.SIGTERM)
        assert p.wait(timeout=30) != 0
        import os
        deadline = time.monotonic() + 10
        gone = False
        while time.monotonic() < deadline and not gone:
            try:
                os.kill(child_pid, 0)
                time.sleep(0.1)
            except ProcessLookupError:
                gone = True
        assert gone, "supervised child survived its supervisor"
    finally:
        if p.poll() is None:
            p.kill()


def test_supervisor_cli_rejects_missing_command():
    import subprocess
    import sys

    p = subprocess.run([sys.executable, "-m", "apex_tpu.fleet.supervise"],
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 2


# -- learner-epoch fencing on the param plane (PR 8) ------------------------

def test_param_plane_carries_learner_epoch():
    """An epoch-stamped publish updates the subscriber's learner_epoch
    while every consumer still sees the plain (version, params) tuple;
    unstamped (legacy) publishes leave the epoch untouched."""
    from apex_tpu.runtime.transport import ParamPublisher, ParamSubscriber

    comms = _comms()
    sub = ParamSubscriber(comms)
    pub = ParamPublisher(comms)
    try:
        time.sleep(0.2)                        # SUB connect (slow joiner)

        def publish_until_seen(version):
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                pub.publish(version, {"w": version})
                got = sub.poll(100)
                if got is not None and got[0] == version:
                    return got
            raise AssertionError("publish never arrived")

        got = publish_until_seen(1)            # unstamped: legacy 2-tuple
        assert got == (1, {"w": 1})
        assert sub.learner_epoch == 0

        pub.epoch = 7                          # stamped: 3-tuple on the wire
        got = publish_until_seen(2)
        assert got == (2, {"w": 2})
        assert sub.learner_epoch == 7
    finally:
        pub.close()
        sub.close()


class _EpochSub:
    """Scripted param stream with an epoch stamp, for the park decision
    table (no sockets: the barrier is monkeypatched).  ``delay_polls``
    makes the first probes miss, so the controller genuinely parks
    before the stream resumes."""

    def __init__(self, delay_polls: int = 1):
        self.learner_epoch = 0
        self.queue: list = []
        self.delay_polls = delay_polls

    def poll(self, timeout_ms: int = 0):
        if self.delay_polls > 0:
            self.delay_polls -= 1
            return None
        if self.queue:
            version, params, epoch = self.queue.pop(0)
            self.learner_epoch = epoch
            return (version, params)
        return None


@pytest.mark.parametrize("resume_epoch,expect_reset", [
    (1, False),      # same epoch: the learner STALLED — acks still coming
    (2, True),       # bumped epoch: a RESTART took the ack window with it
])
def test_park_decision_table_restart_vs_stall(monkeypatch, resume_epoch,
                                              expect_reset):
    monkeypatch.setattr("apex_tpu.runtime.transport.barrier_wait",
                        lambda *a, **kw: True)
    comms = CommsConfig(park_after_s=0.0)      # instantly stale
    stop = threading.Event()
    sub = _EpochSub()
    sub.learner_epoch = 1                      # epoch seen before the park
    sender = _StubSender()
    sender.resets = 0
    sender.reset_credits = lambda: setattr(
        sender, "resets", sender.resets + 1)
    park = ParkController(comms, "actor-0", stop, sub=sub, sender=sender,
                          sleep=lambda s: None)
    park._last_params = -1e9                   # long stale
    sub.queue.append((9, {"w": 9}, resume_epoch))
    got = park.park_and_rejoin()
    assert got == (9, {"w": 9})
    assert park.rejoins == 1
    assert sender.resets == (1 if expect_reset else 0)
    if expect_reset:
        assert park.restarts_seen == 1 and park.stall_resumes == 0
    else:
        assert park.stall_resumes == 1 and park.restarts_seen == 0


def test_park_unstamped_stream_keeps_legacy_reset():
    """A pre-fencing learner (no epoch stamps) must keep today's
    conservative behavior: every rejoin resets the credit window."""
    import unittest.mock as mock

    with mock.patch("apex_tpu.runtime.transport.barrier_wait",
                    return_value=True):
        comms = CommsConfig(park_after_s=0.0)
        stop = threading.Event()
        sub = _EpochSub()                      # epoch stays 0
        sender = _StubSender()
        sender.resets = 0
        sender.reset_credits = lambda: setattr(
            sender, "resets", sender.resets + 1)
        park = ParkController(comms, "actor-0", stop, sub=sub,
                              sender=sender, sleep=lambda s: None)
        park._last_params = -1e9
        sub.queue.append((3, {"w": 3}, 0))
        assert park.park_and_rejoin() == (3, {"w": 3})
        assert sender.resets == 1


# -- registry reactions (PR 8) -----------------------------------------------

def test_registry_dead_fraction_counts_roles_separately():
    t = [0.0]
    comms = CommsConfig(suspect_after_s=2.0, dead_after_s=5.0)
    reg = FleetRegistry(comms, clock=lambda: t[0])
    reg.observe(Heartbeat("actor-0", role="actor"))
    reg.observe(Heartbeat("actor-1", role="actor"))
    reg.observe(Heartbeat("replay-0", role="replay"))
    assert reg.dead_fraction() == 0.0
    t[0] = 20.0
    reg.tick()                                  # everyone DEAD
    reg.observe(Heartbeat("actor-1", role="actor"))   # one actor back
    assert reg.dead_fraction() == pytest.approx(0.5)
    assert reg.dead_fraction(roles=("replay",)) == 1.0
    assert reg.dead_fraction(roles=("evaluator",)) == 0.0   # none seen


def test_rejoin_barrier_admits_late_peers():
    from apex_tpu.runtime import transport

    comms = _comms()
    rb = transport.RejoinBarrier(comms)
    rb.start()
    try:
        assert transport.barrier_wait(comms, "late-actor", timeout_s=10)
        assert transport.barrier_wait(comms, "respawned-actor",
                                      timeout_s=10)
        deadline = time.monotonic() + 5
        while rb.admitted < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert rb.admitted == 2
    finally:
        rb.stop()


def test_heartbeat_resend_and_reroute_counters_reach_snapshot():
    reg = FleetRegistry(CommsConfig())
    reg.observe(Heartbeat("actor-0", role="actor", resends=4, rerouted=2))
    peer = reg.snapshot()["peers"][0]
    assert peer["resends"] == 4 and peer["rerouted"] == 2


# -- ack withholding (learner ingress fault) ---------------------------------

def test_ack_withholding_delays_acks_but_loses_no_chunk(monkeypatch):
    """The seeded ingress fault: acks for a scheduled chunk window park
    for hold_s, the sender's credit window exhausts (bounded sends fail
    and are RETRIED — counted as resends), then the withheld acks
    release and everything recovers with zero chunk loss."""
    from apex_tpu.runtime.transport import ChunkReceiver, ChunkSender

    monkeypatch.setenv("CHAOS_SEED", "5")
    monkeypatch.setenv(
        "CHAOS_SPEC",
        '{"ack_withhold": {"at": 0, "n": 2, "hold_s": 1.0}}')
    comms = _comms(max_outstanding_sends=2)
    recv = ChunkReceiver(comms, queue_depth=8, n_decoders=1)
    recv.start()
    sender = ChunkSender(comms, "actor-0")
    try:
        assert sender.send_chunk({"i": 0})
        assert sender.send_chunk({"i": 1})     # window now full, acks parked
        deadline = time.monotonic() + 10
        while recv.acks_withheld < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert recv.acks_withheld == 2
        # no credit: the bounded send fails and the caller retries
        assert not sender.send_chunk({"i": 2}, max_wait_s=0.2)
        sender.note_resend()
        ok = False
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:     # hold_s elapses mid-loop
            if sender.send_chunk({"i": 2}, max_wait_s=0.5):
                ok = True
                break
            sender.note_resend()
        assert ok, "withheld acks never released"
        got = [recv.chunks.get(timeout=5) for _ in range(3)]
        assert [g["i"] for g in got] == [0, 1, 2]   # delayed, never lost
        assert sender.resends >= 1
        # every chunk eventually acked — the window fully recovered
        deadline = time.monotonic() + 10
        while sender.acks_received < 3 and time.monotonic() < deadline:
            sender._drain_acks(50)
        assert sender.acks_received == 3
    finally:
        sender.close(drain_s=0)
        recv.stop()


# -- elastic scale supervision (PR 8) ----------------------------------------

def test_scale_decision_table():
    from apex_tpu.fleet.supervise import scale_decision

    assert scale_decision(0.9, 4, 1, 8) == 3    # drain-bound: retire one
    assert scale_decision(0.05, 4, 1, 8) == 5   # learner starving: add one
    assert scale_decision(0.3, 4, 1, 8) == 4    # healthy band: hold
    assert scale_decision(None, 4, 1, 8) == 4   # unreadable signal: hold
    assert scale_decision(0.9, 1, 1, 8) == 1    # clamped at the floor
    assert scale_decision(0.0, 8, 1, 8) == 8    # clamped at the ceiling


class _FakeChild:
    def __init__(self, cmd, env):
        self.cmd, self.env = cmd, env
        self.rc = None
        self.terminated = False

    def poll(self):
        return self.rc

    def terminate(self):
        self.terminated = True
        self.rc = -15


def test_scale_supervisor_spawns_substitutes_and_scales():
    from apex_tpu.fleet.supervise import ScaleSupervisor

    spawned: list[_FakeChild] = []

    def spawn(cmd, env):
        child = _FakeChild(cmd, env)
        spawned.append(child)
        return child

    probes = [0.05, 0.9]                        # starving, then drain-bound
    sup = ScaleSupervisor(["run", "--actor-id", "{slot}"], n_min=2,
                          n_max=4, probe=lambda: probes.pop(0),
                          spawn=spawn)
    sup._apply_target()
    assert sorted(sup.children) == [0, 1]
    assert spawned[0].cmd == ["run", "--actor-id", "0"]
    assert spawned[1].cmd == ["run", "--actor-id", "1"]
    assert spawned[0].env["APEX_RESPAWN_COUNT"] == "0"

    sup.tick()                                  # 0.05 -> scale up to 3
    assert sup.target == 3 and sorted(sup.children) == [0, 1, 2]
    assert sup.scale_ups == 1

    sup.children[1].rc = 137                    # a chaos kill: respawn
    sup.tick()                                  # 0.9 -> scale down to 2
    assert sup.target == 2 and sorted(sup.children) == [0, 1]
    assert sup.scale_downs == 1
    respawned = [c for c in spawned if c.cmd == ["run", "--actor-id", "1"]]
    assert len(respawned) == 2                  # original + one respawn
    assert respawned[1].env["APEX_RESPAWN_COUNT"] == "1"
    highest = [c for c in spawned if c.cmd == ["run", "--actor-id", "2"]]
    assert highest[0].terminated                # scale-down retires slot 2


def test_fleet_drain_frac_probe_reads_trainer_summary():
    """The scale supervisor's backpressure probe: one status round-trip
    to a server whose snapshot_fn is the trainer's fleet summary."""
    from apex_tpu.fleet.supervise import fleet_drain_frac

    comms = _comms()
    reg = FleetRegistry(comms)
    srv = FleetStatusServer(
        comms, reg,
        snapshot_fn=lambda: {"peers": [],
                             "metrics": {"actor_drain_frac": 0.42}})
    srv.start()
    try:
        got = fleet_drain_frac(learner_ip="127.0.0.1",
                               status_port=comms.status_port)
        assert got == pytest.approx(0.42)
    finally:
        srv.stop()


class _NullPool:
    """Interface-complete pool stub for trainer-level reaction tests."""

    procs: list = []

    def start(self):
        pass

    def cleanup(self):
        pass

    def poll_chunks(self, n, timeout=0.0):
        return []

    def poll_stats(self):
        return []

    def publish_params(self, version, params):
        pass


def test_learner_relaxes_and_restores_floor_on_dead_actor_capacity():
    """The registry-reaction loop closed (tentpole leg 1): with half the
    actor fleet DEAD the replay-ratio floor relaxes (the effective floor
    reads None), and it restores when the peers rejoin.  The reaction
    state and the dead fraction surface in fleet_summary."""
    import dataclasses

    from apex_tpu.config import small_test_config
    from apex_tpu.training.apex import ApexTrainer

    cfg = small_test_config()
    cfg = cfg.replace(comms=dataclasses.replace(
        cfg.comms, relax_floor_dead_frac=0.5))
    trainer = ApexTrainer(cfg, pool=_NullPool(), respawn_workers=False,
                          train_ratio=8.0, min_train_ratio=0.5)
    t = [0.0]
    reg = FleetRegistry(cfg.comms, clock=lambda: t[0])
    trainer.fleet = reg
    reg.observe(Heartbeat("actor-0", role="actor"))
    reg.observe(Heartbeat("actor-1", role="actor"))
    trainer._react_to_fleet(0)
    assert not trainer._floor_relaxed
    assert trainer._min_ratio_effective() == 0.5

    t[0] = 100.0
    reg.tick()                                   # both DEAD
    reg.observe(Heartbeat("actor-1", role="actor"))  # one rejoins
    assert reg.dead_fraction() == pytest.approx(0.5)
    trainer._react_to_fleet(0)
    assert trainer._floor_relaxed
    assert trainer._min_ratio_effective() is None
    assert trainer.floor_relaxes == 1

    reg.observe(Heartbeat("actor-0", role="actor"))  # capacity back
    trainer._react_to_fleet(0)
    assert not trainer._floor_relaxed
    assert trainer._min_ratio_effective() == 0.5

    summary = trainer.fleet_summary()["metrics"]
    assert summary["floor_relaxes"] == 1
    assert summary["floor_relaxed"] is False
    assert summary["dead_actor_frac"] == 0.0
    assert summary["learner_epoch"] == 1


def test_learner_epoch_survives_and_bumps_through_restore(tmp_path):
    """Epoch fencing through --restore: each restored life is one epoch
    past the checkpoint's writer, monotonically, including pre-fencing
    checkpoints (no learner_epoch in meta -> restore as life 2)."""
    from apex_tpu.config import small_test_config
    from apex_tpu.training.apex import ApexTrainer

    cfg = small_test_config()
    trainer = ApexTrainer(cfg, pool=_NullPool(), respawn_workers=False,
                          checkpoint_dir=str(tmp_path))
    assert trainer.learner_epoch == 1            # first life
    trainer.save_checkpoint()
    trainer.restore()
    assert trainer.learner_epoch == 2            # restart bumps
    trainer.steps_rate.total += 1                # a newer checkpoint
    trainer.save_checkpoint()
    trainer.restore()
    assert trainer.learner_epoch == 3            # monotone across lives
    # a pre-fencing checkpoint (no epoch key) restores as life 2
    trainer._apply_counters({"ingested": 0, "steps": 0,
                             "param_version": 0})
    assert trainer.learner_epoch == 2


# -- HTTP metrics sidecar (PR 6 follow-up) -----------------------------------

def test_http_metrics_sidecar_round_trip():
    import urllib.request

    from apex_tpu.obs.metrics import make_http_sidecar

    comms = _comms()
    reg = FleetRegistry(comms)
    reg.observe(Heartbeat("actor-5", role="actor", fps=9.0))
    srv = FleetStatusServer(comms, reg)
    srv.start()
    http_port = _free_ports(1)[0]
    sidecar = make_http_sidecar(comms, port=http_port,
                                learner_ip="127.0.0.1", bind="127.0.0.1")
    t = threading.Thread(target=sidecar.serve_forever, daemon=True)
    t.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{http_port}/metrics", timeout=10) as r:
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            body = r.read().decode()
        assert "# TYPE apex_fleet_alive gauge" in body
        assert 'apex_fleet_peer_fps{identity="actor-5"} 9.0' in body
        # non-metrics paths 404 instead of scraping
        import urllib.error
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{http_port}/nope", timeout=10)
    finally:
        sidecar.shutdown()
        sidecar.server_close()
        srv.stop()


def test_http_metrics_sidecar_503_when_learner_gone():
    import urllib.error
    import urllib.request

    from apex_tpu.obs.metrics import make_http_sidecar

    comms = _comms()                            # nothing listening
    http_port = _free_ports(1)[0]
    sidecar = make_http_sidecar(comms, port=http_port,
                                learner_ip="127.0.0.1", bind="127.0.0.1",
                                timeout_s=0.3)
    t = threading.Thread(target=sidecar.serve_forever, daemon=True)
    t.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                f"http://127.0.0.1:{http_port}/metrics", timeout=10)
        assert exc.value.code == 503
    finally:
        sidecar.shutdown()
        sidecar.server_close()


# -- adapters ---------------------------------------------------------------

def test_socket_adapters_expose_fleet_hooks():
    """The roles.py adapters surface wire counters and park state to the
    worker loops' HeartbeatEmitter without the loops knowing about
    sockets."""
    from apex_tpu.runtime.roles import _ChunkQueueAdapter, _ParamQueueAdapter

    comms = _comms()
    stop = threading.Event()

    class _Sub:
        def poll(self, timeout_ms=0):
            return None

    sender = _StubSender()
    park = ParkController(comms, "actor-0", stop, sub=_Sub(), sender=sender)
    chunk_ad = _ChunkQueueAdapter(sender, stop, park=park)
    param_ad = _ParamQueueAdapter(_Sub(), park=park)
    assert chunk_ad.wire_counters() == {"chunks_sent": 0,
                                        "acks_received": 0,
                                        "resends": 0, "rerouted": 0}
    assert param_ad.park_state() == (False, 0)
    chunk_ad.put(("chunk", 0, {"n": 1}))
    assert sender.sent == [{"n": 1}]
    assert chunk_ad.wire_counters()["chunks_sent"] == 1
