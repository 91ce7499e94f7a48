"""Multi-host plane: localhost all-roles topology over real TCP sockets.

The reference exercises its multi-node system by running every role on
127.0.0.1 (``origin_repo/run.sh:1-5``); same trick here, in CI: the learner
(with its socket RemotePool) runs in the test process, actors and the
evaluator run as real spawned processes connected only by TCP — barrier,
CONFLATE param stream, credit-windowed chunk stream, stat stream all live.
"""

import dataclasses
import multiprocessing as mp
import os
import socket

import numpy as np
import pytest

from apex_tpu.config import RoleIdentity, small_test_config


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


def _test_config(n_actors: int):
    cfg = small_test_config(capacity=2048, batch_size=32, n_actors=n_actors)
    cfg = cfg.replace(actor=dataclasses.replace(
        cfg.actor, eps_anneal_steps=500, eps_alpha=3.0))
    batch_port, param_port, barrier_port = _free_ports(3)
    cfg = cfg.replace(comms=dataclasses.replace(
        cfg.comms, batch_port=batch_port, param_port=param_port,
        barrier_port=barrier_port))
    return cfg


def _actor_main(cfg, actor_id, n_actors):
    from apex_tpu.runtime.roles import run_actor
    run_actor(cfg, RoleIdentity(role="actor", actor_id=actor_id,
                                n_actors=n_actors), barrier_timeout_s=60)


def _evaluator_main(cfg):
    from apex_tpu.runtime.roles import run_evaluator
    run_evaluator(cfg, RoleIdentity(role="evaluator"), episodes=0,
                  max_steps=200, barrier_timeout_s=60)


@pytest.mark.slow
def test_localhost_all_roles_topology():
    n_actors = 2
    cfg = _test_config(n_actors)
    ctx = mp.get_context("spawn")

    saved = {"JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS")}
    os.environ["JAX_PLATFORMS"] = "cpu"
    procs = []
    try:
        for i in range(n_actors):
            procs.append(ctx.Process(target=_actor_main,
                                     args=(cfg, i, n_actors), daemon=True))
        procs.append(ctx.Process(target=_evaluator_main, args=(cfg,),
                                 daemon=True))
        for p in procs:
            p.start()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    from apex_tpu.runtime.roles import run_learner
    try:
        trainer = run_learner(cfg, n_peers=n_actors + 1, total_steps=120,
                              max_seconds=180, barrier_timeout_s=60,
                              train_ratio=8.0)
        # the fused learner trained on socket-delivered chunks
        assert trainer.steps_rate.total >= 120
        assert trainer.ingested >= cfg.replay.warmup
        assert trainer.param_version >= 2
        # actor episode stats crossed the wire
        rewards = trainer.log.history.get("learner/episode_reward")
        assert rewards, "no episode stats arrived over TCP"
        # the evaluator role reported scores (actor_id == -1)
        ids = [v for _, v in trainer.log.history.get("learner/actor_id", [])]
        assert -1.0 in ids, "no evaluator stats arrived"
        # learner-side policy sanity via the standard eval path
        assert np.isfinite(trainer.evaluate(episodes=1, max_steps=100))
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            p.join(timeout=10)


@pytest.mark.slow
def test_topology_sharded_learner_vector_actors():
    """The flagship scale topology in miniature: VECTORIZED actors (2
    processes x 3 env slots) feed the dp=8 SHARDED learner over real TCP —
    chunk aggregation round-robins whole chunks across 8 per-chip frame
    pools, gradients pmean over the virtual mesh, params broadcast back to
    the fleet.  This is 'N remote actors vs an 8-chip learner'
    (BASELINE.md north star) end to end in CI."""
    n_actors = 2
    cfg = _test_config(n_actors)
    cfg = cfg.replace(
        actor=dataclasses.replace(cfg.actor, n_envs_per_actor=3),
        learner=dataclasses.replace(cfg.learner, mesh_shape=(8,)))
    ctx = mp.get_context("spawn")

    saved = {"JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS")}
    os.environ["JAX_PLATFORMS"] = "cpu"
    procs = []
    try:
        for i in range(n_actors):
            procs.append(ctx.Process(target=_actor_main,
                                     args=(cfg, i, n_actors), daemon=True))
        for p in procs:
            p.start()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    from apex_tpu.runtime.roles import run_learner
    try:
        trainer = run_learner(cfg, n_peers=n_actors, total_steps=60,
                              max_seconds=240, barrier_timeout_s=60,
                              train_ratio=8.0)
        assert trainer.n_dp == 8
        assert trainer.steps_rate.total >= 60
        assert trainer.ingested >= cfg.replay.warmup
        # stats carry GLOBAL slot ids from the vector workers: 2 procs x 3
        # slots = ids in 0..5, with at least one beyond the scalar range
        ids = [v for _, v in trainer.log.history.get("learner/actor_id", [])]
        assert ids and max(ids) >= 2, f"vector slots missing: {set(ids)}"
        assert np.isfinite(trainer.evaluate(episodes=1, max_steps=100))
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            p.join(timeout=10)


def test_remote_pool_reports_silent_peers():
    """A remote actor that stops sending shows up in silent_peers after
    the threshold (the learner can't respawn remote processes, but it no
    longer loses them silently)."""
    import time as time_mod

    from apex_tpu.runtime.transport import RemotePool

    cfg = _test_config(1)
    pool = RemotePool(cfg.comms, n_peers=0, barrier_timeout_s=1)
    try:
        now = time_mod.monotonic()
        pool.receiver.last_seen = {"actor-0": now - 100.0,
                                   "actor-1": now - 1.0,
                                   "evaluator-0": now - 500.0}
        # only chunk senders count: the quiet evaluator is NOT a false alarm
        pool.receiver._chunk_senders = {"actor-0", "actor-1"}
        assert pool.silent_peers(threshold_s=30.0) == ["actor-0"]
        assert pool.silent_peers(threshold_s=200.0) == []
    finally:
        pool.receiver.stop()


_CONFIG_CLASSES = ("ReplayConfig", "LearnerConfig", "ActorConfig",
                   "EnvConfig", "R2D2Config", "AQLConfig", "CommsConfig",
                   "ApexConfig", "RoleIdentity")


@pytest.mark.parametrize("cls_name", _CONFIG_CLASSES)
def test_every_config_field_has_a_reader(cls_name):
    """A field of ``config.py`` that nothing in ``apex_tpu/`` names is a
    switch wired to nothing: it goes, or gets its reader."""
    import re
    from pathlib import Path

    import apex_tpu
    from apex_tpu import config

    package = Path(apex_tpu.__file__).parent
    sources = [p.read_text() for p in sorted(package.rglob("*.py"))
               if p != package / "config.py"]
    assert set(_CONFIG_CLASSES) == {
        name for name, obj in vars(config).items()
        if dataclasses.is_dataclass(obj) and isinstance(obj, type)}
    unread = [f.name for f in dataclasses.fields(getattr(config, cls_name))
              if not any(re.search(rf"\b{f.name}\b", text)
                         for text in sources)]
    assert not unread, f"{cls_name}: no reader in apex_tpu/ for {unread}"


def test_cli_parser_roles_and_env_twins(monkeypatch):
    from apex_tpu.runtime.cli import (build_parser, config_from_args,
                                      identity_from_args)
    monkeypatch.setenv("APEX_ROLE", "actor")
    monkeypatch.setenv("ACTOR_ID", "3")
    monkeypatch.setenv("N_ACTORS", "8")
    monkeypatch.setenv("LEARNER_IP", "10.1.2.3")
    args = build_parser().parse_args(["--env-id", "ApexCartPole-v0"])
    assert args.role == "actor"
    ident = identity_from_args(args)
    assert (ident.actor_id, ident.n_actors, ident.learner_ip) == \
        (3, 8, "10.1.2.3")
    cfg = config_from_args(args)
    assert cfg.env.env_id == "ApexCartPole-v0"
    # flags beat env vars
    args2 = build_parser().parse_args(["--role", "evaluator"])
    assert args2.role == "evaluator"
    # vector actors reachable from the CLI and its env-var twin
    monkeypatch.setenv("N_ENVS_PER_ACTOR", "16")
    cfg3 = config_from_args(build_parser().parse_args([]))
    assert cfg3.actor.n_envs_per_actor == 16
    cfg4 = config_from_args(
        build_parser().parse_args(["--n-envs-per-actor", "32"]))
    assert cfg4.actor.n_envs_per_actor == 32


def test_cli_replay_service_flags_and_env_twins(monkeypatch):
    """The replay-service topology flags ride the shared COMMON set with
    env twins, like the ports — one export configures the whole fleet."""
    from apex_tpu.runtime.cli import (build_parser, config_from_args,
                                      identity_from_args)
    args = build_parser().parse_args([])
    cfg = config_from_args(args)
    assert cfg.comms.replay_shards == 0           # default: in-learner
    assert cfg.comms.replay_strict_order

    monkeypatch.setenv("APEX_REPLAY_SHARDS", "4")
    monkeypatch.setenv("APEX_REPLAY_PORT_BASE", "54001")
    monkeypatch.setenv("REPLAY_IP", "10.9.8.7")
    monkeypatch.setenv("SHARD_ID", "2")
    args = build_parser().parse_args(["--role", "replay"])
    cfg = config_from_args(args)
    assert cfg.comms.replay_shards == 4
    assert cfg.comms.replay_port_base == 54001
    assert args.shard_id == 2
    assert identity_from_args(args).replay_ip == "10.9.8.7"
    # flags beat env twins; --replay-loose flips the ordering contract
    args = build_parser().parse_args(["--replay-shards", "2",
                                      "--replay-loose"])
    cfg = config_from_args(args)
    assert cfg.comms.replay_shards == 2
    assert not cfg.comms.replay_strict_order


def test_cli_shard_snapshot_flags_and_env_twins(monkeypatch):
    """Shard durability knobs (PR 8): snapshot dir/cadence have env
    twins so run_local.sh and the deploy bootstraps configure the whole
    shard fleet with two exports."""
    from apex_tpu.runtime.cli import build_parser, config_from_args

    args = build_parser().parse_args([])
    assert args.replay_snapshot_dir is None
    assert config_from_args(args).comms.replay_snapshot_s == 0.0

    monkeypatch.setenv("APEX_REPLAY_SNAPSHOT_DIR", "/tmp/snaps")
    monkeypatch.setenv("APEX_REPLAY_SNAPSHOT_S", "2.5")
    args = build_parser().parse_args([])
    assert args.replay_snapshot_dir == "/tmp/snaps"
    assert config_from_args(args).comms.replay_snapshot_s == 2.5

    args = build_parser().parse_args(["--replay-snapshot-dir", "/e",
                                      "--replay-snapshot-every", "9"])
    assert args.replay_snapshot_dir == "/e"     # flags beat env twins
    assert config_from_args(args).comms.replay_snapshot_s == 9.0


@pytest.mark.slow
def test_actor_rejoin_after_kill_clears_silent_peers():
    """The supervisor-respawn contract (deploy/actor.sh + roles.py
    _join_fleet / transport.barrier_wait rejoin): kill the only actor
    mid-run; the learner's
    silent_peers flags it; a respawned actor with the SAME identity
    rejoins PAST the long-gone startup barrier by observing the param
    stream, resumes shipping chunks, and silent_peers clears."""
    import threading
    import time as time_mod

    import pytest

    from apex_tpu.runtime.transport import RemotePool
    from apex_tpu.training.apex import ApexTrainer

    cfg = _test_config(1)
    cfg = cfg.replace(replay=dataclasses.replace(cfg.replay, warmup=128))
    ctx = mp.get_context("spawn")
    pool = RemotePool(cfg.comms, n_peers=1, barrier_timeout_s=60)
    trainer = ApexTrainer(cfg, publish_min_seconds=0.1, train_ratio=8.0,
                          pool=pool)

    saved = {"JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS")}
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        actor = ctx.Process(target=_actor_main, args=(cfg, 0, 1),
                            daemon=True)
        actor.start()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    done = threading.Event()
    respawn = None
    try:
        t = threading.Thread(
            target=lambda: (trainer.train(total_steps=10 ** 9,
                                          max_seconds=300), done.set()),
            daemon=True)
        t.start()

        def wait_for(cond, timeout, what):
            deadline = time_mod.monotonic() + timeout
            while time_mod.monotonic() < deadline:
                if cond():
                    return
                time_mod.sleep(0.25)
            pytest.fail(f"timed out waiting for {what}")

        # phase 1: the actor joined and ships chunks.  Liveness is WAITED
        # for, not asserted instantly: during the first ingest compile the
        # bounded queues fill and the socket thread stops receiving, so
        # last_seen can legitimately be seconds stale at this moment.
        wait_for(lambda: trainer.ingested > 0, 60, "first chunks")
        wait_for(lambda: pool.silent_peers(threshold_s=5.0) == [], 30,
                 "initial liveness")

        # phase 2: SIGKILL the actor; it goes silent
        actor.kill()
        actor.join(timeout=10)
        wait_for(lambda: pool.silent_peers(threshold_s=3.0) == ["actor-0"],
                 30, "silence detection")

        # phase 3: respawn with the same identity — the barrier is gone,
        # so this exercises the param-stream rejoin path
        ingested_before = trainer.ingested
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            respawn = ctx.Process(target=_actor_main, args=(cfg, 0, 1),
                                  daemon=True)
            respawn.start()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        wait_for(lambda: pool.silent_peers(threshold_s=3.0) == []
                 and trainer.ingested > ingested_before,
                 90, "rejoin + silence clearing")
    finally:
        for p in (actor, respawn):
            if p is not None:
                p.terminate()
                p.join(timeout=10)
        trainer.request_stop()  # train() returns at its next iteration,
        done.wait(timeout=60)   # unwinding pool.cleanup() (bound ports)


def _aql_actor_main(cfg, actor_id, n_actors):
    from apex_tpu.runtime.roles import run_actor
    run_actor(cfg, RoleIdentity(role="actor", actor_id=actor_id,
                                n_actors=n_actors), family="aql",
              barrier_timeout_s=60)


def _r2d2_actor_main(cfg, actor_id, n_actors):
    from apex_tpu.runtime.roles import run_actor
    run_actor(cfg, RoleIdentity(role="actor", actor_id=actor_id,
                                n_actors=n_actors), family="r2d2",
              barrier_timeout_s=60)


@pytest.mark.slow
def test_localhost_r2d2_topology():
    """The recurrent family over real TCP (C13/C14 for the third model
    family): VECTORIZED stateful actor processes (2 env slots each, one
    batched [B, H] carry) ship grouped sequence messages to the socket
    learner, which trains the fused sequence step and publishes back."""
    n_actors = 2
    cfg = _test_config(n_actors)
    cfg = cfg.replace(
        env=dataclasses.replace(cfg.env, env_id="ApexCartPolePO-v0"),
        actor=dataclasses.replace(cfg.actor, n_envs_per_actor=2))
    ctx = mp.get_context("spawn")

    saved = {"JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS")}
    os.environ["JAX_PLATFORMS"] = "cpu"
    procs = []
    try:
        for i in range(n_actors):
            procs.append(ctx.Process(target=_r2d2_actor_main,
                                     args=(cfg, i, n_actors), daemon=True))
        for p in procs:
            p.start()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    from apex_tpu.runtime.roles import run_learner
    try:
        trainer = run_learner(cfg, n_peers=n_actors, total_steps=25,
                              max_seconds=180, family="r2d2",
                              barrier_timeout_s=60)
        assert trainer.steps_rate.total >= 25
        assert trainer.ingested >= cfg.replay.warmup
        assert trainer.param_version >= 2
        assert trainer.log.history.get("learner/episode_reward")
        assert np.isfinite(trainer.evaluate(episodes=1, max_steps=60))
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            p.join(timeout=10)


@pytest.mark.slow
def test_localhost_aql_topology():
    """The AQL family over real TCP (C13/C14 for the second model family):
    AQL actor processes ship a_mu-carrying chunks to the socket learner,
    which trains the fused two-loss step and publishes back."""
    n_actors = 2
    cfg = _test_config(n_actors)
    cfg = cfg.replace(
        env=dataclasses.replace(cfg.env, env_id="ApexContinuousNav-v0"),
        aql=dataclasses.replace(cfg.aql, propose_sample=8,
                                uniform_sample=16))
    ctx = mp.get_context("spawn")

    saved = {"JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS")}
    os.environ["JAX_PLATFORMS"] = "cpu"
    procs = []
    try:
        for i in range(n_actors):
            procs.append(ctx.Process(target=_aql_actor_main,
                                     args=(cfg, i, n_actors), daemon=True))
        for p in procs:
            p.start()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    from apex_tpu.runtime.roles import run_learner
    try:
        trainer = run_learner(cfg, n_peers=n_actors, total_steps=30,
                              max_seconds=180, family="aql",
                              barrier_timeout_s=60, train_ratio=8.0)
        assert trainer.steps_rate.total >= 30
        assert trainer.ingested >= cfg.replay.warmup
        assert trainer.param_version >= 2
        assert trainer.log.history.get("learner/episode_reward")
        assert np.isfinite(trainer.evaluate(episodes=1, max_steps=40))
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            p.join(timeout=10)


def test_chunk_sender_close_drains_inflight_window():
    """close() must not drop the last window of chunks: linger=0 discards
    unflushed messages, so close drains the ack-credit window first — a
    full window sent then immediately closed still arrives intact."""
    from apex_tpu.runtime.transport import ChunkReceiver, ChunkSender

    cfg = _test_config(1)
    recv = ChunkReceiver(cfg.comms, queue_depth=16)
    recv.start()
    try:
        s = ChunkSender(cfg.comms, "actor-0")
        w = cfg.comms.max_outstanding_sends
        for i in range(w):                 # exactly one full credit window
            assert s.send_chunk({"i": i, "blob": b"y" * 20_000})
        s.close(drain_s=10.0)              # returns early once acks land
        got = sorted(recv.chunks.get(timeout=5.0)["i"] for _ in range(w))
        assert got == list(range(w))
    finally:
        recv.stop()


def test_chunk_receiver_decode_pipeline_credits_flow():
    """The decoder-pool receiver (reference learner.py:71-114's N pullers):
    with a credit window of 3, a sender can only complete >3 sends if acks
    flow back through the decode pipeline; chunks and stats all arrive
    intact across 4 decoder threads."""
    import threading as th

    from apex_tpu.runtime.transport import ChunkReceiver, ChunkSender

    cfg = _test_config(1)
    recv = ChunkReceiver(cfg.comms, queue_depth=64, n_decoders=4)
    assert len(recv._decoders) == 4
    recv.start()
    n_chunks, senders = 12, 2
    try:
        def sender_body(sid):
            s = ChunkSender(cfg.comms, f"actor-{sid}")
            try:
                for i in range(n_chunks):
                    assert s.send_chunk({"sid": sid, "i": i,
                                         "blob": b"x" * 50_000})
                    s.send_stat({"sid": sid, "ep": i})
            finally:
                s.close()

        threads = [th.Thread(target=sender_body, args=(sid,))
                   for sid in range(senders)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive(), "sender wedged: credits not flowing"

        got = []
        deadline = 20.0
        import time as time_mod
        end = time_mod.monotonic() + deadline
        while len(got) < senders * n_chunks and time_mod.monotonic() < end:
            try:
                got.append(recv.chunks.get(timeout=0.5))
            except Exception:
                pass
        assert len(got) == senders * n_chunks
        # per-sender arrival order is preserved enough to recover every
        # message exactly once
        per = {sid: sorted(m["i"] for m in got if m["sid"] == sid)
               for sid in range(senders)}
        for sid in range(senders):
            assert per[sid] == list(range(n_chunks))
        with recv._peers_lock:
            assert recv._chunk_senders == {"actor-0", "actor-1"}
    finally:
        recv.stop()
