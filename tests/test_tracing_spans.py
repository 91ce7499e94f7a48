"""The span primitive (``TraceRing.span``), the learner loop's phases, the
step program's trace scopes, policy lag in steps, and the benchmark's
reductions of all three (``benchmark/spans.py`` and its readers).

Tier-1: a scripted pool and fake clocks on the CPU; the device side is
checked on a synthetic ``.xplane.pb`` written here byte by byte and on the
trace recorded on the chip beside ``benchmark/tests/fixtures/small``."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import struct
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.config import small_test_config
from apex_tpu.obs import trace as obs_trace
from apex_tpu.obs.spans import LearnerObs
from apex_tpu.obs.trace import TraceRing
from benchmark import spans
from tests.test_ingest_pipeline import ScriptedPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "benchmark", "tests", "fixtures")
NEW_METRICS = (
    "loop_prep_share_pct", "loop_dispatch_share_pct",
    "loop_unnamed_share_pct", "idle_unattributed_pct", "ingest_ms",
    "sample_ms", "gather_ms", "update_ms", "writeback_ms",
    "step_unscoped_share_pct", "policy_lag_p95_steps")


# -- the span primitive --------------------------------------------------------

class _FakeAnnotation:
    entered: list = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs

    def __enter__(self):
        _FakeAnnotation.entered.append((self.name, self.kwargs))
        return self

    def __exit__(self, *exc):
        return False


def test_span_off_reads_no_clock_and_records_nothing(monkeypatch):
    ring = TraceRing("learner", enabled=False)

    def boom():
        raise AssertionError("clock read with tracing off")

    monkeypatch.setattr(time, "perf_counter", boom)
    monkeypatch.setattr(obs_trace, "_ANNOTATION", _FakeAnnotation)
    _FakeAnnotation.entered = []
    first = ring.span("dispatch", "learner-hot-loop", {"it": 1})
    with first as sp:
        sp.note(kind="fused")
    assert ring.span("beta") is first           # one shared no-op object
    assert not _FakeAnnotation.entered
    assert not [ev for ev in ring.to_chrome()["traceEvents"]
                if ev.get("ph") != "M"]


def test_span_on_makes_one_ring_event_and_one_annotation(monkeypatch):
    monkeypatch.setattr(obs_trace, "_ANNOTATION", _FakeAnnotation)
    _FakeAnnotation.entered = []
    ring = TraceRing("learner", enabled=True)
    with ring.span("loop_iter", "learner-hot-loop", {"it": 7}) as sp:
        sp.note(kind="train")
    events = [ev for ev in ring.to_chrome()["traceEvents"]
              if ev.get("ph") == "X"]
    assert len(events) == 1
    assert events[0]["name"] == "loop_iter" and events[0]["dur"] >= 0
    assert events[0]["args"] == {"it": 7, "kind": "train"}
    assert _FakeAnnotation.entered == [("loop_iter", {"it": 7})]
    # a profiler trace alone (--profile-dir): the annotation, no ring event
    quiet = TraceRing("learner", enabled=False)
    quiet.annotate(True)
    with quiet.span("dispatch", args={"program": "jit_train_step"}):
        pass
    assert _FakeAnnotation.entered[-1] == (
        "dispatch", {"program": "jit_train_step"})
    assert len(quiet.to_chrome()["traceEvents"]) == 1      # the label only
    quiet.annotate(False)
    assert quiet.span("dispatch") is obs_trace._NO_SPAN


def test_real_annotation_is_importable_and_nests():
    ring = TraceRing("learner", enabled=True)
    with ring.span("loop_iter", args={"it": 0}):
        with ring.span("dispatch", args={"it": 0, "program": "jit_x"}):
            pass
    names = [ev["name"] for ev in ring.to_chrome()["traceEvents"]
             if ev.get("ph") == "X"]
    assert names == ["dispatch", "loop_iter"]       # recorded at exit


# -- the learner loop's phases -------------------------------------------------------

def test_in_flight_bound_waits_under_a_span_of_its_own():
    """At most two updates enqueued: the third pass asks for the oldest
    loss.  Ready already (a loop the host paces): no wait and no span.
    Not ready (a loop the device paces): one ``in_flight_wait`` span
    around the wait, so the ring tells it from host work."""
    import collections
    import contextlib
    import types

    from apex_tpu.training.apex import ConcurrentTrainer

    seen = []

    class Loss:
        def __init__(self, ready):
            self.ready, self.waited = ready, False

        def is_ready(self):
            return self.ready

        def block_until_ready(self):
            self.waited = True

    @contextlib.contextmanager
    def span(name):
        seen.append(name)
        yield

    loop = types.SimpleNamespace(_in_flight=collections.deque(),
                                 max_steps_in_flight=2, _span=span)
    losses = [Loss(True), Loss(False), Loss(True), Loss(True)]
    for loss in losses:
        ConcurrentTrainer._bound_in_flight(loop, {"loss": loss})
    assert [x.waited for x in losses] == [False, True, False, False]
    assert seen == ["in_flight_wait"]
    assert len(loop._in_flight) == 2


def _scripted_messages(n: int = 24) -> list[dict]:
    from apex_tpu.actors.pool import drain_builder_chunks
    from apex_tpu.obs import spans as obs_spans
    from apex_tpu.replay.frame_chunks import FrameChunkBuilder

    rng = np.random.default_rng(31)
    builder = FrameChunkBuilder(3, 0.99, 1, (4,), chunk_transitions=8,
                                frame_dtype=np.float32)
    msgs: list[dict] = []
    while len(msgs) < n:
        builder.begin_episode(rng.normal(size=4).astype(np.float32))
        ep_len = int(rng.integers(4, 30))
        for t in range(ep_len):
            builder.add_step(int(rng.integers(0, 2)), float(rng.normal()),
                             rng.normal(size=2).astype(np.float32),
                             rng.normal(size=4).astype(np.float32),
                             terminated=t == ep_len - 1, truncated=False)
        msgs.extend(drain_builder_chunks(builder))
    msgs = msgs[:n]
    for msg in msgs:
        obs_spans.mark_send(msg, param_version=1)
    return msgs


def _small_trainer(msgs, scan_steps: int = 1):
    from apex_tpu.training.apex import ApexTrainer

    cfg = small_test_config(capacity=256, batch_size=8, n_actors=1)
    cfg = cfg.replace(
        replay=dataclasses.replace(cfg.replay, warmup=32),
        learner=dataclasses.replace(cfg.learner, target_update_interval=50,
                                    scan_steps=scan_steps))
    return ApexTrainer(cfg, pool=ScriptedPool(msgs),
                       publish_min_seconds=30.0, respawn_workers=False)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One small trainer run on the CPU with the trace ring live; the
    ring's Chrome events and the trainer."""
    mp = pytest.MonkeyPatch()
    mp.setenv("APEX_TRACE_DIR", str(tmp_path_factory.mktemp("ring")))
    mp.setenv("APEX_TRACE_FLUSH_S", "0")            # no flusher thread
    obs_trace.reset_for_tests()
    try:
        trainer = _small_trainer(_scripted_messages())
        trainer.train(total_steps=12, max_seconds=120, log_every=4)
        chrome = obs_trace.get_ring().to_chrome()
    finally:
        mp.undo()
        obs_trace.reset_for_tests()
    return chrome, trainer


def _on_track(chrome: dict, track: str) -> list[dict]:
    tid = next(ev["tid"] for ev in chrome["traceEvents"]
               if ev.get("name") == "thread_name"
               and ev["args"]["name"] == track)
    return [ev for ev in chrome["traceEvents"]
            if ev.get("ph") == "X" and ev["tid"] == tid]


def test_loop_iter_spans_hold_their_children(traced_run):
    chrome, trainer = traced_run
    events = _on_track(chrome, "learner-hot-loop")
    passes = [ev for ev in events if ev["name"] == "loop_iter"]
    assert len(passes) >= 12
    its = [ev["args"]["it"] for ev in passes]
    assert its == sorted(set(its))                  # one span a pass
    assert {ev["args"]["kind"] for ev in passes} <= {
        "fused", "train", "ingest", "idle"}
    assert {"fused", "ingest"} <= {ev["args"]["kind"] for ev in passes}
    by_it = {ev["args"]["it"]: ev for ev in passes}
    children = [ev for ev in events
                if ev["name"] not in ("loop_iter", "host_gap")]
    assert {"poll_slot", "dispatch_key", "beta", "dispatch", "obs_join",
            "drain_stats", "log_scalars"} <= {ev["name"] for ev in children}
    for ev in children:
        it = ev["args"]["it"]
        if it not in by_it:             # the publish before the first pass
            assert ev["name"] == "publish_handoff"
            continue
        parent = by_it[it]
        assert parent["ts"] - 1 <= ev["ts"]
        assert ev["ts"] + ev["dur"] <= parent["ts"] + parent["dur"] + 1
    # which passes trained alone depends on when the ring ran dry
    programs = {ev["args"]["program"] for ev in children
                if ev["name"] == "dispatch"}
    assert {"jit_fused_step", "jit_ingest"} <= programs \
        <= {"jit_fused_step", "jit_ingest", "jit_train_step"}


def _host_gap_leaves_out_the_dispatch_interval(events: list[dict]) -> None:
    """``host_gap`` runs from ``gap.dispatch_returned()`` to the next
    ``gap.about_to_dispatch()``, as the hand-written sites had it: the
    eager convert of beta, the jitted call and the hand-over of its
    results (``adopt``, inside ``dispatch``) all lie outside every gap."""
    def edges(name):
        return sorted((ev["ts"], ev["ts"] + ev["dur"]) for ev in events
                      if ev["name"] == name)

    dispatches, adopts = edges("dispatch"), edges("adopt")
    assert len(adopts) == len(dispatches)
    for (d0, d1), (a0, a1) in zip(dispatches, adopts):
        assert d0 <= a0 and a1 <= d1 + 0.2          # adopt ends dispatch
    for g0, g1 in edges("host_gap"):
        for name in ("beta", "dispatch", "adopt"):
            for a, b in edges(name):                # 0.1 us rounding
                assert b <= g0 + 0.2 or a >= g1 - 0.2, (name, a, b, g0, g1)
    # a gap opens where a dispatch's interval closes
    ends = [d1 for _d0, d1 in dispatches]
    for g0, _g1 in edges("host_gap"):
        assert min(abs(g0 - d1) for d1 in ends) < 200


def test_one_dispatch_span_per_host_gap_and_host_gap_unchanged(traced_run):
    chrome, trainer = traced_run
    events = _on_track(chrome, "learner-hot-loop")
    gaps = [ev for ev in events if ev["name"] == "host_gap"]
    dispatches = [ev for ev in events if ev["name"] == "dispatch"]
    assert gaps and abs(len(dispatches) - len(gaps)) <= 1
    assert all("args" not in ev for ev in gaps)     # as it always was
    _host_gap_leaves_out_the_dispatch_interval(events)
    assert trainer._dispatch_gap.count == len(gaps)
    # the ring's reduction sees the same passes, and the spans account
    # for the loop: what no child covers is a small part of a pass
    red = spans.loop_phases([ev for ev in chrome["traceEvents"]
                             if ev.get("ph") in ("X", "i")])
    assert red["by_name"]["dispatch"]["n"] == len(dispatches)
    assert sum(red["kinds"].values()) == red["by_name"]["loop_iter"]["n"]
    assert 0 <= red["loop_self_s"] < red["by_name"]["loop_iter"]["s"]
    lags = spans.policy_lag([ev for ev in chrome["traceEvents"]
                             if ev.get("ph") in ("X", "i")])
    assert lags and min(lags) >= 0
    assert trainer.log.history and any(
        tag.endswith("obs_policy_lag_p50_steps")
        for tag in trainer.log.history)


def test_pipelined_loop_spans_and_train_alone_kind(tmp_path, monkeypatch):
    monkeypatch.setenv("APEX_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("APEX_TRACE_FLUSH_S", "0")
    obs_trace.reset_for_tests()
    try:
        trainer = _small_trainer(_scripted_messages(12))
        trainer.train(total_steps=30, max_seconds=120, log_every=10)
        chrome = obs_trace.get_ring().to_chrome()
    finally:
        obs_trace.reset_for_tests()
    events = _on_track(chrome, "learner-hot-loop")
    kinds = {ev["args"]["kind"] for ev in events
             if ev["name"] == "loop_iter"}
    assert "train" in kinds and ("fused" in kinds or "ingest" in kinds)
    on_staging = _on_track(chrome, "ingest-staging")
    staged = {ev["name"] for ev in on_staging}
    # the staging thread's events keep the names an operator knows; a
    # slot's build is one ``stage`` span that carries the slot's kind
    assert staged <= {"stage", "publish", "prio_writeback"}
    kinds = {ev["args"]["kind"] for ev in on_staging if ev["name"] == "stage"}
    assert kinds & {"single", "merged"}
    assert kinds <= {"single", "merged", "scan", "batch"}
    _host_gap_leaves_out_the_dispatch_interval(events)
    sizes = {name: getattr(trainer, name)._cache_size()
             for name in ("_fused", "_train")}
    assert set(sizes.values()) <= {0, 1}            # no second program


def test_the_chunk_path_builds_each_slot_under_one_stage_span(monkeypatch):
    """The staging thread's slot build is a span (ring event AND profiler
    annotation, so it is on the host plane), given the slot's kind once
    the build knows it; no ``stage_<kind>`` event is written after the
    fact."""
    from apex_tpu.training.ingest_pipeline import IngestPipeline

    monkeypatch.setattr(obs_trace, "_ANNOTATION", _FakeAnnotation)
    _FakeAnnotation.entered = []
    pipe = IngestPipeline(ScriptedPool(_scripted_messages(4)), depth=4,
                          merge_max=1, put_device=False)
    pipe.ring = TraceRing("learner", enabled=True)
    pipe.start()
    try:
        got = [pipe.poll_slot(timeout=5.0) for _ in range(4)]
    finally:
        pipe.stop()
    assert all(slot is not None for slot in got)
    events = [ev for ev in pipe.ring.to_chrome()["traceEvents"]
              if ev.get("ph") == "X"]
    stages = [ev for ev in events if ev["name"] == "stage"]
    assert len(stages) == len(got)
    assert [ev["args"]["kind"] for ev in stages] == [s.kind for s in got]
    assert not [ev for ev in events if ev["name"].startswith("stage_")]
    assert _FakeAnnotation.entered.count(("stage", {})) == len(got)


def test_scan_dispatch_splits_its_keys_outside_the_gap(tmp_path, monkeypatch):
    monkeypatch.setenv("APEX_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("APEX_TRACE_FLUSH_S", "0")
    obs_trace.reset_for_tests()
    try:
        trainer = _small_trainer(_scripted_messages(), scan_steps=2)
        trainer.train(total_steps=12, max_seconds=120, log_every=4)
        chrome = obs_trace.get_ring().to_chrome()
    finally:
        obs_trace.reset_for_tests()
    assert trainer.scan_dispatches > 0
    events = _on_track(chrome, "learner-hot-loop")
    assert "scan" in {ev["args"]["kind"] for ev in events
                      if ev["name"] == "loop_iter"}
    scans = [ev for ev in events if ev["name"] == "dispatch"
             and ev["args"]["program"] == "jit_fused_multi_step"]
    assert len(scans) == trainer.scan_dispatches
    _host_gap_leaves_out_the_dispatch_interval(events)
    # the split into per-step keys is evaluated after the gap closed, as
    # the call expression had it: the dispatch_key span just before each
    # scan dispatch overlaps no host_gap
    gaps = [(ev["ts"], ev["ts"] + ev["dur"]) for ev in events
            if ev["name"] == "host_gap"]
    keys = sorted((ev["ts"], ev["ts"] + ev["dur"]) for ev in events
                  if ev["name"] == "dispatch_key")
    for d in scans:
        a, b = max(k for k in keys if k[0] <= d["ts"])
        assert all(b <= g0 + 0.2 or a >= g1 - 0.2 for g0, g1 in gaps)


# -- the step program's scopes -------------------------------------------------------

@pytest.mark.parametrize("site,torso", [
    ("trainer", "glm47_flash_tiny"), ("rollout", "glm47_flash_tiny"),
    ("trainer", "dueling")])
def test_attention_path_instant_says_which_way_attention_went(
        site, torso, tmp_path, monkeypatch, capfd):
    """Whoever builds a token torso (a trainer, an on-device rollout)
    leaves one ``attention_path`` instant in the ring and one line on
    stderr: here, on a CPU and at the toy's widths, ``fused: 0`` and no
    block sizes; a dueling network has no attention and says nothing."""
    from apex_tpu.config import (ActorConfig, ApexConfig, EnvConfig,
                                 LearnerConfig, ReplayConfig)
    from apex_tpu.training.anakin import make_anakin_engine
    from apex_tpu.training.apex import ApexTrainer

    tokens = torso != "dueling"
    cfg = ApexConfig(
        env=EnvConfig(env_id="ApexTokens-v0" if tokens
                      else "ApexCatchSmall-v0",
                      frame_stack=1 if tokens else 2, clip_rewards=False,
                      episodic_life=False),
        replay=ReplayConfig(capacity=256, warmup=32),
        learner=LearnerConfig(batch_size=8, compute_dtype="float32",
                              torso=torso),
        actor=ActorConfig(n_actors=1, n_envs_per_actor=2, send_interval=16))
    monkeypatch.setenv("APEX_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("APEX_TRACE_FLUSH_S", "0")
    obs_trace.reset_for_tests()
    try:
        if site == "trainer":
            ApexTrainer(cfg, pool=ScriptedPool([]), respawn_workers=False)
        else:
            make_anakin_engine(cfg, rollout_len=4)
        found = [ev["args"] for ev
                 in obs_trace.get_ring().to_chrome()["traceEvents"]
                 if ev.get("name") == "attention_path"]
    finally:
        obs_trace.reset_for_tests()
    err = capfd.readouterr().err
    if not tokens:
        assert found == [] and "attention_path" not in err
        return
    assert found == [{"site": site, "torso": torso, "fused": 0,
                      "context": 16, "qk_head_dim": 16, "v_head_dim": 16,
                      "platform": "cpu"}]
    assert f"attention_path site={site} torso={torso} fused=0" in err


@pytest.mark.parametrize("site,torso", [
    ("trainer", "nemotron_h_tiny"), ("rollout", "nemotron_h_tiny"),
    ("trainer", "glm47_flash_tiny")])
def test_torso_layout_instant_says_what_the_chip_holds(
        site, torso, tmp_path, monkeypatch, capfd):
    """Whoever builds a torso that holds a share of its mixers' heads
    leaves one ``torso_layout`` instant in the ring and one line on
    stderr, beside ``attention_path``; a torso that says nothing of a
    layout (GLM's) leaves none."""
    from apex_tpu.config import (ActorConfig, ApexConfig, EnvConfig,
                                 LearnerConfig, ReplayConfig)
    from apex_tpu.training.anakin import make_anakin_engine
    from apex_tpu.training.apex import ApexTrainer

    cfg = ApexConfig(
        env=EnvConfig(env_id="ApexTokens-v0", frame_stack=1,
                      clip_rewards=False, episodic_life=False,
                      token_context=32),
        replay=ReplayConfig(capacity=256, warmup=32),
        learner=LearnerConfig(batch_size=8, compute_dtype="float32",
                              torso=torso),
        actor=ActorConfig(n_actors=1, n_envs_per_actor=2, send_interval=16))
    monkeypatch.setenv("APEX_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("APEX_TRACE_FLUSH_S", "0")
    obs_trace.reset_for_tests()
    try:
        if site == "trainer":
            ApexTrainer(cfg, pool=ScriptedPool([]), respawn_workers=False)
        else:
            make_anakin_engine(cfg, rollout_len=4)
        events = obs_trace.get_ring().to_chrome()["traceEvents"]
    finally:
        obs_trace.reset_for_tests()
    found = [ev["args"] for ev in events if ev.get("name") == "torso_layout"]
    paths = [ev["args"] for ev in events
             if ev.get("name") == "attention_path"]
    err = capfd.readouterr().err
    assert [a["torso"] for a in paths] == [torso]
    if torso != "nemotron_h_tiny":
        assert found == [] and "torso_layout" not in err
        return
    assert found == [{
        "site": site, "torso": torso, "pattern": "ME*ME",
        "mamba_heads": "2/4", "groups": "1/2", "attn_heads": "2/4",
        "kv_heads": "1/2", "experts": "2/8", "expert_rank": 0,
        "chunk": 8, "ssd_impl": "xla",
        "params": found[0]["params"]}]
    assert found[0]["params"] > 0
    assert (f"torso_layout site={site} torso={torso} pattern=ME*ME "
            "mamba_heads=2/4 groups=1/2") in err


@pytest.mark.parametrize("site,torso", [
    ("trainer", "glm47_flash_tiny"), ("rollout", "glm47_flash_tiny"),
    ("trainer", "nemotron_h_tiny"), ("rollout", "nemotron_h_tiny"),
    ("trainer", "dueling")])
def test_grouped_path_instant_says_what_the_grouped_kernel_is_handed(
        site, torso, tmp_path, monkeypatch, capfd):
    """Whoever builds a torso with an expert layer leaves one
    ``grouped_path`` instant in the ring and one line on stderr, beside
    ``attention_path``: here, on a CPU, the widths as they are through
    ``ragged_dot`` and no tile; a dueling network says nothing."""
    from apex_tpu.config import (ActorConfig, ApexConfig, EnvConfig,
                                 LearnerConfig, ReplayConfig)
    from apex_tpu.training.anakin import make_anakin_engine
    from apex_tpu.training.apex import ApexTrainer

    tokens = torso != "dueling"
    cfg = ApexConfig(
        env=EnvConfig(env_id="ApexTokens-v0" if tokens
                      else "ApexCatchSmall-v0",
                      frame_stack=1 if tokens else 2, clip_rewards=False,
                      episodic_life=False,
                      **({"token_context": 32} if "nemotron" in torso
                         else {})),
        replay=ReplayConfig(capacity=256, warmup=32),
        learner=LearnerConfig(batch_size=8, compute_dtype="float32",
                              torso=torso),
        actor=ActorConfig(n_actors=1, n_envs_per_actor=2, send_interval=16))
    monkeypatch.setenv("APEX_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("APEX_TRACE_FLUSH_S", "0")
    obs_trace.reset_for_tests()
    try:
        if site == "trainer":
            ApexTrainer(cfg, pool=ScriptedPool([]), respawn_workers=False)
        else:
            make_anakin_engine(cfg, rollout_len=4)
        found = [ev["args"] for ev
                 in obs_trace.get_ring().to_chrome()["traceEvents"]
                 if ev.get("name") == "grouped_path"]
    finally:
        obs_trace.reset_for_tests()
    err = capfd.readouterr().err
    if not tokens:
        assert found == [] and "grouped_path" not in err
        return
    assert found == [{"site": site, "torso": torso, "hidden": 64,
                      "width": 32, "platform": "cpu", "hidden_handed": 64,
                      "width_handed": 32, "tile_k": 0, "tile_n": 0,
                      "impl": "ragged_dot"}]
    assert (f"grouped_path site={site} torso={torso} hidden=64 width=32 "
            "platform=cpu hidden_handed=64 width_handed=32") in err


@pytest.mark.parametrize("torso,platform,want", [
    # 512 does not divide the published widths: the tiled kernel
    ("nemotron_twotower_ep16", "tpu",
     dict(hidden=2688, width=1856, hidden_handed=2688, width_handed=1920,
          tile_k=896, tile_n=640, impl="megablox_gmm")),
    # left alone: 512 divides both; under one tile; no TPU
    ("glm47_flash_ep8", "tpu",
     dict(hidden=2048, width=1536, hidden_handed=2048, width_handed=1536,
          tile_k=512, tile_n=512, impl="ragged_dot")),
    ("nemotron_h_tiny", "tpu",
     dict(hidden=64, width=32, hidden_handed=64, width_handed=32,
          tile_k=128, tile_n=128, impl="ragged_dot")),
    ("nemotron_twotower_ep16", "cpu",
     dict(hidden=2688, width=1856, hidden_handed=2688, width_handed=1856,
          tile_k=0, tile_n=0, impl="ragged_dot")),
    # 512 divides 2,048 and IS the experts' width: left on ``ragged_dot``
    ("qwen3_next_80b_ep16", "tpu",
     dict(hidden=2048, width=512, hidden_handed=2048, width_handed=512,
          tile_k=512, tile_n=512, impl="ragged_dot")),
    # 1,792 = 2 x 896: the tiled kernel, nothing padded
    ("lfm2_8b_a1b_ep4", "tpu",
     dict(hidden=2048, width=1792, hidden_handed=2048, width_handed=1792,
          tile_k=512, tile_n=896, impl="megablox_gmm"))])
def test_grouped_path_of_the_presets(torso, platform, want):
    """What each family's published preset says of its grouped products
    in a program compiled for ``platform``."""
    from apex_tpu.models import make_q_network
    m = make_q_network(dict(torso=torso, num_actions=64))
    assert m.grouped_path(platform) == dict(want, platform=platform)


@pytest.mark.parametrize("site", ["trainer", "rollout"])
def test_the_third_family_says_its_layout_and_its_paths(
        site, tmp_path, monkeypatch, capfd):
    """Whoever builds the Qwen3-Next torso leaves one ``torso_layout``,
    one ``grouped_path`` and one ``attention_path`` instant in the ring
    and a line each on stderr: the layer pattern, the heads and experts
    held over published, the chunk and how the chunk's triangular matrix
    is inverted."""
    from apex_tpu.config import (ActorConfig, ApexConfig, EnvConfig,
                                 LearnerConfig, ReplayConfig)
    from apex_tpu.training.anakin import make_anakin_engine
    from apex_tpu.training.apex import ApexTrainer

    torso = "qwen3_next_tiny"
    cfg = ApexConfig(
        env=EnvConfig(env_id="ApexTokens-v0", frame_stack=1,
                      clip_rewards=False, episodic_life=False,
                      token_context=32),
        replay=ReplayConfig(capacity=256, warmup=32),
        learner=LearnerConfig(batch_size=8, compute_dtype="float32",
                              torso=torso),
        actor=ActorConfig(n_actors=1, n_envs_per_actor=2, send_interval=16))
    monkeypatch.setenv("APEX_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("APEX_TRACE_FLUSH_S", "0")
    obs_trace.reset_for_tests()
    try:
        if site == "trainer":
            ApexTrainer(cfg, pool=ScriptedPool([]), respawn_workers=False)
        else:
            make_anakin_engine(cfg, rollout_len=4)
        events = obs_trace.get_ring().to_chrome()["traceEvents"]
    finally:
        obs_trace.reset_for_tests()
    by_name = {name: [ev["args"] for ev in events if ev.get("name") == name]
               for name in ("torso_layout", "grouped_path", "attention_path")}
    err = capfd.readouterr().err
    layout = by_name["torso_layout"]
    assert layout == [{
        "site": site, "torso": torso, "pattern": "DDDA", "key_heads": "2/4",
        "value_heads": "4/8", "attn_heads": "2/4", "kv_heads": "1/2",
        "experts": "2/32", "expert_rank": 0, "chunk": 8,
        "inverse": "nilpotent_doubling_f32", "params": layout[0]["params"]}]
    assert layout[0]["params"] > 0
    assert (f"torso_layout site={site} torso={torso} pattern=DDDA "
            "key_heads=2/4 value_heads=4/8") in err
    assert "inverse=nilpotent_doubling_f32" in err
    assert by_name["grouped_path"] == [{
        "site": site, "torso": torso, "hidden": 64, "width": 32,
        "platform": "cpu", "hidden_handed": 64, "width_handed": 32,
        "tile_k": 0, "tile_n": 0, "impl": "ragged_dot"}]
    assert by_name["attention_path"] == [{
        "site": site, "torso": torso, "fused": 0, "context": 32,
        "qk_head_dim": 16, "v_head_dim": 16, "platform": "cpu"}]
    assert f"grouped_path site={site} torso={torso}" in err
    assert f"attention_path site={site} torso={torso} fused=0" in err


@pytest.mark.parametrize("site", ["trainer", "rollout"])
def test_the_fourth_family_says_its_layout_and_its_paths(
        site, tmp_path, monkeypatch, capfd):
    """Whoever builds the LFM2 torso leaves one ``torso_layout``, one
    ``grouped_path`` and one ``attention_path`` instant in the ring and a
    line each on stderr: the mixer pattern, the feed-forward kinds, the
    experts held over published, no shared expert, the vocabulary held
    over published and the tied head."""
    from apex_tpu.config import (ActorConfig, ApexConfig, EnvConfig,
                                 LearnerConfig, ReplayConfig)
    from apex_tpu.models.lfm2_moe import PRESETS, param_count
    from apex_tpu.training.anakin import make_anakin_engine
    from apex_tpu.training.apex import ApexTrainer

    torso = "lfm2_tiny"
    cfg = ApexConfig(
        env=EnvConfig(env_id="ApexTokens-v0", frame_stack=1,
                      clip_rewards=False, episodic_life=False,
                      token_context=32),
        replay=ReplayConfig(capacity=256, warmup=32),
        learner=LearnerConfig(batch_size=8, compute_dtype="float32",
                              torso=torso),
        actor=ActorConfig(n_actors=1, n_envs_per_actor=2, send_interval=16))
    monkeypatch.setenv("APEX_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("APEX_TRACE_FLUSH_S", "0")
    obs_trace.reset_for_tests()
    try:
        if site == "trainer":
            ApexTrainer(cfg, pool=ScriptedPool([]), respawn_workers=False)
        else:
            make_anakin_engine(cfg, rollout_len=4)
        events = obs_trace.get_ring().to_chrome()["traceEvents"]
    finally:
        obs_trace.reset_for_tests()
    by_name = {name: [ev["args"] for ev in events if ev.get("name") == name]
               for name in ("torso_layout", "grouped_path", "attention_path")}
    err = capfd.readouterr().err
    vocab = cfg.env.token_vocab
    assert by_name["torso_layout"] == [{
        "site": site, "torso": torso, "pattern": "CACCC", "ffn": "DEEEE",
        "experts": "2/8", "expert_rank": 0, "shared_experts": 0,
        "vocab": f"{vocab}/65536", "tied_head": 1,
        "params": param_count(dict(PRESETS[torso], vocab_held=vocab))}]
    assert (f"torso_layout site={site} torso={torso} pattern=CACCC "
            "ffn=DEEEE experts=2/8") in err
    assert by_name["grouped_path"] == [{
        "site": site, "torso": torso, "hidden": 64, "width": 32,
        "platform": "cpu", "hidden_handed": 64, "width_handed": 32,
        "tile_k": 0, "tile_n": 0, "impl": "ragged_dot"}]
    assert by_name["attention_path"] == [{
        "site": site, "torso": torso, "fused": 0, "context": 32,
        "qk_head_dim": 16, "v_head_dim": 16, "platform": "cpu"}]
    assert f"attention_path site={site} torso={torso} fused=0" in err


def _hlo_ops(lowered) -> list[tuple[str, str]]:
    """``(opcode, op_name)`` of every instruction of the compiled program
    (compiled: XLA's inliner is what prefixes the operations of a called
    computation, a scan's body among them, with their caller's path)."""
    text = lowered.compile().as_text()
    return re.findall(r"= \S+ ([a-z][\w\-]*)\(.*?op_name=\"([^\"]+)\"",
                      text)


def _lowered(family: str, program: str):
    k_build, key = jax.random.split(jax.random.key(0))
    beta = jnp.float32(0.4)
    if family == "dqn":
        msgs = _scripted_messages(2)
        trainer = _small_trainer([])
        ts, rs = trainer.train_state, trainer.replay_state
        if program == "fused":
            return trainer._fused.lower(
                ts, rs, msgs[0]["payload"],
                jnp.asarray(msgs[0]["priorities"]), key, beta)
        return trainer._train.lower(ts, rs, key, beta)
    if family == "aql":
        from apex_tpu.envs.registry import make_env
        from apex_tpu.training.aql import aql_model_spec, build_aql
        cfg = small_test_config(capacity=256, batch_size=16,
                                env_id="ApexContinuousNav-v0")
        cfg = cfg.replace(aql=dataclasses.replace(
            cfg.aql, propose_sample=4, uniform_sample=4))
        env = make_env(cfg.env.env_id, cfg.env, seed=0)
        spec = aql_model_spec(cfg, env)
        obs_shape = env.observation_space.shape
        env.close()
        _model, ts, replay, item, core = build_aql(
            cfg, spec, obs_shape, np.float32, k_build)
    else:
        from apex_tpu.training.r2d2 import build_r2d2
        cfg = small_test_config(capacity=256, batch_size=8)
        *_, replay, item, ts, core = build_r2d2(cfg, k_build)
    rs = jax.eval_shape(lambda: replay.init(item))
    return core.jit_train_step().lower(ts, rs, key, beta)


@pytest.mark.parametrize("family,program", [
    ("dqn", "fused"), ("dqn", "train"), ("aql", "train"), ("r2d2", "train")])
def test_step_program_carries_the_scopes(family, program):
    ops = _hlo_ops(_lowered(family, program))
    want = set(spans.SCOPES) - (set() if program == "fused" else {"ingest"})
    seen = {spans.scope_of(name + ":") for _op, name in ops}
    assert want <= seen, (want, seen)
    heavy = [(op, name) for op, name in ops
             if op in ("dot", "convolution")]
    assert heavy
    for op, name in heavy:
        assert spans.scope_of(name + ":") in spans.SCOPES, (op, name)
    inner = {part for _op, name in ops for part in name.split("/")}
    assert {"loss_grad", "optimizer", "target_sync"} <= inner


def test_scope_of_reads_the_path_not_the_primitive():
    assert spans.scope_of(
        "jit(fused_step)/update/loss_grad/jvp(DuelingDQN)/Conv_0/"
        "conv_general_dilated:") == "update"
    assert spans.scope_of("jit(train_step)/sample/while/body/gather:") \
        == "sample"
    assert spans.scope_of("jit(train_step)/gather:") is None
    assert spans.scope_of("jit(train_step)/dot_general:") is None
    assert spans.scope_of(None) is None


# -- policy lag in learner steps -----------------------------------------------------

def test_learner_obs_lag_steps_with_gaps_and_an_evicted_version():
    ring = TraceRing("learner", enabled=True)
    obs = LearnerObs(ring=ring, max_versions=3, clock=lambda: 0.0,
                     wall=lambda: 10.0)
    for version, step in ((1, 0), (2, 25), (4, 75), (5, 100), (6, 125)):
        obs.note_publish(version, step)         # 3 never noted; 1, 2 evicted
    assert list(obs._pub) == [4, 5, 6]

    def consume(pv, step):
        span = {"pv": pv, "hops": {"sealed": (0.0, 8.0)}}
        obs.pre_consume([span])
        obs.post_consume([span], step)
        return ring.to_chrome()["traceEvents"][-1]["args"]

    assert consume(5, 130)["lag_steps"] == 30
    # evicted (or never noted): the first later version the ledger holds,
    # so the lag reads too low, never too high
    assert consume(2, 130)["lag_steps"] == 55
    assert consume(3, 130)["lag_steps"] == 55
    # a version from the future (another learner life): nothing to join
    assert consume(9, 130)["lag_steps"] is None
    # no step given: seconds only, as before
    assert consume(5, None)["lag_steps"] is None
    last = ring.to_chrome()["traceEvents"][-1]
    assert last["name"] == "consume" and last["ph"] == "i"
    assert last["args"]["age_s"] == pytest.approx(2.0)
    assert obs.policy_lag.count == 3
    sc = obs.scalars()
    assert sc["obs_policy_lag_p50_steps"] == 55
    assert sc["obs_policy_lag_p99_steps"] == 55


def test_publish_records_the_step_the_version_left_at():
    trainer = _small_trainer([])
    trainer._obs = LearnerObs()
    trainer.steps_rate.tick(40)
    trainer._publish()
    assert trainer._obs._pub[trainer.param_version][2] == 40


# -- the readers -----------------------------------------------------------------------

def _reader(name: str):
    path = os.path.join(ROOT, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_none_without_ring_or_trace(name, monkeypatch):
    monkeypatch.delenv("APEX_TRACE_DIR", raising=False)
    obs_trace.reset_for_tests()
    said = []
    ctx = dict(window_s=51.0, open={"wall": 0.0}, close={"wall": 4e9},
               trace=None, traced_s=None,
               traffic={"step_programs": {"jit_train_step": {}}},
               say=said.append)
    try:
        assert _reader(name).read(ctx) is None
    finally:
        obs_trace.reset_for_tests()


def test_benchmark_json_lists_the_new_readers_last():
    """PR 26's readers follow everything that was there before them, in
    order, as one block (later PRs append after it)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW_METRICS[0])
    tail = bench["per_layer"][at:at + len(NEW_METRICS)]
    assert [m["name"] for m in tail] == list(NEW_METRICS)
    assert all(m["workloads"] == ["dqn_hostfed"] for m in tail)
    layers = {m["layer"] for m in bench["per_layer"][:at]}
    assert {m["layer"] for m in tail} <= layers


def test_ring_reductions_on_hand_made_events():
    def x(name, ts, dur, **args):
        return {"name": name, "ph": "X", "tid": 1000, "ts": ts, "dur": dur,
                "args": args}

    events = [
        x("loop_iter", 0, 1000, it=1, kind="fused"),
        x("dispatch_key", 10, 290, it=1),
        x("beta", 310, 90, it=1),
        x("dispatch", 400, 100, it=1, program="jit_fused_step"),
        {"name": "host_gap", "ph": "X", "tid": 1000, "ts": 5, "dur": 395},
        x("loop_iter", 1005, 995, it=2, kind="train"),
        x("dispatch", 1300, 100, it=2, program="jit_train_step"),
        {"name": "stage_single", "ph": "X", "tid": 1001, "ts": 0, "dur": 900},
        {"name": "consume", "ph": "i", "tid": 1002, "ts": 50,
         "args": {"pv": 3, "lag_steps": 40, "age_s": 0.5}},
        {"name": "consume", "ph": "i", "tid": 1002, "ts": 60,
         "args": {"pv": 3, "lag_steps": None, "age_s": 0.5}},
    ]
    red = spans.loop_phases(events)
    assert red["by_name"]["loop_iter"] == {"n": 2, "s": pytest.approx(1995e-6)}
    assert red["by_name"]["dispatch"]["n"] == 2
    assert "host_gap" not in red["by_name"]
    assert "stage_single" not in red["by_name"]
    assert red["loop_self_s"] == pytest.approx((1995 - 480 - 100) * 1e-6)
    assert red["kinds"] == {"fused": 1, "train": 1}
    assert red["programs"]["jit_train_step"]["n"] == 1
    assert spans.policy_lag(events) == [40.0]
    assert spans.loop_phases([e for e in events
                              if e["name"] != "loop_iter"]) is None
    window = spans.ring_window({"traceEvents": events}, 0.0, 0.0011)
    assert len(window) == len(events) - 1           # the late dispatch is out


# -- the wire format and the trace's reductions ---------------------------------------------

def _varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _msg(*fields) -> bytes:
    return b"".join(_field(n, v) for n, v in fields)


STAT_IDS = {"tf_op": 1, "source": 2, "hlo_category": 3, "program": 4,
            "it": 5, "jit_train_step": 6, "flops": 7}


def _stat(name: str, value, ref: bool = False) -> bytes:
    kind = 7 if ref else {int: 4, float: 2, str: 5}[type(value)]
    return _msg((1, STAT_IDS[name]),
                (kind, STAT_IDS[value] if ref else value))


def _plane(name: str, metadata: dict, lines: dict) -> bytes:
    """``metadata``: ``{id: (name, [stat, ...])}``; ``lines``: ``{name:
    (timestamp ns, [(metadata id, offset us, duration us, [stat, ...])])}``
    """
    fields = [(2, name)]
    for line_name, (t0_ns, events) in lines.items():
        evs = [(4, _msg((1, m), (2, int(off * 1e6)), (3, int(dur * 1e6)),
                        *[(4, s) for s in stats]))
               for m, off, dur, stats in events]
        fields.append((3, _msg((2, line_name), (3, t0_ns), *evs)))
    for meta_id, (meta_name, stats) in metadata.items():
        fields.append((4, _msg((1, meta_id), (2, _msg(
            (1, meta_id), (2, meta_name), *[(5, s) for s in stats])))))
    for stat_name, stat_id in STAT_IDS.items():
        fields.append((5, _msg((1, stat_id), (2, _msg(
            (1, stat_id), (2, stat_name))))))
    return _msg(*fields)


@pytest.fixture(scope="module")
def synthetic_trace(tmp_path_factory):
    """Two passes of the loop and what they put on the device, times in
    microseconds: pass 1 dispatches ``jit_fused_step`` (after an eager
    ``jit__threefry_split``), pass 2 ``jit_train_step``."""
    fused = "jit(fused_step)/"
    device = _plane("/device:TPU:0", {
        1: ("jit_fused_step(123)", []),
        2: ("jit_train_step(456)", []),
        3: ("jit__threefry_split(9)", []),
        10: ("%scatter = u8[8]", [_stat("tf_op", fused + "ingest/scatter:")]),
        11: ("%while = s32[32]", [_stat("tf_op", fused + "sample/while:")]),
        12: ("%fusion.body = s32[32]", []),         # inherits the while's
        13: ("%gather = u8[32]", [
            _stat("tf_op", fused + "gather/gather:"), _stat("flops", 0)]),
        14: ("%conv = bf16[32]", [_stat(
            "tf_op", fused + "update/loss_grad/jvp(DuelingDQN)/Conv_0/"
            "conv_general_dilated:")]),
        15: ("%scatter.2 = f32[32]", [
            _stat("tf_op", fused + "writeback/scatter-add:")]),
        16: ("%copy.3 = u8[32]", [_stat("source", "frame_pool.py:385"),
                                  _stat("hlo_category", "data formatting")]),
        17: ("%rng = u32[2]", [_stat("tf_op", "jit(_threefry_split)/x:")]),
    }, {
        "XLA Modules": (0, [(3, 100, 20, []), (1, 450, 450, []),
                            (2, 1350, 350, [])]),
        "XLA Ops": (0, [
            (17, 100, 20, []),
            (10, 450, 50, []), (11, 500, 100, []), (12, 510, 40, []),
            (13, 600, 100, []), (14, 700, 100, []), (15, 800, 50, []),
            (16, 850, 30, []),
            (11, 1350, 100, []), (13, 1450, 100, []), (14, 1550, 100, []),
            (15, 1650, 50, [])]),
    })

    def ann(it, program=None):
        stats = [_stat("it", it)]
        if program == "jit_train_step":
            stats.append(_stat("program", program, ref=True))
        elif program:
            stats.append(_stat("program", program))
        return stats

    host = _plane("/host:CPU", {
        1: ("loop_iter", []), 2: ("dispatch_key", []), 3: ("beta", []),
        4: ("dispatch", []), 5: ("PjitFunction(fused_step)", []),
        6: ("stage_single", []),
    }, {
        "python3": (1, [                # 1 ns = 0.001 us into the session
            (1, 0, 1000, ann(1)), (2, 10, 290, ann(1)), (3, 310, 90, ann(1)),
            (4, 400, 100, ann(1, "jit_fused_step")), (5, 405, 90, []),
            (1, 1005, 995, ann(2)), (2, 1010, 190, ann(2)),
            (3, 1210, 90, ann(2)), (4, 1300, 100, ann(2, "jit_train_step")),
        ]),
        "python3 ": (0, [(6, 0, 2000, [])]),
    })
    path = tmp_path_factory.mktemp("xplane") / "synthetic.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host),
                          (1, _plane("/host:metadata", {}, {}))))
    return str(path)


def test_wire_reader_reads_planes_lines_events_and_metadata(synthetic_trace):
    planes = spans.read_xspace(synthetic_trace)
    assert [p.name for p in planes] == ["/device:TPU:0", "/host:CPU",
                                        "/host:metadata"]
    device, host = planes[0], planes[1]
    ops = device.line("XLA Ops")
    assert len(ops) == 12 and ops[1][:3] == (10, 450_000_000, 50_000_000)
    assert device.meta(14)["stats"]["tf_op"].endswith("conv_general_dilated:")
    assert device.meta(16)["stats"] == {
        "source": "frame_pool.py:385", "hlo_category": "data formatting"}
    assert device.meta(13)["stats"]["flops"] == 0
    loop = host.line("python3")
    assert loop[0][1] == 1000                       # the line's timestamp
    assert spans._stats(loop[3][3], host.stat_names) == {
        "it": 1, "program": "jit_fused_step"}
    assert spans._stats(loop[8][3], host.stat_names)["program"] \
        == "jit_train_step"                         # by reference


def test_host_attribution_owns_idle_time_and_matches_one_clock(
        synthetic_trace):
    host = spans.host_attribution(spans.read_xspace(synthetic_trace))
    us = 1e-6
    assert host["idle_s"] == pytest.approx(800 * us)
    got = {k: round(v / us, 2) for k, v in host["idle_by_span"].items()}
    assert got == {"dispatch_key": 370.0, "beta": 180.0, "dispatch": 100.0,
                   "loop_iter (own)": 145.0}
    # under no span at all 5 us, under loop_iter alone 145: no phase's
    assert host["idle_unattributed_s"] == pytest.approx(150 * us, rel=1e-3)
    # no program before its dispatch: nothing to move, one reading
    assert host["clock_skew_floor_us"] == 0.0
    assert host["idle_by_span_moved"] == host["idle_by_span"]
    assert host["idle_unattributed_moved_s"] == host["idle_unattributed_s"]
    assert host["annotations"] == {"loop_iter": 2, "dispatch_key": 2,
                                   "beta": 2, "dispatch": 2}
    assert host["inside_dispatch"][0][0] == "PjitFunction(fused_step)"
    clock = host["one_clock"]
    assert (clock["dispatches"], clock["modules"], clock["paired"]) \
        == (2, 2, 2)
    assert clock["shift"] == 0 and clock["mismatched"] == 0
    assert clock["started_before_dispatch"] == 0
    assert clock["offset_us_median"] == pytest.approx(50.0, abs=0.01)


def test_idle_seconds_by_span_follow_the_alignment():
    ps = 1_000_000                                  # 1 us
    segments = spans._leaf_segments([
        (0, 100 * ps, "loop_iter"), (10 * ps, 40 * ps, "dispatch_key"),
        (40 * ps, 60 * ps, "beta"), (60 * ps, 90 * ps, "dispatch")])
    busy = [(0, 20 * ps), (70 * ps, 95 * ps), (120 * ps, 130 * ps)]
    idle, by_span, loose = spans._idle_by_span(busy, segments)
    assert idle == 75 * ps
    assert {k: round(v * 1e6, 3) for k, v in by_span.items()} == {
        "dispatch_key": 20.0, "beta": 20.0, "dispatch": 10.0,
        "loop_iter (own)": 5.0}
    assert loose == 25 * ps             # 5 under the pass alone, 20 beyond
    # the device 10 us later: the same gaps lie under later spans
    later = [(a + 10 * ps, b + 10 * ps) for a, b in busy]
    idle, by_span, loose = spans._idle_by_span(later, segments)
    assert idle == 75 * ps
    assert {k: round(v * 1e6, 3) for k, v in by_span.items()} == {
        "dispatch_key": 10.0, "beta": 20.0, "dispatch": 20.0}
    assert loose == 25 * ps


# -- the host's other threads: gc, the trace exporter, staging -------------------------

HOST_METRICS = ("ring_flush_share_pct", "gc_pause_share_pct",
                "idle_staging_pct")


@pytest.fixture(scope="module")
def threads_trace(tmp_path_factory):
    """One pass of the loop beside the staging thread and the trace
    exporter, times in microseconds: the device busy in four runs of 100,
    so idle 100-300, 400-700 and 800-1000."""
    device = _plane("/device:TPU:0", {
        1: ("jit_fused_step(1)", []), 10: ("%fusion = f32[8]", []),
    }, {
        "XLA Modules": (0, [(1, 0, 1100, [])]),
        "XLA Ops": (0, [(10, 0, 100, []), (10, 300, 100, []),
                        (10, 700, 100, []), (10, 1000, 100, [])]),
    })
    host = _plane("/host:CPU", {
        1: ("loop_iter", []), 2: ("dispatch", []), 3: ("adopt", []),
        4: ("beta", []), 5: ("gc", []), 6: ("stage", []), 7: ("publish", []),
        8: ("ring_flush", []),
    }, {
        "python3": (0, [(1, 0, 1100, []), (2, 50, 100, []), (3, 120, 30, []),
                        (4, 400, 100, []), (5, 450, 30, [])]),
        "apex-ingest-staging": (0, [(6, 150, 100, []), (5, 200, 20, []),
                                    (7, 600, 50, [])]),
        "apex-trace-flush": (0, [(8, 820, 80, [])]),
    })
    path = tmp_path_factory.mktemp("threads") / "threads.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host)))
    return spans.read_xspace(str(path))


def test_idle_seconds_by_the_loop_and_the_other_threads(threads_trace):
    from benchmark import host_threads

    red = host_threads.idle_by_threads(threads_trace)
    us = 1e-6
    assert red["idle_s"] == pytest.approx(700 * us)
    # stage (a gc inside it included) and publish open on the staging line
    assert red["staging_s"] == pytest.approx(150 * us)
    got = {k: round(v / us, 3) for k, v in red["pairs"]}
    assert got == {
        ("loop_iter (own)", "-"): 320.0, ("loop_iter (own)", "stage"): 80.0,
        ("loop_iter (own)", "ring_flush"): 80.0,
        ("loop_iter (own)", "publish"): 50.0,
        ("loop_iter (own)", "gc"): 20.0, ("beta", "-"): 70.0,
        ("beta", "gc"): 30.0, ("adopt", "-"): 30.0, ("dispatch", "-"): 20.0}
    assert sum(got.values()) == pytest.approx(700.0)
    # the loop's own reduction is not moved by the new annotations
    host = spans.host_attribution(threads_trace)
    assert host["idle_s"] == pytest.approx(700 * us)
    assert host["annotations"] == {"loop_iter": 1, "dispatch": 1,
                                   "adopt": 1, "beta": 1}
    # a trace from before ``stage`` was a span reads nothing
    small = spans.read_xspace(os.path.join(FIXTURES, "scoped.xplane.pb"))
    assert host_threads.idle_by_threads(small) is None


def _x(name, ts, dur, tid=1000, **args):
    return {"name": name, "ph": "X", "tid": tid, "ts": ts, "dur": dur,
            "args": args}


def test_gc_and_flush_reductions_on_hand_made_ring_events():
    from benchmark import host_threads

    events = [
        _x("loop_iter", 0, 1000, it=1, kind="fused"),
        _x("dispatch", 100, 200, it=1, program="jit_fused_step"),
        _x("adopt", 250, 50, it=1),
        _x("gc", 120, 20, tid=77, gen=0, collected=5),
        _x("gc", 260, 30, tid=77, gen=0, collected=0),
        _x("gc", 500, 60, tid=88, gen=2, collected=100),
        _x("gc", 1100, 10, tid=77, gen=1, collected=1),
        # the wait for the update before last is a phase of its own here
        _x("in_flight_wait", 700, 100, it=1),
        _x("gc", 750, 10, tid=77, gen=0, collected=0),
        _x("ring_flush", 2000, 2000, tid=1003, events=900, bytes=90_000),
        _x("ring_flush", 9000, 3000, tid=1003, events=1100, bytes=110_000),
    ]
    gc_red = host_threads.gc_pauses(events)
    assert gc_red["n"] == 5 and gc_red["s"] == pytest.approx(130e-6)
    assert {k: round(v * 1e6, 3) for k, v in gc_red["by_phase"].items()} == {
        "loop_iter (own)": 60.0, "adopt": 30.0, "dispatch": 20.0,
        "outside any pass": 10.0, "in_flight_wait": 10.0}
    assert gc_red["by_gen"] == {
        0: {"n": 3, "s": pytest.approx(60e-6), "collected": 5},
        1: {"n": 1, "s": pytest.approx(10e-6), "collected": 1},
        2: {"n": 1, "s": pytest.approx(60e-6), "collected": 100}}
    flush = host_threads.flushes(events)
    assert flush == {"n": 2, "s": pytest.approx(5e-3),
                     "longest_s": pytest.approx(3e-3), "events": 2000,
                     "bytes": 200_000}
    # the loop's own reduction does not count them
    assert set(spans.loop_phases(events)["by_name"]) == {
        "loop_iter", "dispatch", "adopt"}
    parent = [ev for ev in events if ev["name"] not in ("gc", "ring_flush")]
    assert host_threads.gc_pauses(parent) is None
    assert host_threads.flushes(parent) is None


@pytest.mark.parametrize("name,want", [
    ("ring_flush_share_pct", 0.5), ("gc_pause_share_pct", 0.012),
    ("idle_staging_pct", 100.0 * 150 / 700)])
def test_the_host_thread_readers(name, want, threads_trace, monkeypatch):
    """Each reader on the synthetic ring and plane, and on what a parent
    without the spans leaves: nothing, and no error."""
    events = [
        _x("loop_iter", 0, 1000, it=1, kind="fused"),
        _x("gc", 500, 120, tid=88, gen=2, collected=100),
        _x("ring_flush", 2000, 2000, tid=1003, events=900, bytes=90_000),
        _x("ring_flush", 9000, 3000, tid=1003, events=1100, bytes=110_000),
    ]
    said = []
    monkeypatch.setattr(spans, "load", lambda c: c["_spans"])
    ctx = dict(_spans={"planes": threads_trace, "ring": events},
               window_s=1.0, say=said.append)
    assert _reader(name).read(ctx) == pytest.approx(want)
    assert said                                 # its breakdown on stderr
    bare = dict(ctx, _spans={"planes": None, "ring": events[:1]})
    assert _reader(name).read(bare) is None


@pytest.mark.parametrize("name", HOST_METRICS)
def test_host_thread_reader_returns_none_without_ring_or_trace(
        name, monkeypatch):
    monkeypatch.delenv("APEX_TRACE_DIR", raising=False)
    obs_trace.reset_for_tests()
    ctx = dict(window_s=51.0, open={"wall": 0.0}, close={"wall": 4e9},
               trace=None, traced_s=None, say=[].append)
    try:
        assert _reader(name).read(ctx) is None
    finally:
        obs_trace.reset_for_tests()


def test_benchmark_json_lists_the_host_thread_readers_for_the_hostfed_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(HOST_METRICS[0])
    assert at > names.index("delta_roofline")   # after what was there
    tail = bench["per_layer"][at:at + len(HOST_METRICS)]
    assert [m["name"] for m in tail] == list(HOST_METRICS)
    assert all(m["workloads"] == ["dqn_hostfed"] for m in tail)
    assert [m["layer"] for m in tail] == ["Trainer loop", "Trainer loop",
                                          "Ingest staging"]


def test_device_scopes_account_for_the_step_programs(synthetic_trace):
    planes = spans.read_xspace(synthetic_trace)
    red = spans.device_scopes(planes, ("jit_fused_step", "jit_train_step"))
    us = 1e-6
    fused = red["programs"]["jit_fused_step"]
    assert fused["calls"] == 1
    assert fused["seconds"] == pytest.approx(450 * us)
    assert {k: round(v / us, 2) for k, v in fused["scopes"].items()} == {
        "ingest": 50.0, "sample": 100.0, "gather": 100.0, "update": 100.0,
        "writeback": 50.0}
    assert fused["unscoped_s"] == pytest.approx(30 * us)
    assert red["unscoped_ops"][0][0] == "%copy.3 = u8[32]"
    assert red["unscoped_ops"][0][1]["source"] == "frame_pool.py:385"
    t = spans.scope_totals(red)
    assert t["scopes"]["ingest"] == {"s": pytest.approx(50 * us), "calls": 1}
    assert t["scopes"]["sample"] == {"s": pytest.approx(200 * us), "calls": 2}
    assert t["module_s"] == pytest.approx(800 * us)
    assert t["scoped_s"] + t["unscoped_ops_s"] + t["between_ops_s"] \
        == pytest.approx(t["module_s"])
    assert t["between_ops_s"] == pytest.approx(20 * us)
    # a trace from before the scopes: nothing to read, and no error
    assert spans.device_scopes(planes, ("jit__threefry_split",)) is None
    small = spans.read_xspace(os.path.join(FIXTURES, "small.xplane.pb"))
    assert spans.device_scopes(small, ("jit_fused_step",
                                       "jit_train_step")) is None
    assert spans.host_attribution(small) is None


def test_torso_scopes_place_the_grouped_kernels_by_name(tmp_path):
    """The device plane of one update of the torso as XLA:TPU names it (the
    ``tf_op`` of PR 29's AOT-compiled step): the grouped products are
    kernels named ``%ragged-dot-none.<n>`` with NO scope path, the masks
    and converts around them carry ``.../router/.../experts/...``, in the
    backward pass under ``transpose(jvp(...))`` and
    ``rematted_computation``.  ``experts`` is the kernels' time plus the
    in-scope operations', ``router`` loses nothing to it, and an operation
    of another program counts nowhere."""
    from benchmark import torso_scopes

    step = "jit(fused_step)/update/loss_grad/"
    fwd = step + "jvp(Glm4MoeLiteQ)/layers_1/moe/router/moe.routed/"
    bwd = (step + "transpose(jvp(Glm4MoeLiteQ))/update/loss_grad/"
           "jvp(Glm4MoeLiteQ)/checkpoint/layers_1/moe/router/moe.routed/"
           "checkpoint/rematted_computation/")
    device = _plane("/device:TPU:0", {
        1: ("jit_fused_step(123)", []),
        2: ("jit__threefry_split(9)", []),
        10: ("%ragged-dot-none.1 = f32[16384,1536]{1,0} custom-call(",
             [_stat("tf_op", "ragged-dot-none")]),
        11: ("%select_convert_fusion = bf16[16384,1536] fusion(", [_stat(
            "tf_op", fwd + "checkpoint/experts/moe.grouped/"
            "convert_element_type:")]),
        12: ("%select_multiply_fusion = f32[16384,2048] fusion(",
             [_stat("tf_op", fwd + "checkpoint/mul:")]),
        13: ("%ragged-dot-none.7 = f32[8,2048,1536]{2,1,0} custom-call(",
             [_stat("tf_op", "ragged-dot-none")]),
        14: ("%select_add_fusion = f32[16384,1536] fusion(", [_stat(
            "tf_op", bwd + "experts/moe.grouped/add_any:")]),
        15: ("%fusion.9 = f32[16384,2048] fusion(",
             [_stat("tf_op", bwd + "scatter-add:")]),
        16: ("%fusion.20 = f32[16,1024,5120] fusion(", [_stat(
            "tf_op", step + "jvp(Glm4MoeLiteQ)/layers_1/mla/mla/q_b/"
            "dot_general:")]),
        17: ("%fusion.30 = f32[2048] fusion(",
             [_stat("tf_op", "jit(fused_step)/update/optimizer/mul:")]),
        18: ("%ragged-dot-none.2 = f32[8] custom-call(",
             [_stat("tf_op", "ragged-dot-none")]),
    }, {
        "XLA Modules": (0, [(2, 0, 50, []), (1, 100, 900, [])]),
        "XLA Ops": (0, [
            (18, 10, 20, []),                  # in another program
            (16, 100, 300, []), (10, 400, 70, []), (11, 470, 10, []),
            (12, 480, 40, []), (13, 520, 60, []), (14, 580, 20, []),
            (15, 600, 50, []), (17, 650, 100, [])]),
    })
    path = tmp_path / "torso.xplane.pb"
    path.write_bytes(_msg((1, device), (1, _plane("/host:metadata", {}, {}))))
    red = torso_scopes.reduce_planes(spans.read_xspace(str(path)),
                                     ("jit_fused_step",))
    assert set(red) == {"jit_fused_step"}
    got = red["jit_fused_step"]
    us = 1e-6
    assert got["calls"] == 1
    assert {k: round(v / us, 2) for k, v in got["scopes"].items()
            if v} == {"mla": 300.0, "router": 90.0, "experts": 160.0}
    # the products alone: every ragged-dot operation of the program
    assert got["kernels_s"] == pytest.approx(130 * us)
    assert {k: round(v / us, 2) for k, v in got["rest"].items()} == {
        "%fusion.30": 100.0}
    # by path alone, as the reader went before, they fell under no scope
    assert torso_scopes.scope_of("ragged-dot-none") is None
    assert torso_scopes.scope_of(
        step + "jvp(router)/transpose(jvp(experts))/mul:") == "experts"


def test_nemotron_h_scopes_reduce_by_the_innermost_name(tmp_path):
    """The device plane of one update of the Nemotron-H torso: an
    operation counts under the innermost of the family's names on its path
    (``ssd`` and ``conv`` inside ``mamba``, ``experts`` inside ``router``),
    the grouped products by their own name; ``mamba_ms`` is the scope with
    what lies inside it, ``ssd_ms`` the scan alone, and ``ssd_roofline``
    the scan's least time by the family's counts over its device time."""
    from benchmark import costs, nemotron_h_scopes

    step = "jit(fused_step)/update/loss_grad/"
    fwd = step + "jvp(NemotronHQ)/checkpoint/layers_0/mamba/mamba/"
    bwd = (step + "transpose(jvp(NemotronHQ))/update/loss_grad/"
           "jvp(NemotronHQ)/checkpoint/rematted_computation/layers_0/"
           "mamba/mamba/")
    device = _plane("/device:TPU:0", {
        1: ("jit_fused_step(123)", []),
        10: ("%fusion.1 = bf16[16,1024,5120] fusion(",
             [_stat("tf_op", fwd + "mamba.dot/dot_general:")]),
        11: ("%fusion.2 = f32[16,1024,3072] fusion(",
             [_stat("tf_op", fwd + "conv/jit(silu)/mul:")]),
        12: ("%fusion.3 = f32[16,8,4,8,128,128] fusion(", [_stat(
            "tf_op", fwd + "ssd/bclgn,bcsgn->bcgls/dot_general:")]),
        13: ("%while.4 = (s32[], f32[16,4,8,64,128]) while(",
             [_stat("tf_op", bwd + "ssd/while:")]),
        14: ("%fusion.5 = f32[16,4,8,64,128] fusion(", []),  # in the while
        15: ("%fusion.6 = f32[16,16,1024,128] fusion(", [_stat(
            "tf_op", step + "jvp(NemotronHQ)/checkpoint/layers_5/attention/"
            "attention/btd,dhk->bhtk/dot_general:")]),
        16: ("%ragged-dot-none.1 = f32[12288,1856]{1,0} custom-call(",
             [_stat("tf_op", "ragged-dot-none")]),
        17: ("%fusion.7 = f32[16384,2688] fusion(", [_stat(
            "tf_op", step + "jvp(NemotronHQ)/layers_1/moe/router/"
            "moe.routed/checkpoint/mul:")]),
        18: ("%fusion.8 = f32[2688] fusion(",
             [_stat("tf_op", "jit(fused_step)/update/optimizer/mul:")]),
    }, {
        "XLA Modules": (0, [(1, 100, 1000, [])]),
        "XLA Ops": (0, [
            (10, 100, 100, []), (11, 200, 50, []), (12, 250, 200, []),
            (13, 450, 100, []), (14, 460, 80, []), (15, 550, 150, []),
            (16, 700, 120, []), (17, 820, 30, []), (18, 850, 100, [])]),
    })
    path = tmp_path / "nemotron.xplane.pb"
    path.write_bytes(_msg((1, device), (1, _plane("/host:metadata", {}, {}))))
    planes = spans.read_xspace(str(path))
    got = nemotron_h_scopes.reduce_planes(planes, ("jit_fused_step",))[
        "jit_fused_step"]
    us = 1e-6
    assert {k: round(v / us, 2) for k, v in got["scopes"].items() if v} == {
        "mamba": 100.0, "conv": 50.0, "ssd": 300.0, "attention": 150.0,
        "experts": 120.0, "router": 30.0}
    assert {k: round(v / us, 2) for k, v in got["rest"].items()} == {
        "%fusion.8": 100.0}
    # the GLM torso's reader knows none of these names: nothing to read
    from benchmark import torso_scopes
    assert torso_scopes.scope_of(fwd + "ssd/exp:") is None
    assert nemotron_h_scopes.scope_of(
        step + "transpose(jvp(ssd))/mul:") == "ssd"
    assert nemotron_h_scopes.reduce_planes(
        spans.read_xspace(os.path.join(FIXTURES, "scoped.xplane.pb")),
        ("jit_fused_step", "jit_train_step")) is None
    # the readers over the same plane
    said = []
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron_twotower_q_ep16.json")) as f:
        config = json.load(f)
    ctx = dict(_spans={"planes": planes, "ring": []},
               traffic={"step_programs": {
                   "jit_fused_step": {"learner_steps": 1}}},
               trace={"busy_s": 850 * us}, config=config,
               peaks=costs.peaks_for("TPU v5 lite"), say=said.append)
    monkey = pytest.MonkeyPatch()
    monkey.setattr(spans, "load", lambda c: c["_spans"])
    try:
        assert _reader("mamba_ms").read(ctx) == pytest.approx(0.45)
        assert _reader("ssd_ms").read(ctx) == pytest.approx(0.30)
        assert _reader("attention_ms").read(ctx) == pytest.approx(0.15)
        fam = costs.family_costs("nemotron_h_q")
        shapes = config["shapes"]
        least = fam.ssd_bytes(shapes, 16_384, 6 * 4) / 819e9
        assert least > 2 * 24 * fam.ssd_macs(shapes, 16_384) / 197e12
        assert _reader("ssd_roofline").read(ctx) == pytest.approx(
            100.0 * least / (300 * us))
    finally:
        monkey.undo()
    assert any("torso scopes hold" in line for line in said)


SHARED_SCOPES = ("embed", "router", "experts", "shared_expert", "q_head")


@pytest.fixture(scope="module")
def shared_plane(tmp_path_factory):
    """One update whose operations lie under the names both torsos use
    (the expert layer is one class, ``embed`` and ``q_head`` mean the
    same), beside one operation of each family's own."""
    step = "jit(fused_step)/update/loss_grad/jvp(Q)/"
    moe = step + "layers_1/moe/"
    device = _plane("/device:TPU:0", {
        1: ("jit_fused_step(7)", []),
        10: ("%fusion.1 = f32[16384,2688] fusion(",
             [_stat("tf_op", step + "embed/gather:")]),
        11: ("%fusion.2 = f32[16384,128] fusion(",
             [_stat("tf_op", moe + "router/moe.route/dot_general:")]),
        12: ("%ragged-dot-none.1 = f32[24576,1856]{1,0} custom-call(",
             [_stat("tf_op", "ragged-dot-none")]),
        13: ("%fusion.3 = bf16[24576,1856] fusion(", [_stat(
            "tf_op", moe + "router/moe.routed/checkpoint/experts/"
            "moe.grouped/mul:")]),
        14: ("%fusion.4 = f32[16384,3712] fusion(", [_stat(
            "tf_op", moe + "shared_expert/shared/dot_general:")]),
        15: ("%fusion.5 = f32[16,16384] fusion(",
             [_stat("tf_op", step + "q_head/head/dot_general:")]),
        16: ("%fusion.6 = f32[16,1024,5120] fusion(",
             [_stat("tf_op", step + "layers_0/mamba/mamba/dot_general:")]),
        17: ("%fusion.7 = f32[16,1024,5120] fusion(",
             [_stat("tf_op", step + "layers_0/mla/mla/dot_general:")]),
    }, {
        "XLA Modules": (0, [(1, 100, 1000, [])]),
        "XLA Ops": (0, [
            (10, 100, 10, []), (11, 110, 20, []), (12, 130, 40, []),
            (13, 170, 80, []), (14, 250, 160, []), (15, 410, 320, []),
            (16, 730, 100, []), (17, 830, 100, [])]),
    })
    path = tmp_path_factory.mktemp("shared") / "shared.xplane.pb"
    path.write_bytes(_msg((1, device), (1, _plane("/host:metadata", {}, {}))))
    return spans.read_xspace(str(path))


@pytest.mark.parametrize("scope", SHARED_SCOPES)
def test_both_torso_readers_agree_on_the_names_they_share(shared_plane,
                                                          scope):
    """``nemotron_h_scopes.py`` is ``torso_scopes.py``'s reduction over
    another tuple of names (the benchmark's files may not be edited, so
    the reader could not be made one: PERF.md section 7 queues that).
    Until they are one, the names both know read the same seconds on the
    same plane, by path and by kernel name alike."""
    from benchmark import nemotron_h_scopes, torso_scopes

    want = {"embed": 10, "router": 20, "experts": 120, "shared_expert": 160,
            "q_head": 320}
    glm = torso_scopes.reduce_planes(shared_plane, ("jit_fused_step",))
    nem = nemotron_h_scopes.reduce_planes(shared_plane, ("jit_fused_step",))
    assert glm["jit_fused_step"]["scopes"][scope] == pytest.approx(
        want[scope] * 1e-6)
    assert nem["jit_fused_step"]["scopes"][scope] == pytest.approx(
        glm["jit_fused_step"]["scopes"][scope])
    # each leaves the other family's own operation under no scope
    assert set(glm["jit_fused_step"]["rest"]) == {"%fusion.6"}
    assert set(nem["jit_fused_step"]["rest"]) == {"%fusion.7"}


@pytest.fixture(scope="module")
def qnext_ctx(tmp_path_factory):
    """The device plane of one update of the Qwen3-Next torso, and the
    context a metric's reader is handed over it."""
    from benchmark import costs

    step = "jit(fused_step)/update/loss_grad/"
    fwd = step + "jvp(Qwen3NextQ)/layers_0/while/body/checkpoint/mixer/gdn/"
    bwd = (step + "transpose(jvp(Qwen3NextQ))/layers_0/while/body/"
           "checkpoint/rematted_computation/mixer/gdn/")
    device = _plane("/device:TPU:0", {
        1: ("jit_fused_step(123)", []),
        10: ("%fusion.1 = bf16[8,1024,6144] fusion(",
             [_stat("tf_op", fwd + "in_proj_qkvz/dot_general:")]),
        11: ("%fusion.2 = f32[8,1024,4096] fusion(",
             [_stat("tf_op", fwd + "conv/jit(silu)/mul:")]),
        12: ("%fusion.3 = f32[8,16,8,2,64,64] fusion(", [_stat(
            "tf_op", fwd + "delta/bnhik,bnhjk->bnhij/dot_general:")]),
        13: ("%while.4 = (s32[], f32[8,8,2,128,128]) while(",
             [_stat("tf_op", bwd + "delta/while:")]),
        14: ("%fusion.5 = f32[8,8,2,128,128] fusion(", []),  # in the while
        15: ("%fusion.6 = f32[8,8,1024,256] fusion(", [_stat(
            "tf_op", step + "jvp(Qwen3NextQ)/layers_3/while/body/checkpoint/"
            "mixer/gated_attention/attention/btd,dhk->bhtk/dot_general:")]),
        16: ("%ragged-dot-none.1 = f32[20480,512]{1,0} custom-call(",
             [_stat("tf_op", "ragged-dot-none")]),
        17: ("%fusion.7 = f32[8192,2048] fusion(", [_stat(
            "tf_op", step + "jvp(Qwen3NextQ)/layers_1/while/body/checkpoint/"
            "experts/moe/router/moe.routed/checkpoint/mul:")]),
        18: ("%fusion.8 = f32[2048] fusion(",
             [_stat("tf_op", "jit(fused_step)/update/optimizer/mul:")]),
    }, {
        "XLA Modules": (0, [(1, 100, 1000, [])]),
        "XLA Ops": (0, [
            (10, 100, 100, []), (11, 200, 50, []), (12, 250, 200, []),
            (13, 450, 100, []), (14, 460, 80, []), (15, 550, 150, []),
            (16, 700, 120, []), (17, 820, 30, []), (18, 850, 100, [])]),
    })
    path = tmp_path_factory.mktemp("qnext") / "qnext.xplane.pb"
    path.write_bytes(_msg((1, device), (1, _plane("/host:metadata", {}, {}))))
    planes = spans.read_xspace(str(path))
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3_next_q_ep16.json")) as f:
        config = json.load(f)
    said = []
    return dict(_spans={"planes": planes, "ring": []},
                traffic={"step_programs": {
                    "jit_fused_step": {"learner_steps": 1}}},
                trace={"busy_s": 850e-6}, config=config,
                peaks=costs.peaks_for("TPU v5 lite"), say=said.append,
                said=said)


def test_family_scopes_reduce_by_the_innermost_name_of_the_table(qnext_ctx):
    """An operation counts under the innermost of the family's names on
    its path (``delta`` and ``conv`` inside ``gdn``, ``experts`` inside
    ``router``), the grouped products by their own name; a plane of
    another family's program has no device time under this family's own
    scopes."""
    from benchmark import family_scopes

    table = family_scopes.table_for("qwen3_next_q")
    planes = qnext_ctx["_spans"]["planes"]
    got = family_scopes.reduce_planes(table, planes, ("jit_fused_step",))[
        "jit_fused_step"]
    us = 1e-6
    assert got["calls"] == 1
    assert {k: round(v / us, 2) for k, v in got["scopes"].items() if v} == {
        "gdn": 100.0, "conv": 50.0, "delta": 300.0,
        "gated_attention": 150.0, "experts": 120.0, "router": 30.0}
    assert got["kernels_s"] == pytest.approx(120 * us)
    assert {k: round(v / us, 2) for k, v in got["rest"].items()} == {
        "%fusion.8": 100.0}
    assert family_scopes.scope_of(
        table, "jit(f)/transpose(jvp(delta))/mul:") == "delta"
    other = family_scopes.reduce_planes(
        table, spans.read_xspace(os.path.join(FIXTURES, "scoped.xplane.pb")),
        ("jit_fused_step", "jit_train_step"))
    assert other and not any(
        p["scopes"][s] for p in other.values()
        for s in ("gdn", "conv", "delta", "gated_attention"))


@pytest.mark.parametrize("metric,want_us", [
    ("gdn_ms", 450.0), ("delta_ms", 300.0), ("gated_attention_ms", 150.0),
    ("delta_roofline", None)])
def test_the_third_familys_metrics_read_their_scopes(qnext_ctx, metric,
                                                     want_us):
    """``gdn_ms`` is the scope with what lies inside it, ``delta_ms`` the
    rule alone, ``delta_roofline`` the rule's least time by the family's
    counts (memory-bound) over its device time; over a run that left no
    trace each reads nothing and does not raise."""
    from benchmark import costs

    monkey = pytest.MonkeyPatch()
    monkey.setattr(spans, "load", lambda c: c["_spans"])
    try:
        got = _reader(metric).read(qnext_ctx)
        bare = dict(qnext_ctx, _spans={"planes": None, "ring": []})
        assert _reader(metric).read(bare) is None
    finally:
        monkey.undo()
    if want_us is not None:
        assert got == pytest.approx(want_us / 1000.0)
        return
    fam = costs.family_costs("qwen3_next_q")
    shapes = qnext_ctx["config"]["shapes"]
    least = fam.delta_bytes(shapes, 16_384, 6 * 3) / 819e9
    assert least > 2 * 18 * fam.delta_macs(shapes, 16_384) / 197e12
    assert got == pytest.approx(100.0 * least / 300e-6)
    assert any("torso scopes hold" in line for line in qnext_ctx["said"])


@pytest.fixture(scope="module")
def lfm2_ctx(tmp_path_factory):
    """The device plane of one update of the LFM2 torso, and the context a
    metric's reader is handed over it."""
    from benchmark import costs

    step = "jit(fused_step)/update/loss_grad/"
    fwd = step + "jvp(Lfm2MoeQ)/checkpoint/layers_0/shortconv/short_conv/"
    bwd = (step + "transpose(jvp(Lfm2MoeQ))/jvp(Lfm2MoeQ)/checkpoint/"
           "rematted_computation/layers_2/shortconv/short_conv/")
    attn = (step + "jvp(Lfm2MoeQ)/checkpoint/layers_1/qk_norm_attention/"
            "self_attn/")
    device = _plane("/device:TPU:0", {
        1: ("jit_fused_step(123)", []),
        10: ("%fusion.1 = f32[16384,6144] fusion(",
             [_stat("tf_op", fwd + "in_proj/in_proj.dot/dot_general:")]),
        11: ("%fusion.2 = f32[16,1024,2048] fusion(",
             [_stat("tf_op", fwd + "conv/mul:")]),
        12: ("%fusion.3 = f32[16384,2048] fusion(",
             [_stat("tf_op", bwd + "conv/add:")]),
        13: ("%fusion.4 = f32[16384,2048] fusion(",
             [_stat("tf_op", bwd + "mul:")]),
        14: ("%fusion.5 = f32[16,32,1024,64] fusion(",
             [_stat("tf_op", attn + "q_layernorm/mul:")]),
        15: ("%flash.6 = bf16[16,32,1024,64] custom-call(", [_stat(
            "tf_op", attn + "cond/branch_0_fun/jit(flash_attention)/"
            "pallas_call:")]),
        16: ("%gmm.7 = f32[16384,1792]{1,0} custom-call(", [_stat(
            "tf_op", step + "jvp(Lfm2MoeQ)/checkpoint/layers_2/feed_forward/"
            "router/feed_forward.routed/checkpoint/experts/gmm:")]),
        17: ("%fusion.8 = f32[16384,2048] fusion(", [_stat(
            "tf_op", step + "jvp(Lfm2MoeQ)/checkpoint/layers_2/feed_forward/"
            "router/feed_forward.routed/checkpoint/mul:")]),
        18: ("%fusion.9 = f32[2048] fusion(",
             [_stat("tf_op", "jit(fused_step)/update/optimizer/mul:")]),
    }, {
        "XLA Modules": (0, [(1, 100, 1000, [])]),
        "XLA Ops": (0, [
            (10, 100, 100, []), (11, 200, 50, []), (12, 250, 40, []),
            (13, 290, 60, []), (14, 350, 20, []), (15, 370, 130, []),
            (16, 500, 120, []), (17, 620, 30, []), (18, 650, 100, [])]),
    })
    path = tmp_path_factory.mktemp("lfm2") / "lfm2.xplane.pb"
    path.write_bytes(_msg((1, device), (1, _plane("/host:metadata", {}, {}))))
    planes = spans.read_xspace(str(path))
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_8b_a1b_q_ep4.json")) as f:
        config = json.load(f)
    said = []
    return dict(_spans={"planes": planes, "ring": []},
                traffic={"step_programs": {
                    "jit_fused_step": {"learner_steps": 1}}},
                trace={"busy_s": 650e-6}, config=config,
                peaks=costs.peaks_for("TPU v5 lite"), say=said.append,
                said=said)


def test_the_fourth_familys_table_places_its_operations(lfm2_ctx):
    """The short convolution's own operations count under ``shortconv``,
    the three-tap sum's under ``conv`` inside it (the module's name
    ``short_conv`` on the path is no scope of the table), the attention
    layer's and its kernel under ``qk_norm_attention``, the grouped
    products under ``experts`` by their path."""
    from benchmark import family_scopes

    table = family_scopes.table_for("lfm2_moe_q")
    got = family_scopes.reduce_planes(
        table, lfm2_ctx["_spans"]["planes"], ("jit_fused_step",))[
        "jit_fused_step"]
    us = 1e-6
    assert {k: round(v / us, 2) for k, v in got["scopes"].items() if v} == {
        "shortconv": 160.0, "conv": 90.0, "qk_norm_attention": 150.0,
        "experts": 120.0, "router": 30.0}
    assert {k: round(v / us, 2) for k, v in got["rest"].items()} == {
        "%fusion.9": 100.0}


@pytest.mark.parametrize("metric,want_us", [
    ("shortconv_ms", 250.0), ("qk_norm_attention_ms", 150.0),
    ("shortconv_roofline", None)])
def test_the_fourth_familys_metrics_read_their_scopes(lfm2_ctx, metric,
                                                      want_us):
    """``shortconv_ms`` is the scope with ``conv`` inside it,
    ``qk_norm_attention_ms`` the attention layers', ``shortconv_roofline``
    the block's least time by the family's counts (compute-bound: the two
    projections) over its device time; over a run that left no trace each
    reads nothing and does not raise."""
    from benchmark import costs

    monkey = pytest.MonkeyPatch()
    monkey.setattr(spans, "load", lambda c: c["_spans"])
    try:
        got = _reader(metric).read(lfm2_ctx)
        bare = dict(lfm2_ctx, _spans={"planes": None, "ring": []})
        assert _reader(metric).read(bare) is None
    finally:
        monkey.undo()
    if want_us is not None:
        assert got == pytest.approx(want_us / 1000.0)
        return
    fam = costs.family_costs("lfm2_moe_q")
    shapes = lfm2_ctx["config"]["shapes"]
    passes = 6 * 4
    least = 2 * passes * fam.shortconv_macs(shapes, 16_384) / 197e12
    assert least > fam.shortconv_bytes(shapes, 16_384, passes) / 819e9
    assert got == pytest.approx(100.0 * least / 250e-6)
    assert any(line.startswith("shortconv:") for line in lfm2_ctx["said"])


@pytest.mark.parametrize("scope", SHARED_SCOPES)
@pytest.mark.parametrize("family", ["glm", "nemotron"])
def test_the_one_reader_reads_what_the_two_copies_read(shared_plane, family,
                                                       scope):
    """``family_scopes.py`` handed the other two families' names as a
    table reads, on the same plane, the seconds their own readers read: a
    ``benchmark`` issue can fold ``torso_scopes.py`` and
    ``nemotron_h_scopes.py`` into it (PERF.md section 7)."""
    import types

    from benchmark import family_scopes, nemotron_h_scopes, torso_scopes

    copy = {"glm": torso_scopes, "nemotron": nemotron_h_scopes}[family]
    table = types.SimpleNamespace(
        SCOPES=getattr(copy, "TORSO", None) or copy.SCOPES,
        KERNELS=copy.KERNELS, INSIDE=getattr(copy, "INSIDE", {}))
    one = family_scopes.reduce_planes(table, shared_plane,
                                      ("jit_fused_step",))["jit_fused_step"]
    two = copy.reduce_planes(shared_plane,
                             ("jit_fused_step",))["jit_fused_step"]
    assert one["scopes"][scope] == pytest.approx(two["scopes"][scope])
    assert one["scopes"][scope] > 0
    assert one["rest"] == two["rest"] and one["seconds"] == two["seconds"]


# -- the trace recorded on the chip ------------------------------------------------------

@pytest.fixture(scope="module")
def scoped_fixture():
    with open(os.path.join(FIXTURES, "scoped.expected.json")) as f:
        want = json.load(f)
    red = spans.reduce_file(os.path.join(FIXTURES, "scoped.xplane.pb"),
                            ("jit_fused_step", "jit_train_step"))
    return want, red


def test_fixture_scopes_add_up_to_the_programs_device_seconds(scoped_fixture):
    want, red = scoped_fixture
    assert want["device"] == "TPU v5 lite"
    programs = red["scopes"]["programs"]
    assert {n: p["calls"] for n, p in programs.items()} \
        == want["recorded"]["programs"]
    for name, p in programs.items():
        assert all(s > 0 for scope, s in p["scopes"].items()
                   if scope != "ingest"), (name, p)
        assert p["unscoped_s"] > 0          # the multiply under no scope
    assert programs["jit_fused_step"]["scopes"]["ingest"] > 0
    assert programs["jit_train_step"]["scopes"]["ingest"] == 0
    t = red["scopes"]["totals"]
    assert t["scoped_s"] == pytest.approx(
        sum(v["s"] for v in t["scopes"].values()))
    assert t["scoped_s"] + t["unscoped_ops_s"] + t["between_ops_s"] \
        == pytest.approx(t["module_s"], rel=1e-12)
    assert 0 <= t["between_ops_s"] < 0.1 * t["module_s"]
    assert t["scopes"]["ingest"]["calls"] == 3
    assert t["scopes"]["update"]["calls"] == 6
    # the body of the fori_loop counts under its scope, its own time apart
    assert t["scopes"]["sample"]["s"] > t["scopes"]["writeback"]["s"]


def test_fixture_annotations_are_as_recorded_and_on_the_device_clock(
        scoped_fixture):
    want, red = scoped_fixture
    host = red["host"]
    recorded = {k: v for k, v in want["recorded"].items() if k != "programs"}
    assert host["annotations"] == recorded
    clock = host["one_clock"]
    assert clock["paired"] == clock["dispatches"] == clock["modules"] == 6
    assert clock["mismatched"] == 0 and clock["shift"] == 0
    # the session's clocks agree to within a millisecond, and are read as
    # written: every program of this trace reads as started before its
    # dispatch, which the reduction reports and does not correct
    assert abs(clock["offset_us_median"]) < 2000
    assert clock["started_before_dispatch"] == 6
    assert host["clock_skew_floor_us"] == pytest.approx(
        -clock["offset_us_min"])
    # ... and shares the idle seconds out a second time with the device
    # moved later by that floor: the same seconds, a little elsewhere
    assert sum(host["idle_by_span_moved"].values()) == pytest.approx(
        sum(host["idle_by_span"].values()), rel=1e-3)
    assert host["idle_by_span_moved"] != host["idle_by_span"]
    # what lies under loop_iter alone names no phase: unattributed
    assert host["idle_unattributed_s"] == pytest.approx(
        host["idle_s"] - sum(s for name, s in host["idle_by_span"].items()
                             if name != "loop_iter (own)"))
    assert host["idle_unattributed_s"] \
        >= host["idle_by_span"]["loop_iter (own)"]
    assert set(host["idle_by_span"]) <= {
        "dispatch_key", "beta", "dispatch", "loop_iter (own)"}
    assert any(name.startswith("PjitFunction(")
               for name, _v in host["inside_dispatch"])
    assert json.loads(json.dumps(red)) == want["reduced"]
    assert spans.main([os.path.join(FIXTURES, "scoped.xplane.pb"),
                       "--check"]) == 0
