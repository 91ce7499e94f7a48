"""Obs plane (apex_tpu/obs): chunk lineage spans, the trace ring + merge
tool, the learner-side latency join, Prometheus rendering, and the
DispatchGapTimer percentile fix.

Everything is tier-1: fake clocks / scripted pools, no sockets except
where the surface IS a socket (the /metrics scrape round-trip lives in
``tests/test_fleet.py`` beside the status-server tests)."""

from __future__ import annotations

import copy
import json
import os

import numpy as np
import pytest

from apex_tpu.obs import merge as obs_merge
from apex_tpu.obs import metrics as obs_metrics
from apex_tpu.obs import spans as obs_spans
from apex_tpu.obs.spans import LatencyHistogram, LearnerObs
from apex_tpu.obs.trace import TraceRing
from apex_tpu.utils.metrics import percentile

# the same real-builder chunk stream the ingest-pipeline suite uses
from tests.test_ingest_pipeline import (_assert_states_identical,
                                        _pool_spec,
                                        _random_chunk_messages)


# -- percentiles (satellite: DispatchGapTimer even-median fix) ---------------

def test_percentile_nearest_rank():
    assert percentile([], 0.5) == 0.0
    assert percentile([5.0], 0.5) == 5.0
    assert percentile([1, 2, 3], 0.5) == 2
    # EVEN length: the lower middle element, not the upper (the old
    # ``vals[n // 2]`` picked 3 here)
    assert percentile([1, 2, 3, 4], 0.5) == 2
    assert percentile([1, 2, 3, 4], 0.9) == 4
    assert percentile(list(range(1, 101)), 0.99) == 99
    assert percentile(list(range(1, 101)), 0.90) == 90


def test_dispatch_gap_snapshot_percentiles():
    from apex_tpu.utils.profiling import DispatchGapTimer

    t = DispatchGapTimer()
    # inject a known gap distribution (the clock-driven path is exercised
    # by every trainer test; here the math is the contract)
    gaps = [0.001 * g for g in (1, 2, 3, 4, 5, 6, 7, 8, 9, 10)]
    t._gaps.extend(gaps)
    t.count = len(gaps)
    t.total = sum(gaps)
    t.max = max(gaps)
    snap = t.snapshot()
    assert snap["dispatch_gap_ms_p50"] == pytest.approx(5.0)   # lower mid
    assert snap["dispatch_gap_ms_p90"] == pytest.approx(9.0)
    assert snap["dispatch_gap_ms_p99"] == pytest.approx(10.0)
    assert snap["dispatch_gap_ms_max"] == pytest.approx(10.0)
    assert snap["dispatches"] == 10


# -- span lifecycle ----------------------------------------------------------

def test_drain_builder_chunks_stamps_sealed_and_send_marks_version():
    msgs = _random_chunk_messages(seed=3, n_chunks=2)
    for msg in msgs:
        spans = obs_spans.spans_of(msg)
        assert len(spans) == 1
        assert "sealed" in spans[0]["hops"]
        obs_spans.mark_send(msg, param_version=42)
        assert spans[0]["pv"] == 42
        assert "send" in spans[0]["hops"]
        # stamps are first-wins: a second recv keeps the earlier time
        obs_spans.stamp(msg, "recv")
        first = spans[0]["hops"]["recv"]
        obs_spans.stamp(msg, "recv")
        assert spans[0]["hops"]["recv"] is first
    # payload NEVER carries timestamps (the merge bit-parity contract)
    assert obs_spans.SPAN_KEY not in msgs[0]["payload"]


def test_span_stamping_disabled_by_env(monkeypatch):
    monkeypatch.setenv("APEX_OBS_SPANS", "0")
    msgs = _random_chunk_messages(seed=4, n_chunks=1)
    assert obs_spans.spans_of(msgs[0]) == []
    obs_spans.mark_send(msgs[0], 5)          # no-op while disabled
    assert obs_spans.SPAN_KEY not in msgs[0]


def test_mark_send_creates_span_on_bare_message():
    msg = {"payload": {}, "priorities": None, "n_trans": 1}
    obs_spans.mark_send(msg, 7)
    spans = obs_spans.spans_of(msg)
    assert spans[0]["pv"] == 7
    assert {"sealed", "send"} <= spans[0]["hops"].keys()


# -- span round-trip through the merges (payload bit-parity re-pinned) -------

@pytest.mark.parametrize("m", [2, 4])
def test_merge_chunk_messages_carries_spans_and_keeps_payload_parity(m):
    """Span-stamped messages merge to one message carrying m spans (merge
    hop stamped), and the merged PAYLOAD stays bit-identical to merging
    the same stream without spans — timestamps ride metadata only."""
    msgs = _random_chunk_messages(seed=10 + m, n_chunks=m)
    for i, msg in enumerate(msgs):
        obs_spans.mark_send(msg, param_version=i)
    bare = copy.deepcopy(msgs)
    for msg in bare:
        msg.pop(obs_spans.SPAN_KEY, None)

    from apex_tpu.training.ingest_pipeline import merge_chunk_messages
    merged = merge_chunk_messages(copy.deepcopy(msgs))
    merged_bare = merge_chunk_messages(bare)

    spans = obs_spans.spans_of(merged)
    assert len(spans) == m
    assert [s["pv"] for s in spans] == list(range(m))
    assert all("merge" in s["hops"] for s in spans)
    assert obs_spans.SPAN_KEY not in merged["payload"]
    for key in merged_bare["payload"]:
        if key == "extras":
            continue
        assert np.array_equal(np.asarray(merged["payload"][key]),
                              np.asarray(merged_bare["payload"][key])), key
    assert np.array_equal(np.asarray(merged["priorities"]),
                          np.asarray(merged_bare["priorities"]))

    # and the replay-state parity contract itself still holds with spans on
    pool = _pool_spec()
    seq = pool.init()
    for msg in msgs:
        seq = pool.add(seq, msg["payload"],
                       np.asarray(msg["priorities"], np.float32))
    one = pool.add(pool.init(), merged["payload"],
                   np.asarray(merged["priorities"], np.float32))
    _assert_states_identical(seq, one)


def test_merge_group_messages_carries_spans(monkeypatch):
    from apex_tpu.parallel.aggregate import stack_chunk_messages
    from apex_tpu.training.ingest_pipeline import merge_group_messages

    n_dp, m = 2, 3
    groups = []
    for g in range(m):
        chunk_msgs = _random_chunk_messages(seed=50 + g, n_chunks=n_dp)
        for msg in chunk_msgs:
            obs_spans.mark_send(msg, param_version=g)
        payload, prios, n_tr = stack_chunk_messages(chunk_msgs)
        group = {"payload": payload, "priorities": prios, "n_trans": n_tr,
                 obs_spans.SPAN_KEY: obs_spans.merge_spans(chunk_msgs)}
        groups.append(group)
    merged = merge_group_messages(copy.deepcopy(groups), n_dp)
    spans = obs_spans.spans_of(merged)
    assert len(spans) == n_dp * m            # one per SOURCE chunk
    assert obs_spans.SPAN_KEY not in merged["payload"]
    assert sorted({s["pv"] for s in spans}) == list(range(m))


def test_chunk_aggregator_stamps_merge_and_flattens_spans():
    from apex_tpu.parallel.aggregate import ChunkAggregator
    from tests.test_ingest_pipeline import ScriptedPool

    msgs = _random_chunk_messages(seed=9, n_chunks=4)
    for msg in msgs:
        obs_spans.mark_send(msg, 1)
    agg = ChunkAggregator(ScriptedPool(msgs), n_dp=2)
    groups = agg.poll_chunks(4)
    assert len(groups) == 2
    for group in groups:
        spans = obs_spans.spans_of(group)
        assert len(spans) == 2
        assert all("merge" in s["hops"] for s in spans)


# -- the learner-side join ---------------------------------------------------

def test_latency_histogram_snapshot():
    h = LatencyHistogram(window=100)
    for v in (1, 2, 3, 4):
        h.record(v)
    s = h.snapshot()
    assert s["count"] == 4
    assert s["p50_s"] == 2.0            # even window, lower middle
    assert s["p99_s"] == 4.0
    assert s["max_s"] == 4.0
    assert s["mean_s"] == pytest.approx(2.5)


def test_learner_obs_joins_frame_age_and_param_lag():
    mono, wall = [100.0], [1000.0]
    obs = LearnerObs(clock=lambda: mono[0], wall=lambda: wall[0])
    obs.note_publish(7)                  # pv 7 published at mono=100

    span = {"pv": 7, "hops": {"sealed": (100.5, 1000.5),
                              "send": (100.6, 1000.6)}}
    mono[0], wall[0] = 103.0, 1003.0     # consumed 3s later
    obs.pre_consume([span])
    assert "consume" in span["hops"]
    obs.post_consume([span])
    assert "prio_wb" in span["hops"]
    assert obs.frame_age.count == 1
    # sealed at wall 1000.5, consumed at wall 1003 -> 2.5s frame age
    assert obs.frame_age.snapshot()["p50_s"] == pytest.approx(2.5)
    # published at mono 100, consumed at mono 103 -> 3s propagation lag
    assert obs.param_lag.snapshot()["p50_s"] == pytest.approx(3.0)

    # unknown version / missing sealed: joins skip, nothing crashes
    obs.post_consume([{"pv": 99, "hops": {}}])
    assert obs.param_lag.count == 1
    sc = obs.scalars()
    assert sc["obs_spans_consumed"] == 2
    assert set(obs.summary()) == {"frame_age_at_train_s",
                                  "param_propagation_lag_s",
                                  "spans_consumed"}


def test_learner_obs_publish_ledger_is_bounded():
    obs = LearnerObs(max_versions=4, clock=lambda: 0.0, wall=lambda: 0.0)
    for v in range(10):
        obs.note_publish(v)
    assert len(obs._pub) == 4 and 9 in obs._pub and 0 not in obs._pub


def test_learner_obs_emits_lineage_events():
    ring = TraceRing("learner", enabled=True)
    obs = LearnerObs(ring=ring, clock=lambda: 5.0, wall=lambda: 105.0)
    span = {"pv": 1, "hops": {"sealed": (1.0, 101.0),
                              "send": (2.0, 102.0),
                              "recv": (3.0, 103.0)}}
    obs.pre_consume([span])
    obs.post_consume([span])
    chrome = ring.to_chrome()
    names = [ev["name"] for ev in chrome["traceEvents"]
             if ev.get("ph") == "X"]
    assert "sealed→send" in names and "send→recv" in names
    # lineage events use the wall timebase directly
    ev = next(e for e in chrome["traceEvents"] if e["name"] == "sealed→send")
    assert ev["ts"] == pytest.approx(101.0 * 1e6)
    assert ev["dur"] == pytest.approx(1e6)


# -- pipeline carries spans into staged slots --------------------------------

def test_ingest_pipeline_slots_carry_staged_spans():
    from apex_tpu.training.ingest_pipeline import IngestPipeline
    from tests.test_ingest_pipeline import ScriptedPool

    msgs = _random_chunk_messages(seed=21, n_chunks=4)
    for msg in msgs:
        obs_spans.mark_send(msg, 3)
    pipe = IngestPipeline(ScriptedPool(msgs), depth=4, merge_max=1,
                          put_device=False)
    pipe.start()
    try:
        got = []
        while len(got) < 4:
            slot = pipe.poll_slot(timeout=5.0)
            assert slot is not None
            got.append(slot)
        for slot in got:
            assert len(slot.spans) == 1
            hops = slot.spans[0]["hops"]
            assert {"sealed", "send", "recv", "stage"} <= hops.keys()
            # pipeline ordering: recv happened at/after send, stage after
            assert hops["recv"][0] >= hops["send"][0]
            assert hops["stage"][0] >= hops["recv"][0]
    finally:
        pipe.stop()


# -- end-to-end: trainer join over a scripted stream -------------------------

def test_trainer_latency_summary_end_to_end():
    """A real (tiny) ApexTrainer over a span-stamped scripted stream: the
    latency section fills — frame-age and param-lag histograms count
    consumed spans, obs_* scalars reach the metric log."""
    import dataclasses

    from apex_tpu.config import small_test_config
    from apex_tpu.replay.frame_chunks import FrameChunkBuilder
    from apex_tpu.training.apex import ApexTrainer
    from tests.test_ingest_pipeline import ScriptedPool

    # chunks in the trainer's env geometry (CartPole: 4-dim, stack 1),
    # drained through the real message factory so spans are born there
    from apex_tpu.actors.pool import drain_builder_chunks
    rng = np.random.default_rng(31)
    builder = FrameChunkBuilder(3, 0.99, 1, (4,), chunk_transitions=8,
                                frame_dtype=np.float32)
    msgs: list[dict] = []
    while len(msgs) < 24:
        builder.begin_episode(rng.normal(size=4).astype(np.float32))
        ep_len = int(rng.integers(4, 30))
        for t in range(ep_len):
            builder.add_step(int(rng.integers(0, 2)), float(rng.normal()),
                             rng.normal(size=2).astype(np.float32),
                             rng.normal(size=4).astype(np.float32),
                             terminated=t == ep_len - 1, truncated=False)
        msgs.extend(drain_builder_chunks(builder))
    msgs = msgs[:24]
    for msg in msgs:
        obs_spans.mark_send(msg, param_version=1)
    cfg = small_test_config(capacity=256, batch_size=8, n_actors=1)
    cfg = cfg.replace(replay=dataclasses.replace(cfg.replay, warmup=32),
                      learner=dataclasses.replace(
                          cfg.learner, target_update_interval=50))
    trainer = ApexTrainer(cfg, pool=ScriptedPool(msgs),
                          publish_min_seconds=30.0, respawn_workers=False)
    trainer.train(total_steps=6, max_seconds=60, log_every=2)
    latency = trainer.latency_summary()
    assert latency is not None
    assert latency["spans_consumed"] > 0
    assert latency["frame_age_at_train_s"]["count"] > 0
    assert latency["frame_age_at_train_s"]["p50_s"] >= 0
    # the acted-under version was published by this trainer (version 1 is
    # its first publish), so the propagation-lag join found it
    assert latency["param_propagation_lag_s"]["count"] > 0
    assert "dispatch_gap_ms" in latency
    assert "dispatch_gap_ms_p90" in latency["dispatch_gap_ms"]
    assert any(tag.endswith("obs_frame_age_p50_s")
               for tag in trainer.log.history)


# -- trace ring --------------------------------------------------------------

def test_trace_ring_bounded_and_wall_converted():
    ring = TraceRing("actor-0", enabled=True, capacity=8)
    for i in range(20):
        ring.complete("phase", float(i), 0.5, track="t")
    chrome = ring.to_chrome()
    xs = [ev for ev in chrome["traceEvents"] if ev.get("ph") == "X"]
    assert len(xs) == 8                  # bounded: only the newest 8
    # perf->wall conversion uses the anchor
    anchor = chrome["metadata"]["clock_sync"]
    want = (anchor["wall"] + (19.0 - anchor["perf"])) * 1e6
    assert xs[-1]["ts"] == pytest.approx(want, abs=1.0)
    # process/thread naming metadata present
    assert any(ev.get("name") == "process_name"
               and ev["args"]["name"] == "actor-0"
               for ev in chrome["traceEvents"])
    assert any(ev.get("name") == "thread_name"
               and ev["args"]["name"] == "t"
               for ev in chrome["traceEvents"])

    off = TraceRing("y", enabled=False)
    off.complete("e", 0.0, 0.1)
    assert sum(1 for ev in off.to_chrome()["traceEvents"]
               if ev.get("ph") == "X") == 0


def test_get_ring_disabled_without_env(monkeypatch, tmp_path):
    from apex_tpu.obs import trace as obs_trace

    monkeypatch.delenv("APEX_TRACE_DIR", raising=False)
    obs_trace.reset_for_tests()
    try:
        ring = obs_trace.get_ring()
        assert not ring.enabled
        assert obs_trace.dump_ring() is None
    finally:
        obs_trace.reset_for_tests()


def test_ring_dump_and_phase_timer_integration(monkeypatch, tmp_path):
    from apex_tpu.obs import trace as obs_trace
    from apex_tpu.utils.profiling import PhaseTimer

    monkeypatch.setenv("APEX_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("APEX_TRACE_FLUSH_S", "0")   # no flusher thread
    obs_trace.reset_for_tests()
    try:
        obs_trace.set_process_label("actor-7")
        ring = obs_trace.get_ring()
        assert ring.enabled
        timer = PhaseTimer(ring=ring, track="phases")
        with timer.phase("env_step"):
            pass
        path = obs_trace.dump_ring()
        assert path is not None and os.path.exists(path)
        with open(path) as fh:
            data = json.load(fh)
        assert data["metadata"]["label"] == "actor-7"
        assert any(ev.get("name") == "env_step"
                   for ev in data["traceEvents"])
    finally:
        obs_trace.reset_for_tests()


def _written(paths) -> list[dict]:
    """The events (metadata left out) of segment files, in order."""
    out = []
    for path in paths:
        with open(path) as fh:
            out += [ev for ev in json.load(fh)["traceEvents"]
                    if ev.get("ph") != "M"]
    return out


def _segment_number(path: str) -> int:
    return int(path.rsplit(".", 2)[1])


def test_flushes_write_each_event_once_and_keep_a_ring_on_disk(tmp_path):
    ring = TraceRing("learner", enabled=True, capacity=50)
    paths = []
    for k in range(3):
        for i in range(10):
            ring.complete("phase", 100.0 * k + i, 0.5, track="t",
                          args={"k": k, "i": i})
        paths.append(ring.flush(str(tmp_path)))
    held = [ev for ev in ring.to_chrome()["traceEvents"]
            if ev.get("ph") != "M"]
    # together the segments hold what the ring holds, each event once; the
    # last flush's own span goes out with the next flush
    assert _written(paths) == held[:-1]
    assert held[-1]["name"] == "ring_flush"
    assert [ev["args"]["events"] for ev in held
            if ev["name"] == "ring_flush"] == [10, 11, 11]
    for path in paths:                   # each a whole trace, anchored
        with open(path) as fh:
            meta = json.load(fh)["metadata"]
        assert meta["label"] == "learner" and "clock_sync" in meta
    # past the ring's capacity the oldest segments go first
    for k in range(3, 9):
        for i in range(10):
            ring.complete("phase", 100.0 * k + i, 0.5, track="t")
        paths.append(ring.flush(str(tmp_path)))
    kept = sorted((str(p) for p in tmp_path.glob("trace-learner-*.json")),
                  key=_segment_number)
    on_disk = _written(kept)
    assert len(on_disk) <= ring.capacity
    assert kept == paths[-len(kept):] and not os.path.exists(paths[0])
    held = [ev for ev in ring.to_chrome()["traceEvents"]
            if ev.get("ph") != "M"]
    n = min(len(on_disk), len(held) - 1)
    assert on_disk[-n:] == held[:-1][-n:]
    # one segment fewer would have fit: only what had to go went
    dropped = paths[-len(kept) - 1]
    assert len(on_disk) + 11 > ring.capacity and not os.path.exists(dropped)


def test_a_flush_encodes_what_came_since_the_flush_before(tmp_path):
    ring = TraceRing("actor-0", enabled=True, capacity=10_000)
    for _ in range(500):
        ring.instant("tick", "t")
    ring.flush(str(tmp_path))
    # nothing but the first flush's own span is new: nothing is written
    assert ring.flush(str(tmp_path)) is None
    for _ in range(37):
        ring.instant("tick", "t")
    second = ring.flush(str(tmp_path))
    flushes = [ev for ev in ring.to_chrome()["traceEvents"]
               if ev["name"] == "ring_flush"]
    # 37 new events and the span of the flush before, not the whole ring
    assert [ev["args"]["events"] for ev in flushes] == [500, 38]
    assert len(_written([second])) == 38
    assert flushes[1]["args"]["bytes"] == os.path.getsize(second)
    assert {ev["tid"] for ev in flushes} == {ring._tid("trace-flush")}
    # a flush that finds another under way (SIGUSR2 on the thread that
    # holds it) writes nothing rather than wait for itself
    ring.instant("tick", "t")
    ring._flush_lock.acquire()
    try:
        assert ring.flush(str(tmp_path), wait=False) is None
    finally:
        ring._flush_lock.release()


def test_gc_spans_only_while_the_ring_is_enabled(monkeypatch, tmp_path):
    import gc
    import threading

    from apex_tpu.obs import trace as obs_trace

    monkeypatch.delenv("APEX_TRACE_DIR", raising=False)
    obs_trace.reset_for_tests()
    try:
        assert not obs_trace.get_ring().enabled
        assert obs_trace._gc_span not in gc.callbacks
        monkeypatch.setenv("APEX_TRACE_DIR", str(tmp_path))
        monkeypatch.setenv("APEX_TRACE_FLUSH_S", "0")
        obs_trace.reset_for_tests()
        ring = obs_trace.get_ring()
        assert gc.callbacks.count(obs_trace._gc_span) == 1
        was_on = gc.isenabled()
        gc.disable()                 # no automatic collection frees it first
        try:
            junk = [[] for _ in range(3)]
            for a, b in zip(junk, junk[1:] + junk[:1]):
                a.append(b)                      # a cycle for it to free
            del junk, a, b
            gc.collect()
        finally:
            if was_on:
                gc.enable()
        last = [ev for ev in ring.to_chrome()["traceEvents"]
                if ev["name"] == "gc"][-1]
        assert last["args"]["gen"] == 2 and last["args"]["collected"] >= 3
        assert last["tid"] == threading.get_ident() % 100_000
        assert last["dur"] >= 0
    finally:
        obs_trace.reset_for_tests()
    assert obs_trace._gc_span not in gc.callbacks


# -- merge: clock alignment --------------------------------------------------

def _fake_trace(label: str, events: list[tuple[str, float, float]]) -> dict:
    return {
        "traceEvents": [
            {"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
             "args": {"name": label}},
        ] + [
            {"name": name, "ph": "X", "pid": 1, "tid": 1,
             "ts": ts_s * 1e6, "dur": dur_s * 1e6}
            for name, ts_s, dur_s in events
        ],
        "metadata": {"label": label},
    }


def test_merge_traces_aligns_skewed_clocks_into_one_ordered_timeline():
    """Two processes with skewed wall clocks: actor-0's clock runs 5s
    AHEAD of the learner's.  True order is learner.a (t=10), actor.b
    (true t=11, stamped 16), learner.c (t=12).  Without offsets the
    merged order is wrong; with the heartbeat-derived offset (-5s for
    actor-0) the timeline is correct and ordered."""
    learner = _fake_trace("learner", [("a", 10.0, 0.1), ("c", 12.0, 0.1)])
    actor = _fake_trace("actor-0", [("b", 16.0, 0.1)])

    naive = obs_merge.merge_traces([learner, actor])
    naive_names = [ev["name"] for ev in naive["traceEvents"]
                   if ev.get("ph") == "X"]
    assert naive_names == ["a", "c", "b"]            # skew-corrupted order

    merged = obs_merge.merge_traces([learner, actor],
                                    offsets={"actor-0": -5.0})
    names = [ev["name"] for ev in merged["traceEvents"]
             if ev.get("ph") == "X"]
    assert names == ["a", "b", "c"]                  # true order restored
    # timeline re-zeroed at the earliest event and pids remapped per file
    ts = {ev["name"]: ev["ts"] for ev in merged["traceEvents"]
          if ev.get("ph") == "X"}
    assert ts["a"] == 0.0
    assert ts["b"] == pytest.approx(1e6)
    assert {ev["pid"] for ev in merged["traceEvents"]} == {1, 2}
    assert merged["metadata"]["offsets_applied"] == {"actor-0": -5.0}


def test_merge_dir_uses_fleet_summary_offsets(tmp_path):
    for label, events in (("learner", [("a", 10.0, 0.1)]),
                          ("actor-0", [("b", 16.0, 0.1)])):
        with open(tmp_path / f"trace-{label}-1.json", "w") as fh:
            json.dump(_fake_trace(label, events), fh)
    with open(tmp_path / "fleet_summary.json", "w") as fh:
        json.dump({"peers": [{"identity": "actor-0",
                              "clock_offset_s": -5.0,
                              "clock_offset_n": 9}]}, fh)
    out = tmp_path / "merged.json"
    merged = obs_merge.merge_dir(str(tmp_path), str(out))
    assert out.exists()
    names = [ev["name"] for ev in merged["traceEvents"]
             if ev.get("ph") == "X"]
    assert names == ["a", "b"]
    assert merged["traceEvents"][-1]["ts"] == pytest.approx(1e6)
    # estimate quality rides the merged metadata for triage
    assert merged["metadata"]["offset_samples"] == {"actor-0": 9}


def test_merge_dir_joins_segments_and_reads_an_older_single_file(tmp_path):
    ring = TraceRing("learner", enabled=True)
    ring.complete("a", 1.0, 0.1, track="loop")
    ring.flush(str(tmp_path))
    ring.complete("b", 2.0, 0.1, track="loop")
    ring.flush(str(tmp_path))
    # a run from before segments: one file a process
    with open(tmp_path / "trace-actor-0-77.json", "w") as fh:
        json.dump(_fake_trace("actor-0", [("c", 1.5, 0.1)]), fh)
    merged = obs_merge.merge_dir(str(tmp_path), str(tmp_path / "m.json"))
    assert merged["metadata"]["merged_from"] == ["actor-0", "learner"]
    events = merged["traceEvents"]
    names = {ev["name"] for ev in events if ev.get("ph") == "X"}
    assert names == {"a", "b", "c", "ring_flush"}
    # one process group each, its metadata once however many segments
    assert {ev["pid"] for ev in events} == {1, 2}
    learner = [ev for ev in events if ev["pid"] == 2 and ev.get("ph") == "M"]
    assert sorted(ev["name"] for ev in learner) == [
        "process_name", "thread_name", "thread_name"]
    # the last flush's own span is in no segment yet
    assert sum(1 for ev in events if ev.get("ph") == "X"
               and ev["name"] == "ring_flush") == 1


def test_merge_cli_main(tmp_path, capsys):
    with open(tmp_path / "trace-learner-1.json", "w") as fh:
        json.dump(_fake_trace("learner", [("a", 1.0, 0.1)]), fh)
    rc = obs_merge.main([str(tmp_path)])
    assert rc == 0
    assert (tmp_path / "merged_trace.json").exists()
    assert "perfetto" in capsys.readouterr().out
    assert obs_merge.main([str(tmp_path / "empty")]) == 1


# -- registry clock offsets (the heartbeat join merge consumes) --------------

def test_registry_records_clock_offset_from_heartbeat_wall():
    from apex_tpu.config import CommsConfig
    from apex_tpu.fleet.heartbeat import Heartbeat
    from apex_tpu.fleet.registry import FleetRegistry

    wall = [2000.0]
    reg = FleetRegistry(CommsConfig(), clock=lambda: 1.0,
                        wall_clock=lambda: wall[0])
    reg.observe(Heartbeat("actor-0", wall_ts=1995.0))
    snap = reg.snapshot()
    assert snap["peers"][0]["clock_offset_s"] == pytest.approx(5.0)
    # unstamped beats (wall_ts=0) leave the offset unknown, not garbage
    reg.observe(Heartbeat("actor-1"))
    snap = reg.snapshot()
    peer1 = next(p for p in snap["peers"] if p["identity"] == "actor-1")
    assert peer1["clock_offset_s"] is None


def test_registry_offset_is_min_transit_median_not_last_beat():
    """Each beat samples skew + transit; the published offset must be
    the median of the SMALLEST half of the window (transit is strictly
    additive, so small samples bound the skew), not whatever the last
    beat happened to carry — one queue-dwell spike must not own the
    estimate."""
    from apex_tpu.config import CommsConfig
    from apex_tpu.fleet.heartbeat import Heartbeat
    from apex_tpu.fleet.registry import FleetRegistry, _min_transit_offset

    wall = [0.0]
    reg = FleetRegistry(CommsConfig(), clock=lambda: 1.0,
                        wall_clock=lambda: wall[0])
    # true skew 5.0; transits 0.0, 0.8, 0.1, 4.0 (spike), 0.2
    for t, transit in ((100.0, 0.0), (102.0, 0.8), (104.0, 0.1),
                       (106.0, 4.0), (108.0, 0.2)):
        wall[0] = t + 5.0 + transit
        reg.observe(Heartbeat("actor-0", wall_ts=t))
    snap = reg.snapshot()
    p = snap["peers"][0]
    # smallest half of [5.0, 5.1, 5.2, 5.8, 9.0] -> [5.0, 5.1] -> 5.05
    assert p["clock_offset_s"] == pytest.approx(5.05)
    assert p["clock_offset_n"] == 5
    # the helper's selection semantics, pinned directly
    assert _min_transit_offset([7.0]) == 7.0
    assert _min_transit_offset([5.0, 9.0]) == 5.0
    assert _min_transit_offset([5.0, 5.2, 9.0, 5.1]) == \
        pytest.approx(5.05)
    # window bound: old samples age out (deque maxlen)
    for i in range(40):
        wall[0] = 200.0 + i + 2.0          # skew settles to 2.0
        reg.observe(Heartbeat("actor-0", wall_ts=200.0 + i))
    p = reg.snapshot()["peers"][0]
    assert p["clock_offset_s"] == pytest.approx(2.0)
    assert p["clock_offset_n"] == 16


# -- R2D2 sequence messages: span-stamped at the source drain ----------------

def test_r2d2_drain_grouped_stamps_sealed_spans(monkeypatch):
    """The recurrent family's messages are born with a lineage span in
    message METADATA (like drain_builder_chunks), so the merged timeline
    covers R2D2 too — and the payload stays span-free (the learner's
    fixed sequence-batch shapes depend on it)."""
    from apex_tpu.actors.r2d2 import drain_grouped
    from apex_tpu.obs import spans as obs_spans

    def fake_seqs(n):
        return [{"priority": np.float32(1.0), "n_new": 3,
                 "obs": np.zeros((4, 2), np.float32),
                 "action": np.zeros(4, np.int32)} for _ in range(n)]

    ready = fake_seqs(5)
    msgs = drain_grouped(ready, group=2)
    assert len(msgs) == 2 and len(ready) == 1     # partial group buffered
    for msg in msgs:
        spans = obs_spans.spans_of(msg)
        assert len(spans) == 1
        assert "sealed" in spans[0]["hops"]
        assert obs_spans.SPAN_KEY not in msg["payload"]
    # the kill switch turns stamping off at the source
    monkeypatch.setenv("APEX_OBS_SPANS", "0")
    msgs = drain_grouped(fake_seqs(2), group=2)
    assert obs_spans.SPAN_KEY not in msgs[0]


# -- prometheus rendering ----------------------------------------------------

def test_prometheus_render_sections():
    h = LatencyHistogram()
    for v in (0.1, 0.2, 0.3, 0.4):
        h.record(v)
    text = obs_metrics.render(
        gauges={"learner/loss": 0.25, "skipped": None},
        # apexlint: disable=J015 -- synthetic family name exercising the renderer
        counters={"steps_total": 123},
        histograms={"frame_age_at_train_seconds": h.snapshot()},
        labeled={"fleet_peer_fps": [({"identity": "actor-0"}, 55.0)]})
    assert "# TYPE apex_learner_loss gauge" in text
    assert "apex_learner_loss 0.25" in text
    assert "# TYPE apex_steps_total counter" in text
    assert "apex_steps_total 123.0" in text
    assert ('apex_frame_age_at_train_seconds{quantile="0.5"} 0.2'
            in text)
    assert "apex_frame_age_at_train_seconds_count 4" in text
    assert 'apex_fleet_peer_fps{identity="actor-0"} 55.0' in text
    assert "skipped" not in text
    assert text.endswith("\n")


def test_prometheus_render_fleet_and_tails():
    from collections import deque

    from apex_tpu.config import CommsConfig
    from apex_tpu.fleet.heartbeat import Heartbeat
    from apex_tpu.fleet.registry import FleetRegistry

    reg = FleetRegistry(CommsConfig())
    reg.observe(Heartbeat("actor-0", role="actor", fps=60.0,
                          chunks_sent=9))
    gauges, labeled = obs_metrics.render_fleet(reg.snapshot())
    assert gauges["fleet_alive"] == 1
    assert labeled["fleet_peer_fps"][0][1] == 60.0
    text = obs_metrics.render(gauges=gauges, labeled=labeled)
    assert "apex_fleet_alive 1.0" in text
    # labels sort alphabetically; tenant (PR 13) rides every peer row
    assert ('apex_fleet_peer_up{identity="actor-0",role="actor",'
            'state="ALIVE",tenant="t0"} 1.0' in text)

    history = {"learner/loss": deque([(0, 1.0), (5, 0.5)]),
               "learner/empty": deque()}
    assert obs_metrics.scalar_tails(history) == {"learner/loss": 0.5}
