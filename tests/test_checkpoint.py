"""Checkpointing: full-bundle save/restore is bit-exact; enjoy needs no trainer.

The reference persists weights only (``origin_repo/learner.py:166-168``);
SURVEY.md §5.4 asks for the full train-state pytree.  These tests pin the
stronger contract: optimizer state, replay contents (ring + trees + cursors),
and the RNG key all round-trip, so a killed/restored learner continues on
EXACTLY the trajectory the uninterrupted one would have taken.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.config import small_test_config
from apex_tpu.training.checkpoint import (Checkpointer, config_from_meta,
                                          config_to_meta,
                                          evaluate_checkpoint, load_raw)
from apex_tpu.training.dqn import DQNTrainer


def _pure_train_steps(tr, m: int) -> None:
    """Learner-only continuation (no env interaction): the part of a resumed
    run whose bit-exactness the checkpoint alone determines."""
    for _ in range(m):
        tr.key, k = jax.random.split(tr.key)
        tr.train_state, tr.replay_state, _ = tr._train_step(
            tr.train_state, tr.replay_state, k, jnp.float32(0.5))


@pytest.mark.slow
def test_kill_restore_resume_is_bit_exact(tmp_path):
    cfg = small_test_config(capacity=256, batch_size=16, n_actors=1)
    t1 = DQNTrainer(cfg, checkpoint_dir=str(tmp_path / "ck"))
    t1.train(total_frames=300)          # past warmup; real training happened
    assert t1.steps_rate.total > 0
    path = t1.save_checkpoint()

    t2 = DQNTrainer(cfg, checkpoint_dir=str(tmp_path / "ck2"))
    t2.restore(path)                    # the "new process after a kill"
    assert t2.steps_rate.total == t1.steps_rate.total
    assert t2.ingested == t1.ingested

    _pure_train_steps(t1, 5)
    _pure_train_steps(t2, 5)
    for a, b in zip(jax.tree.leaves(t1.train_state),
                    jax.tree.leaves(t2.train_state), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(t1.replay_state),
                    jax.tree.leaves(t2.replay_state), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_autosave_every_save_interval(tmp_path):
    import dataclasses
    cfg = small_test_config(capacity=256, batch_size=16, n_actors=1)
    cfg = cfg.replace(learner=dataclasses.replace(cfg.learner,
                                                  save_interval=50))
    t = DQNTrainer(cfg, checkpoint_dir=str(tmp_path / "ck"))
    t.train(total_frames=200)
    assert t.checkpointer.latest_path() is not None
    _, meta = load_raw(t.checkpointer.latest_path())
    assert meta["steps"] % 50 == 0 and meta["steps"] > 0


def test_evaluate_checkpoint_without_trainer(tmp_path):
    cfg = small_test_config(capacity=256, batch_size=16, n_actors=1)
    t = DQNTrainer(cfg, checkpoint_dir=str(tmp_path / "ck"))
    t.train(total_frames=200)
    path = t.save_checkpoint()
    del t                               # nothing of the trainer survives
    score = evaluate_checkpoint(path, episodes=2, max_steps=100)
    assert np.isfinite(score) and score > 0  # CartPole reward >= episode len


@pytest.mark.slow
def test_evaluate_checkpoint_aql_family(tmp_path):
    """enjoy dispatches on the spec: AQL checkpoints rebuild AQLNetwork
    and drive Box actions — no trainer object, no family flag."""
    import dataclasses

    from apex_tpu.training.aql import AQLTrainer
    cfg = small_test_config(capacity=256, batch_size=16,
                            env_id="ApexContinuousNav-v0")
    cfg = cfg.replace(aql=dataclasses.replace(cfg.aql, propose_sample=8,
                                              uniform_sample=16))
    t = AQLTrainer(cfg, checkpoint_dir=str(tmp_path / "ck"))
    t.train(total_frames=150)
    path = t.save_checkpoint()
    del t
    score = evaluate_checkpoint(path, episodes=2, max_steps=40)
    assert np.isfinite(score)


def test_checkpointer_prunes_to_keep(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=3)
    bundle = dict(x=jnp.arange(4))
    for step in (10, 20, 30, 40):
        ck.save(step, bundle, dict(step=step))
    names = sorted(p.name for p in tmp_path.glob("ckpt_*.msgpack"))
    assert names == ["ckpt_20.msgpack", "ckpt_30.msgpack",
                     "ckpt_40.msgpack"]
    assert ck.latest_path().endswith("ckpt_40.msgpack")


def test_config_meta_roundtrip():
    cfg = small_test_config(capacity=512, batch_size=64, n_actors=4)
    assert config_from_meta(config_to_meta(cfg)) == cfg


@pytest.mark.slow
def test_sharded_trainer_checkpoint_roundtrip(tmp_path):
    """dp=8: the full bundle (replicated train state + 8 sharded frame-pool
    replicas) saves, restores into a FRESH trainer, and the restored state
    drives the sharded fused step — multi-chip runs are resumable too."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from apex_tpu.training.apex import ApexTrainer

    cfg = small_test_config(capacity=512, batch_size=16, n_actors=2)
    cfg = cfg.replace(learner=dataclasses.replace(
        cfg.learner, mesh_shape=(8,)))
    t = ApexTrainer(cfg, publish_min_seconds=0.05,
                    checkpoint_dir=str(tmp_path))
    t.train(total_steps=12, max_seconds=240)
    path = t.save_checkpoint()
    saved_params = jax.device_get(t.train_state.params)
    saved_steps = t.steps_rate.total

    t2 = ApexTrainer(cfg, publish_min_seconds=0.05,
                     checkpoint_dir=str(tmp_path))
    t2.restore(path)
    assert t2.steps_rate.total == saved_steps
    restored = jax.device_get(t2.train_state.params)
    for a, b in zip(jax.tree.leaves(saved_params),
                    jax.tree.leaves(restored)):
        np.testing.assert_array_equal(a, b)

    # the restored (host-resident) state must drive the SHARDED step
    ts, rs, metrics = t2._train(t2.train_state, t2.replay_state,
                                jax.random.key(7), jnp.float32(0.5))
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.slow
def test_cli_kill_minus_nine_and_resume(tmp_path):
    """The operator drill: SIGKILL a running `--role apex`
    learner mid-run, relaunch with --restore, and the run continues from
    the newest checkpoint's step counter instead of step 0."""
    import json
    import os
    import signal
    import subprocess
    import sys
    import time

    from apex_tpu.training.checkpoint import Checkpointer, load_raw

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ckdir = str(tmp_path / "ck")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = [sys.executable, "-m", "apex_tpu.runtime", "--role", "apex",
            "--env-id", "ApexCartPole-v0", "--n-actors", "2",
            "--batch-size", "32", "--capacity", "2048", "--warmup", "64",
            "--save-interval", "50", "--checkpoint-dir", ckdir,
            "--max-seconds", "600"]
    proc = subprocess.Popen(args + ["--total-steps", "1000000"],
                            env=env, cwd=repo_root,
                            start_new_session=True)
    try:
        ck = Checkpointer(ckdir)
        deadline = time.monotonic() + 300
        while not ck._all() and time.monotonic() < deadline:
            time.sleep(0.5)
        assert ck._all(), "no checkpoint appeared before the kill"
    finally:
        # SIGKILL the whole session: no atexit, actor orphans die too
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)

    _, meta = load_raw(ck.latest_path())
    s1 = meta["steps"]
    assert s1 >= 50

    rc = subprocess.run(args + ["--restore", "--total-steps", "120"],
                        env=env, cwd=repo_root, timeout=480).returncode
    assert rc == 0
    _, meta2 = load_raw(ck.latest_path())
    assert meta2["steps"] >= s1 + 100, (s1, meta2["steps"])


def test_enjoy_render_hooks(tmp_path):
    """Rendered enjoy: ascii mode rasterizes pixel
    observations; save mode writes one .npy stack per episode through the
    full checkpoint-eval path."""
    import io

    from apex_tpu.training.checkpoint import evaluate_checkpoint
    from apex_tpu.utils.render import ascii_frame, make_render_hook

    # raster sanity on a synthetic frame: bright pixel -> dense glyph
    frame = np.zeros((84, 84, 1), np.uint8)
    frame[10:20, 10:20] = 255
    art = ascii_frame(frame, width=32)
    lines = art.splitlines()
    assert len(lines) >= 8 and len(lines[0]) == 32
    assert "@" in art and " " in art

    # ascii hook streams without error for pixel and vector obs
    buf = io.StringIO()
    hook = make_render_hook("ascii", stream=buf)
    hook(frame)
    hook(np.array([0.1, -0.2], np.float32))
    assert "@" in buf.getvalue() and "+0.100" in buf.getvalue()

    # save mode through a real checkpoint eval
    cfg = small_test_config(capacity=256, batch_size=16,
                            env_id="ApexCatchSmall-v0")
    trainer = DQNTrainer(cfg, checkpoint_dir=str(tmp_path))
    path = trainer.save_checkpoint()
    out = tmp_path / "frames"
    hook = make_render_hook("save", out_dir=str(out))
    score = evaluate_checkpoint(path, episodes=2, max_steps=30,
                                render_hook=hook)
    assert np.isfinite(score)
    files = sorted(out.glob("episode_*.npy"))
    assert len(files) == 2
    stack = np.load(files[0])
    assert stack.ndim == 4 and stack.shape[1:] == (42, 42, 1)


@pytest.mark.slow
def test_pixel_aql_frame_pool_checkpoint_roundtrip(tmp_path):
    """The frame-pool AQL bundle (frames ring + a_mu sidecar dict in
    FramePoolState.extras) must save and restore bit-exactly like every
    other layout."""
    import dataclasses as dc

    from apex_tpu.training.aql import AQLApexTrainer

    cfg = small_test_config(capacity=1024, batch_size=16, n_actors=1,
                            env_id="ApexCatchSmall-v0")
    cfg = cfg.replace(
        env=dc.replace(cfg.env, frame_stack=2),
        replay=dc.replace(cfg.replay, warmup=64),
        aql=dc.replace(cfg.aql, propose_sample=6, uniform_sample=3))
    t1 = AQLApexTrainer(cfg, publish_min_seconds=0.05, train_ratio=8.0,
                        checkpoint_dir=str(tmp_path))
    t1.train(total_steps=5, max_seconds=180)
    assert t1.steps_rate.total >= 5
    path = t1.save_checkpoint()

    t2 = AQLApexTrainer(cfg, publish_min_seconds=0.05,
                        checkpoint_dir=str(tmp_path))
    t2.restore(path)
    assert t2.steps_rate.total == t1.steps_rate.total
    assert t2.ingested == t1.ingested
    for a, b in zip(jax.tree.leaves(t1.replay_state),
                    jax.tree.leaves(t2.replay_state), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(t1.train_state.params),
                    jax.tree.leaves(t2.train_state.params), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the sidecar dict specifically survived
    np.testing.assert_array_equal(
        np.asarray(t1.replay_state.extras["a_mu"]),
        np.asarray(t2.replay_state.extras["a_mu"]))
