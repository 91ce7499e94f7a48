"""The per-pass operands of the trainer loop that no pass launches a
program for (``ConcurrentTrainer._dispatch_key`` / ``_dispatch``): keys out
of a block one program made (``KeyBlocks``), bit-identical to the eager
chain and dropped when ``self.key`` is assigned from outside; beta as a
device scalar held while the host float stands, else a plain transfer,
landing on the step's one compiled program."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.config import small_test_config
from apex_tpu.obs import trace as obs_trace
from apex_tpu.training import ingest_pipeline
from apex_tpu.training.apex import ApexTrainer
from apex_tpu.training.ingest_pipeline import KEY_BLOCK, KeyBlocks
from tests.test_ingest_pipeline import ScriptedPool, _cartpole_chunk_messages


def _trainer(msgs=(), **kw) -> ApexTrainer:
    cfg = small_test_config(capacity=256, batch_size=16, n_actors=1)
    cfg = cfg.replace(
        replay=dataclasses.replace(cfg.replay, warmup=64),
        learner=dataclasses.replace(cfg.learner,
                                    target_update_interval=20))
    return ApexTrainer(cfg, pool=ScriptedPool(list(msgs)),
                       publish_min_seconds=10.0, respawn_workers=False, **kw)


def _bits(key) -> list:
    return np.asarray(jax.random.key_data(key)).tolist()


def _eager(chain, n: int):
    """``n`` steps of the eager chain: the keys, the chain after."""
    keys = []
    for _ in range(n):
        chain, k = jax.random.split(chain)
        keys.append(k)
    return keys, chain


@pytest.mark.parametrize("make_key", [jax.random.key, jax.random.PRNGKey],
                         ids=["typed", "raw_uint32"])
def test_block_program_is_the_eager_chain(make_key):
    """Every ``(k_i, chain_{i+1})`` of one launch, for both key forms a
    trainer can hold."""
    root = make_key(11)
    pairs = ingest_pipeline._split_block(root)
    assert len(pairs) == KEY_BLOCK
    chain = root
    for k_block, chain_block in pairs:
        chain, k = jax.random.split(chain)
        assert _bits(k_block) == _bits(k)
        assert _bits(chain_block) == _bits(chain)
        assert k_block.shape == k.shape and k_block.dtype == k.dtype
    assert ingest_pipeline._split_block._cache_size() <= 2     # one per key form


@pytest.mark.parametrize("n", [1, KEY_BLOCK, 2 * KEY_BLOCK + 1],
                         ids=["one", "a_whole_block", "two_blocks_and_one"])
def test_blocks_launch_once_per_block(n):
    """``KeyBlocks`` alone: a block serves exactly ``KEY_BLOCK`` takes (the
    last pair of a block launches nothing, the take after it does)."""
    blocks, chain = KeyBlocks(), jax.random.key(3)
    want, end = _eager(chain, n)
    got = []
    for _ in range(n):
        k, chain = blocks.take(chain)
        got.append(k)
    assert [_bits(k) for k in got] == [_bits(k) for k in want]
    assert _bits(chain) == _bits(end)
    assert (blocks.refills, blocks.served) == (-(-n // KEY_BLOCK), n)


def test_dispatch_keys_across_a_refill_are_the_eager_chain():
    tr = _trainer()
    n = KEY_BLOCK + 3
    chain = tr.key
    for i in range(n):
        k = tr._dispatch_key()
        chain, k_eager = jax.random.split(chain)
        assert _bits(k) == _bits(k_eager), i
        assert _bits(tr.key) == _bits(chain), i
        assert k.shape == () and k.dtype == tr.key.dtype
    assert (tr._blocks().refills, tr._blocks().served) == (2, n)
    assert len(tr._blocks()._pairs) == KEY_BLOCK - 3


@pytest.mark.parametrize("outside", ["evaluate_split", "assigned_key"])
def test_key_assigned_from_outside_drops_the_block(outside):
    """Between two dispatches something else moves ``self.key``: the chain
    goes on from the assigned key, as the eager chain would."""
    tr = _trainer()
    first = [tr._dispatch_key() for _ in range(5)]
    if outside == "evaluate_split":                 # evaluate()'s own line
        tr.key, _ = jax.random.split(tr.key)
    else:                                           # restore()'s
        tr.key = jax.random.wrap_key_data(
            jax.random.key_data(jax.random.key(99)))
    want, chain = _eager(tr.key, 4)
    got = [tr._dispatch_key() for _ in range(4)]
    assert [_bits(k) for k in got] == [_bits(k) for k in want]
    assert _bits(tr.key) == _bits(chain)
    assert (tr._blocks().refills, tr._blocks().served) == (2, 9)
    assert _bits(first[0]) != _bits(got[0])


def test_equal_key_in_another_object_still_continues_the_chain():
    """The block is held by identity, so an equal key in a new object (a
    restore of the checkpoint just saved) costs a refill and no more."""
    tr = _trainer()
    tr._dispatch_key()
    want, _ = _eager(tr.key, 2)
    tr.key = jax.random.wrap_key_data(jax.random.key_data(tr.key))
    assert [_bits(tr._dispatch_key()) for _ in range(2)] \
        == [_bits(k) for k in want]
    assert tr._blocks().refills == 2


def test_checkpoint_saved_mid_block_restores_to_the_same_next_key(tmp_path):
    msgs = _cartpole_chunk_messages(10)             # 160 > warmup 64
    tr = _trainer(msgs, checkpoint_dir=str(tmp_path))
    tr.train(total_steps=7, max_seconds=120, log_every=10 ** 9)
    assert 0 < tr._blocks().served < KEY_BLOCK and tr._blocks().refills == 1
    tr.save_checkpoint()
    next_keys = [_bits(tr._dispatch_key()) for _ in range(3)]

    back = _trainer(checkpoint_dir=str(tmp_path)).restore()
    assert back.steps_rate.total == 7
    assert [_bits(back._dispatch_key()) for _ in range(3)] == next_keys
    assert _bits(back.key) == _bits(tr.key)
    # and both are where the eager chain stands after as many dispatches
    _, chain = _eager(jax.random.split(jax.random.key(tr.cfg.env.seed))[0],
                      tr._blocks().served)
    assert _bits(tr.key) == _bits(chain)


def test_loop_and_harness_operands_share_one_program():
    """The benchmark drives ``_fused`` / ``_train`` itself with a
    ``jax.random.key``-derived key and ``jnp.float32(beta)`` and then holds
    the window's loop to the same compiled program (``one_program_each``)."""
    msgs = _cartpole_chunk_messages(12)
    tr = _trainer(msgs[:6])
    beta = jnp.float32(tr.cfg.replay.beta)
    for i, msg in enumerate(msgs[6:]):
        key = jax.random.fold_in(jax.random.key(5), 1000 + i)
        # a chunk's priorities as the staging thread hands them over on
        # this backend (the harness stages as the plan it runs does)
        tr.train_state, tr.replay_state, _ = tr._fused(
            tr.train_state, tr.replay_state, msg["payload"],
            np.asarray(msg["priorities"], np.float32), key, beta)
        tr.ingested += int(msg["n_trans"])
    tr.train_state, tr.replay_state, _ = tr._train(
        tr.train_state, tr.replay_state,
        jax.random.fold_in(jax.random.key(5), 2000), beta)
    assert tr._fused._cache_size() == tr._train._cache_size() == 1
    tr.train(total_steps=12, max_seconds=120, log_every=4)
    assert tr._fused._cache_size() == tr._train._cache_size() == 1
    assert tr._blocks().served == 12 and tr._blocks().refills == 1
    assert tr.beta_puts + tr.beta_reused == 12
    assert tr.beta_puts >= 1
    logged = tr.log.history
    assert logged["learner/loop_keys_served"][-1][1] <= 12
    assert logged["learner/loop_key_refills"][-1][1] == 1
    assert {"learner/loop_beta_puts", "learner/loop_beta_reused"} \
        <= set(logged)


def test_beta_is_held_while_the_float_stands_and_follows_hparams():
    tr = _trainer()
    seen = []

    def fn(*operands):
        seen.append(operands[-1])
        return ()

    def dispatch():
        with tr._dispatch("train", fn, beta=tr._beta) as call:
            call()
        return seen[-1]

    from apex_tpu.utils.profiling import DispatchGapTimer
    tr._dispatch_gap = DispatchGapTimer()
    a, b = dispatch(), dispatch()
    assert a is b and (tr.beta_puts, tr.beta_reused) == (1, 1)
    assert isinstance(a, jax.Array) and a.dtype == jnp.float32 \
        and a.shape == () and not a.weak_type
    assert float(a) == float(np.float32(tr._beta()))
    assert a.aval == jnp.float32(tr._beta()).aval

    tr.ingested += 1000                             # the anneal moves
    c = dispatch()
    assert c is not a and float(c) == float(np.float32(tr._beta()))
    assert float(c) > float(a)

    tr.apply_hparams({"prio_beta": 0.9})            # seen by the very next
    d = dispatch()
    assert float(d) == float(np.float32(tr._beta())) and float(d) > 0.9
    assert (tr.beta_puts, tr.beta_reused) == (3, 1)

    tr.ingested = 10 ** 9                           # anneal over: 1.0 for good
    e, f = dispatch(), dispatch()
    assert float(e) == 1.0 and e is f


def test_a_refill_is_an_instant_on_the_loop_track(tmp_path, monkeypatch):
    monkeypatch.setenv("APEX_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("APEX_TRACE_FLUSH_S", "0")
    obs_trace.reset_for_tests()
    try:
        tr = _trainer(_cartpole_chunk_messages(10))
        tr.train(total_steps=6, max_seconds=120, log_every=10 ** 9)
        chrome = obs_trace.get_ring().to_chrome()
    finally:
        obs_trace.reset_for_tests()
    tid = next(ev["tid"] for ev in chrome["traceEvents"]
               if ev.get("name") == "thread_name"
               and ev["args"]["name"] == "learner-hot-loop")
    on_track = [ev for ev in chrome["traceEvents"] if ev.get("tid") == tid]
    refills = [ev for ev in on_track if ev["name"] == "key_refill"]
    assert len(refills) == tr._blocks().refills == 1
    assert refills[0]["ph"] == "i" and refills[0]["args"]["it"] >= 1
    # inside the pass's dispatch_key span, which keeps its name and site
    keys = [ev for ev in on_track if ev["name"] == "dispatch_key"]
    assert len(keys) == tr._blocks().served == 6
    assert any(k["ts"] <= refills[0]["ts"] <= k["ts"] + k["dur"]
               for k in keys)
    assert sum(ev["name"] == "beta" for ev in on_track) == 6
