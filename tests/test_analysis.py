"""apexlint (apex_tpu/analysis) — rule fixtures, engine behavior, CLI.

Every rule has a firing (bad) and a non-firing (good) fixture: the pair IS
the rule's behavioral contract — heuristics may evolve, these pairs must
keep holding.  A self-check at the bottom asserts the repo itself lints
clean against the checked-in baseline, so the CI gate and this suite can
never drift apart.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from apex_tpu.analysis import (Baseline, all_rules, analyze_source)
from apex_tpu.analysis.cli import load_config, main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_rule(src: str, rule_id: str):
    """Findings of ONE rule on a dedented source snippet."""
    rules = {rule_id: all_rules()[rule_id]}
    findings, _ = analyze_source(textwrap.dedent(src), path="fix.py",
                                 rules=rules)
    return findings


def fires(src: str, rule_id: str) -> bool:
    return any(f.rule == rule_id for f in run_rule(src, rule_id))


# -- J001: jit without donation on step functions ---------------------------

def test_j001_fires_on_undonated_train_step():
    assert fires("""
        import jax
        class Core:
            def jit_train_step(self):
                return jax.jit(self.train_step)
        """, "J001")


def test_j001_silent_with_donation():
    assert not fires("""
        import jax
        class Core:
            def jit_train_step(self):
                return jax.jit(self.train_step, donate_argnums=(0, 1))
        """, "J001")


def test_j001_silent_on_policy_fn():
    # params are reused across calls — donation would be wrong, and the
    # rule must not demand it
    assert not fires("""
        import jax
        policy = jax.jit(make_policy_fn(model))
        act = jax.jit(policy_fn)
        """, "J001")


def test_j001_decorator_forms():
    assert fires("""
        import jax
        @jax.jit
        def fused_train_step(ts, rs, batch):
            return ts
        """, "J001")
    assert not fires("""
        from functools import partial
        import jax
        @partial(jax.jit, donate_argnums=(0, 1))
        def fused_train_step(ts, rs, batch):
            return ts
        """, "J001")


def test_j001_fires_on_ingest():
    assert fires("""
        import jax
        step = jax.jit(ingest)
        """, "J001")


# -- J002: host sync inside jitted code -------------------------------------

def test_j002_fires_on_float_in_jit():
    assert fires("""
        import jax
        @jax.jit
        def train_step(ts, batch):
            lr = float(ts.lr)
            return lr
        """, "J002")


def test_j002_fires_on_item_and_asarray():
    src = """
        import jax
        import numpy as np
        @jax.jit
        def train_step(ts, batch):
            a = ts.loss.item()
            b = np.asarray(batch)
            return a, b
        """
    got = {f.line for f in run_rule(src, "J002")}
    assert len(got) == 2


def test_j002_silent_outside_jit():
    # the host-side driver loop is ALLOWED to sync — that's its job
    assert not fires("""
        import numpy as np
        def add_step(self, q):
            return float(np.max(q))
        """, "J002")


def test_j002_silent_on_constants():
    assert not fires("""
        import jax
        @jax.jit
        def train_step(ts):
            return ts.x * float(1e-3)
        """, "J002")


def test_j002_sees_jit_call_sites_not_just_decorators():
    assert fires("""
        import jax
        def train_step(ts, batch):
            return float(ts.loss)
        step = jax.jit(train_step, donate_argnums=(0,))
        """, "J002")


def test_j002_sees_transitive_callees():
    # train_step is jitted and calls helper: helper is traced too
    assert fires("""
        import jax
        def helper(x):
            return float(x)
        def train_step(ts):
            return helper(ts.x)
        step = jax.jit(train_step)
        """, "J002")


def test_j002_sees_make_fn_factory_closures():
    # the repo convention: make_*_fn closures get jitted at call sites in
    # OTHER modules — the factory body must count as jitted scope
    assert fires("""
        def make_policy_fn(model):
            def policy(params, obs):
                return float(model.apply(params, obs))
            return policy
        """, "J002")


# -- J003: Python control flow on traced values -----------------------------

def test_j003_fires_on_param_comparison():
    assert fires("""
        import jax
        @jax.jit
        def step(x):
            if x > 0:
                return x
            return -x
        """, "J003")


def test_j003_fires_on_jnp_test():
    assert fires("""
        import jax
        import jax.numpy as jnp
        @jax.jit
        def step(mask):
            while jnp.any(mask):
                mask = update(mask)
            return mask
        """, "J003")


def test_j003_fires_on_traced_param_attribute():
    # ts.step is a field of the traced state, traced itself
    assert fires("""
        import jax
        @jax.jit
        def train_step(ts):
            if ts.step > 0:
                return ts
            return ts
        """, "J003")


def test_j003_silent_on_static_dispatch():
    # `is None` / isinstance / static-hint params are config branching
    assert not fires("""
        import jax
        @jax.jit
        def step(x, axis_name=None, mode="a"):
            if axis_name is not None:
                x = psum(x, axis_name)
            if mode == "a":
                return x
            return -x
        """, "J003")


def test_j003_silent_outside_jit():
    assert not fires("""
        def host_loop(reward):
            if reward > 0:
                return reward
        """, "J003")


# -- J004: PRNG key reuse ---------------------------------------------------

def test_j004_fires_on_double_use():
    assert fires("""
        import jax
        def f(key):
            a = jax.random.normal(key, (2,))
            b = jax.random.normal(key, (2,))
            return a + b
        """, "J004")


def test_j004_silent_after_split():
    assert not fires("""
        import jax
        def f(key):
            k1, k2 = jax.random.split(key)
            a = jax.random.normal(k1, (2,))
            b = jax.random.normal(k2, (2,))
            return a + b
        """, "J004")


def test_j004_fires_on_loop_reuse():
    assert fires("""
        import jax
        def f(key):
            out = []
            for _ in range(4):
                out.append(jax.random.normal(key, (2,)))
            return out
        """, "J004")


def test_j004_silent_on_per_iteration_split():
    assert not fires("""
        import jax
        def f(key):
            out = []
            for _ in range(4):
                key, k = jax.random.split(key)
                out.append(jax.random.normal(k, (2,)))
            return out
        """, "J004")


def test_j004_silent_on_branch_exclusive_use():
    # if/else (and early-return fall-through) arms each use the key once
    assert not fires("""
        import jax
        def f(key, discrete):
            if discrete:
                return jax.random.categorical(key, logits)
            return jax.random.normal(key, (2,))
        """, "J004")


def test_j004_silent_on_indexed_key_batch():
    assert not fires("""
        import jax
        def f(key):
            keys = jax.random.split(key, 8)
            out = []
            for i in range(8):
                out.append(jax.random.normal(keys[i], (2,)))
            return out
        """, "J004")


def test_j004_silent_on_comprehension_shadowing():
    assert not fires("""
        import jax
        def f(key, metrics):
            key, k = jax.random.split(key)
            use(k)
            return {k: float(v) for k, v in metrics.items()}
        """, "J004")


def test_j004_silent_on_numpy_generator_param():
    # `rng` is the numpy.random.Generator convention: stateful, reuse is
    # the point — only jax `key` params opt into tracking
    assert not fires("""
        def f(rng):
            a = helper(rng)
            b = helper(rng)
            return a, b
        """, "J004")


def test_j004_fires_in_nested_def_scope():
    assert fires("""
        import jax
        def outer():
            def sample(key):
                a = jax.random.normal(key, (2,))
                b = jax.random.uniform(key, (2,))
                return a + b
            return sample
        """, "J004")


def test_j004_silent_on_introspection_calls():
    # getattr/isinstance/len read type facts, not PRNG material — a
    # dtype dispatch before the single real consumption is not a reuse
    # (the sharded-plan key wrappers in training/apex.py do exactly this)
    assert not fires("""
        import jax
        def dispatch(key, sl):
            if getattr(key, "dtype", None) == "uint32":
                return key
            return sl.device_keys(key)
        """, "J004")


# -- J005: jit inside a loop ------------------------------------------------

def test_j005_fires_in_loop():
    assert fires("""
        import jax
        def run(fns, x):
            for fn in fns:
                y = jax.jit(fn)(x)
            return y
        """, "J005")


def test_j005_silent_outside_loop():
    assert not fires("""
        import jax
        def run(fn, xs):
            jfn = jax.jit(fn)
            for x in xs:
                y = jfn(x)
            return y
        """, "J005")


# -- J006: host sync inside a hot loop --------------------------------------

def test_j006_fires_on_device_get_in_loop():
    assert fires("""
        import jax
        def train_loop(pool, ts):
            while True:
                step(ts)
                params = jax.device_get(ts.params)
                pool.publish_params(1, params)
        """, "J006")


def test_j006_fires_on_block_until_ready_method_in_loop():
    assert fires("""
        def drain(chunks, ingest, rs):
            for chunk in chunks:
                rs = ingest(rs, chunk)
                rs.pos.block_until_ready()
            return rs
        """, "J006")


def test_j006_silent_outside_loop():
    assert not fires("""
        import jax
        def publish(pool, ts):
            params = jax.device_get(ts.params)
            pool.publish_params(1, params)
        """, "J006")


def test_j006_silent_in_timing_harness():
    """A loop that reads the clock is a measurement harness — timing a
    device fence is the one legitimate hot-loop sync (a benchmark's rep
    loops)."""
    assert not fires("""
        import time, jax
        def measure(fn, ts, reps):
            rates = []
            for _ in range(reps):
                t0 = time.perf_counter()
                out = fn(ts)
                jax.block_until_ready(out)
                rates.append(time.perf_counter() - t0)
            return rates
        """, "J006")


def test_j006_silent_under_trace_scope():
    assert not fires("""
        import jax
        from apex_tpu.utils.profiling import trace
        def profile(fn, ts, xs):
            with trace("/tmp/prof"):
                for x in xs:
                    jax.block_until_ready(fn(ts, x))
        """, "J006")


def test_j006_silent_in_jitted_scope():
    """Inside jit it's J002's territory, not a hot-loop finding."""
    assert not fires("""
        import jax
        @jax.jit
        def step(xs):
            for x in xs:
                y = jax.device_get(x)
            return y
        """, "J006")


# -- J007: device_put inside jitted/shard_map scope -------------------------

def test_j007_fires_on_device_put_in_jit():
    assert fires("""
        import jax
        @jax.jit
        def fused_step(ts, batch):
            batch = jax.device_put(batch)
            return update(ts, batch)
        """, "J007")


def test_j007_fires_inside_shard_map_body():
    """shard_map bodies are jitted scope: the mapped per-chip fn always
    runs inside the compiled program (jit detection seeds on any
    shard_map / shard_map_compat call)."""
    assert fires("""
        import jax
        from apex_tpu.parallel.mesh import shard_map_compat
        def make_step(mesh, spec):
            def per_chip(rs, ingest):
                ingest = jax.device_put(ingest)
                return add(rs, ingest)
            return jax.jit(shard_map_compat(
                per_chip, mesh=mesh, in_specs=spec, out_specs=spec))
        """, "J007")


def test_j007_silent_on_host_side_staging():
    """The staging thread's device_put — OUTSIDE any jitted scope — is
    the sanctioned pattern the rule points at."""
    assert not fires("""
        import jax
        def stage(slot, sharding):
            return jax.tree.map(
                lambda x: jax.device_put(x, sharding), slot)
        """, "J007")


def test_j007_silent_on_unrelated_attr():
    assert not fires("""
        import jax
        @jax.jit
        def step(ts, pool):
            return pool.device_put_count
        """, "J007")


# -- J008: jitted result materialized before its use site -------------------

def test_j008_fires_on_eager_materialize_in_step_loop():
    """The exact pre-PR-4 actor anti-pattern: dispatch, block on
    np.asarray immediately, then do unrelated host work before the slot
    loop consumes the values — the sync serializes dispatch against work
    it could overlap (actors/vector.py removed this shape)."""
    assert fires("""
        import jax
        import numpy as np
        class Fam:
            def __init__(self, fn):
                self.policy = jax.jit(fn)
            def step_all(self, params, stacks, eps, key):
                out = self.policy(params, stacks, eps, key)
                actions = np.asarray(out[0])
                stats = []
                bookkeeping(stats)
                for i in range(len(stats)):
                    step_env(i, actions[i])
                return stats
        """, "J008")


def test_j008_fires_on_device_get_inside_loop():
    assert fires("""
        import jax
        step = jax.jit(fused)
        def drive(ts, chunks):
            for chunk in chunks:
                m = step(ts, chunk)
                host = jax.device_get(m)
                other_work(chunk)
                log(host)
        """, "J008")


def test_j008_silent_when_materialized_at_use_site():
    """Deferring the sync to immediately before the consuming loop is the
    sanctioned shape (the double-buffered step materializes each group
    right before stepping that group's envs)."""
    assert not fires("""
        import jax
        import numpy as np
        class Fam:
            def __init__(self, fn):
                self.policy = jax.jit(fn)
            def step_all(self, params, stacks, eps, key):
                out = self.policy(params, stacks, eps, key)
                stats = []
                bookkeeping(stats)
                actions = np.asarray(out[0])
                for i in range(len(stats)):
                    step_env(i, actions[i])
                return stats
        """, "J008")


def test_j008_silent_under_phase_timer_scope():
    """A deliberate, *accounted* wait (PhaseTimer.phase) is exempt — the
    actor families time their policy-wait there on purpose."""
    assert not fires("""
        import jax
        import numpy as np
        class Fam:
            def __init__(self, fn, timer):
                self.policy = jax.jit(fn)
                self.phase = timer
            def step_all(self, params, stacks, eps, key):
                out = self.policy(params, stacks, eps, key)
                with self.phase.phase("policy_wait"):
                    actions = np.asarray(out[0])
                bookkeeping()
                for a in actions:
                    step_env(a)
        """, "J008")


def test_j008_silent_on_plain_numpy_asarray():
    """np.asarray over host values (no jit dispatch in sight) is ordinary
    numpy code, not a device sync."""
    assert not fires("""
        import numpy as np
        def collect(rows):
            arr = np.asarray(rows)
            out = []
            normalize(out)
            for r in arr:
                out.append(r)
            return out
        """, "J008")


# -- C001: process start after a live thread --------------------------------

def test_c001_fires_on_fork_after_thread():
    assert fires("""
        import threading, multiprocessing
        def boot(w, m):
            t = threading.Thread(target=w)
            t.start()
            p = multiprocessing.Process(target=m)
            p.start()
        """, "C001")


def test_c001_silent_with_spawn_context():
    assert not fires("""
        import threading, multiprocessing as mp
        ctx = mp.get_context("spawn")
        def boot(w, m):
            t = threading.Thread(target=w)
            t.start()
            p = ctx.Process(target=m)
            p.start()
        """, "C001")


def test_c001_exactly_one_finding_not_duplicated_at_module_scope():
    findings = run_rule("""
        import threading, multiprocessing
        def boot(w, m):
            t = threading.Thread(target=w)
            t.start()
            p = multiprocessing.Process(target=m)
            p.start()
        """, "C001")
    assert len(findings) == 1


def test_c001_silent_across_separate_functions():
    # runtime order of two functions is unknowable statically
    assert not fires("""
        import threading, multiprocessing
        def a(w):
            t = threading.Thread(target=w)
            t.start()
        def b(m):
            p = multiprocessing.Process(target=m)
            p.start()
        """, "C001")


def test_c001_silent_processes_first():
    assert not fires("""
        import threading, multiprocessing
        def boot(w, m):
            p = multiprocessing.Process(target=m)
            p.start()
            t = threading.Thread(target=w)
            t.start()
        """, "C001")


# -- C002: zmq socket lifecycle ---------------------------------------------

def test_c002_fires_on_unclosed_local_socket():
    assert fires("""
        import zmq
        def send(msg):
            sock = zmq.Context.instance().socket(zmq.PUSH)
            sock.send(msg)
        """, "C002")


def test_c002_silent_when_closed():
    assert not fires("""
        import zmq
        def send(msg):
            sock = zmq.Context.instance().socket(zmq.PUSH)
            try:
                sock.send(msg)
            finally:
                sock.close(linger=0)
        """, "C002")


def test_c002_fires_on_class_socket_without_teardown():
    assert fires("""
        import zmq
        class Pub:
            def __init__(self, ctx):
                self.sock = ctx.socket(zmq.PUB)
        """, "C002")


def test_c002_silent_on_class_with_close():
    assert not fires("""
        import zmq
        class Pub:
            def __init__(self, ctx):
                self.sock = ctx.socket(zmq.PUB)
            def close(self):
                self.sock.close(linger=0)
        """, "C002")


def test_c002_silent_when_socket_escapes():
    # handed to another owner: the receiver's lifecycle problem
    assert not fires("""
        import zmq
        def make(ctx, registry):
            sock = ctx.socket(zmq.PUB)
            registry.add(sock)
        """, "C002")


# -- C003: shm created without close/unlink ---------------------------------

def test_c003_fires_on_leaked_segment():
    assert fires("""
        def make(name):
            ring = ShmRing(name, slot_size=64, n_slots=8, create=True)
            ring.push(b"x")
        """, "C003")


def test_c003_silent_when_closed():
    assert not fires("""
        def make(name):
            ring = ShmRing(name, slot_size=64, n_slots=8, create=True)
            try:
                ring.push(b"x")
            finally:
                ring.close()
        """, "C003")


def test_c003_silent_on_open_not_create():
    assert not fires("""
        def peek(name):
            ring = ShmRing(name)
            return ring.pending()
        """, "C003")


# -- C004: unlink from a non-creator ----------------------------------------

def test_c004_fires_on_foreign_unlink():
    assert fires("""
        from multiprocessing import shared_memory
        def drop(name):
            seg = shared_memory.SharedMemory(name, create=False)
            seg.unlink()
        """, "C004")


def test_c004_silent_for_creator():
    assert not fires("""
        from multiprocessing import shared_memory
        def make(name):
            seg = shared_memory.SharedMemory(name, create=True, size=64)
            seg.unlink()
        """, "C004")


def test_c004_silent_under_owner_guard():
    # ring.py contract: runtime-determined ownership gates unlink
    assert not fires("""
        class Facade:
            def __init__(self, name):
                self._ring = ShmRing(name)
            def close(self):
                if self._owner:
                    self._ring.unlink()
        """, "C004")


def test_c004_fires_on_unguarded_class_unlink():
    assert fires("""
        class Facade:
            def __init__(self, name):
                self._ring = ShmRing(name)
            def close(self):
                self._ring.unlink()
        """, "C004")


# -- C005: naked pickle loads ----------------------------------------------

def test_c005_fires_on_naked_pickle_loads():
    assert fires("""
        import pickle
        def recv(sock):
            return pickle.loads(sock.recv())
        """, "C005")


def test_c005_fires_on_unpickler_construction():
    assert fires("""
        import io, pickle
        def recv(data):
            return pickle.Unpickler(io.BytesIO(data)).load()
        """, "C005")
    assert fires("""
        import io
        from pickle import Unpickler
        def recv(data):
            return Unpickler(io.BytesIO(data)).load()
        """, "C005")


def test_c005_silent_through_restricted_wire():
    # the sanctioned path: route receives through the allowlisted module
    assert not fires("""
        from apex_tpu.runtime import wire
        def recv(sock):
            return wire.restricted_loads(sock.recv())
        """, "C005")
    # dumps (send side) and json.loads are not unpickles
    assert not fires("""
        import json, pickle
        def send(sock, msg):
            sock.send(pickle.dumps(msg))
            return json.loads(sock.recv())
        """, "C005")


def test_c005_allowlisted_module_is_exempt():
    # wire.py IS the restricted unpickler — the one place a raw
    # Unpickler may exist
    src = textwrap.dedent("""
        import pickle
        class RestrictedUnpickler(pickle.Unpickler):
            pass
        def restricted_loads(data):
            import io
            return RestrictedUnpickler(io.BytesIO(data)).load()
        """)
    rules = {"C005": all_rules()["C005"]}
    findings, _ = analyze_source(src, path="apex_tpu/runtime/wire.py",
                                 rules=rules)
    assert not findings


# -- J009: device arrays on mp queues ---------------------------------------

def test_j009_fires_on_device_result_put():
    assert fires("""
        import jax
        policy = jax.jit(policy_fn)
        def worker(params, x, chunk_queue):
            while True:
                actions, q_values = policy(params, x)
                chunk_queue.put((actions, q_values))
        """, "J009")


def test_j009_silent_with_host_materialize():
    # materialized inline at the put site
    assert not fires("""
        import jax
        import numpy as np
        policy = jax.jit(policy_fn)
        def worker(params, x, chunk_queue):
            while True:
                actions, q_values = policy(params, x)
                chunk_queue.put((int(actions[0]), np.asarray(q_values)))
        """, "J009")
    # or rebound to a host var first
    assert not fires("""
        import jax
        import numpy as np
        policy = jax.jit(policy_fn)
        def worker(params, x, stat_q):
            while True:
                q_values = policy(params, x)
                host_q = np.asarray(q_values)
                stat_q.put_nowait(host_q)
        """, "J009")


def test_j009_silent_on_host_data_and_non_queues():
    # plain host messages on queues are the normal case
    assert not fires("""
        import jax
        policy = jax.jit(policy_fn)
        def worker(chunk_queue, builder, params, x):
            a = policy(params, x)
            for msg in builder.poll():
                chunk_queue.put(("chunk", 0, msg))
        """, "J009")
    # a non-queue receiver named `sink` is out of scope
    assert not fires("""
        import jax
        policy = jax.jit(policy_fn)
        def worker(sink, params, x):
            a = policy(params, x)
            sink.put(a)
        """, "J009")


# -- J010: host clocks / obs span emission inside jitted scope ---------------

def test_j010_fires_on_clock_read_in_jitted_step():
    # the obs-plane hazard: a timestamp read inside the compiled program
    # traces to ONE frozen constant per compile
    assert fires("""
        import time
        import jax
        @jax.jit
        def fused_step(ts, rs, chunk):
            t0 = time.perf_counter()
            return update(ts, rs, chunk), t0
        """, "J010")
    assert fires("""
        import jax
        from time import monotonic
        def train_step(ts, batch):
            started = monotonic()
            return apply(ts, batch), started
        step = jax.jit(train_step)
        """, "J010")


def test_j010_fires_on_span_emission_in_jitted_scope():
    assert fires("""
        import jax
        from apex_tpu.obs import spans as obs_spans
        @jax.jit
        def fused_step(ts, rs, msg):
            stamp(msg, "consume")
            return update(ts, rs, msg)
        """, "J010")
    assert fires("""
        import jax
        @jax.jit
        def train_step(ts, batch, ring):
            ring.complete("x", 0.0, 0.1)
            return apply(ts, batch)
        """, "J010")


def test_j010_silent_on_host_loop_timing():
    # the sanctioned shape: clocks around the dispatch, on the host loop
    assert not fires("""
        import time
        import jax
        step = jax.jit(fused)
        def drive(ts, chunks):
            for chunk in chunks:
                t0 = time.perf_counter()
                ts = step(ts, chunk)
                record(time.perf_counter() - t0)
        """, "J010")
    # span stamping at the host consume site is exactly the design
    assert not fires("""
        import jax
        from apex_tpu.obs import spans as obs_spans
        step = jax.jit(fused)
        def consume(ts, slot):
            obs_spans.stamp_spans(slot.spans, "consume")
            return step(ts, slot.payload)
        """, "J010")


def test_j010_silent_on_non_time_receivers():
    # x.time() on an arbitrary receiver is not a clock read
    assert not fires("""
        import jax
        @jax.jit
        def fused_step(ts, sched):
            return ts, sched.time(3)
        """, "J010")
    # .complete on a non-ring receiver is out of scope
    assert not fires("""
        import jax
        @jax.jit
        def train_step(ts, task):
            task.complete("done", 0, 1)
            return ts
        """, "J010")


# -- J011: pjit/shard_map sharding-annotation drift --------------------------

def test_j011_fires_on_undeclared_axis_in_shard_map_specs():
    # the drift: make_mesh declares ("dp", "tp"), the step annotates "mp"
    assert fires("""
        from jax.sharding import PartitionSpec as P
        from apex_tpu.parallel.mesh import make_mesh, shard_map_compat
        mesh = make_mesh(dp=4)
        step = shard_map_compat(train, mesh=mesh,
                                in_specs=(P(), P("mp")),
                                out_specs=P("mp"))
        """, "J011")


def test_j011_fires_on_undeclared_axis_in_named_sharding():
    assert fires("""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(devices, ("dp", "tp"))
        sharding = NamedSharding(mesh, P("model"))
        """, "J011")


def test_j011_fires_on_fused_dp_axis_drift():
    # the PR 17 fused-plane idiom — replay state sharded over the dp
    # mesh via NamedSharding + a shard_map'd per-chip step: an axis
    # name the mesh never declared degrades every pool partition to
    # replication silently
    assert fires("""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from apex_tpu.parallel.mesh import make_mesh, shard_map_compat
        mesh = make_mesh(dp=2)
        shard = NamedSharding(mesh, P("data"))
        step = shard_map_compat(per_chip, mesh=mesh,
                                in_specs=(P(), P("data")),
                                out_specs=(P(), P("data")),
                                check_vma=False)
        """, "J011")


def test_j011_silent_on_declared_axes():
    assert not fires("""
        from jax.sharding import PartitionSpec as P
        from apex_tpu.parallel.mesh import make_mesh, shard_map_compat
        mesh = make_mesh(dp=4)
        step = shard_map_compat(train, mesh=mesh,
                                in_specs=(P(), P("dp"), P(("dp", "tp"))),
                                out_specs=P("dp"))
        """, "J011")


def test_j011_silent_without_mesh_vocabulary():
    # no mesh declared or imported: the rule cannot judge drift
    assert not fires("""
        from jax.sharding import PartitionSpec as P
        step = wrap(train, in_specs=(P("rows"),), out_specs=P("rows"))
        """, "J011")


def test_j011_silent_on_specs_outside_annotation_surfaces():
    # a P(...) passed to arbitrary helpers is not an annotation surface
    # (axis names there are that helper's business)
    assert not fires("""
        from jax.sharding import PartitionSpec as P
        from apex_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(dp=4)
        layout = describe_layout(P("whatever"))
        """, "J011")


# -- J012: cross-process port collisions in one topology ---------------------

def test_j012_fires_on_duplicate_ports_in_one_config_call():
    assert fires("""
        from apex_tpu.config import CommsConfig
        comms = CommsConfig(batch_port=51001, param_port=51001)
        """, "J012")


def test_j012_fires_on_duplicate_port_defaults_in_a_config_class():
    assert fires("""
        from dataclasses import dataclass
        @dataclass(frozen=True)
        class MyComms:
            batch_port: int = 51001
            prios_port: int = 51002
            replay_port_base: int = 51001
        """, "J012")


def test_j012_silent_on_distinct_ports_and_nonport_duplicates():
    # distinct ports are the healthy topology; equal NON-port ints (hwm,
    # window sizes) are not a collision
    assert not fires("""
        from apex_tpu.config import CommsConfig
        comms = CommsConfig(batch_port=51001, param_port=52001,
                            param_hwm=3, max_outstanding_sends=3)
        """, "J012")


def test_j012_silent_on_variable_and_zero_ports():
    # test fixtures bind ephemeral ports through variables, and 0 means
    # disabled/ephemeral — neither is a literal topology
    assert not fires("""
        from apex_tpu.config import CommsConfig
        batch, param = free_ports(2)
        a = CommsConfig(batch_port=batch, param_port=param)
        b = CommsConfig(batch_port=0, param_port=0)
        """, "J012")


# -- J013: zmq socket touched from two thread-entry methods ------------------

def test_j013_fires_on_socket_shared_by_two_thread_entries():
    assert fires("""
        import threading
        import zmq
        class Bad:
            def __init__(self):
                self.sock = zmq.Context.instance().socket(zmq.ROUTER)
                self._recv = threading.Thread(target=self._recv_loop)
                self._acker = threading.Thread(target=self._ack_loop)
            def _recv_loop(self):
                while True:
                    self.sock.recv_multipart()
            def _ack_loop(self):
                while True:
                    self.sock.send(b"ack")
        """, "J013")


def test_j013_fires_through_intra_class_helper_calls():
    # the touch lives in a helper; both thread entries reach it through
    # the class-local call graph — still two threads on one socket
    assert fires("""
        import threading
        import zmq
        class Bad:
            def __init__(self, ctx):
                self.sock = ctx.socket(zmq.DEALER)
                threading.Thread(target=self._a).start()
                threading.Thread(target=self._b).start()
            def _flush(self):
                self.sock.send(b"x")
            def _a(self):
                self._flush()
            def _b(self):
                self._flush()
        """, "J013")


def test_j013_silent_on_queue_handoff_pattern():
    # the ChunkReceiver shape: decoders enqueue acks, ONE socket thread
    # drains the queue and touches the socket — single-owner, clean
    assert not fires("""
        import queue
        import threading
        import zmq
        class Good:
            def __init__(self):
                self.sock = zmq.Context.instance().socket(zmq.ROUTER)
                self._ack_q = queue.Queue()
                self._recv = threading.Thread(target=self._run)
                self._decoders = [threading.Thread(target=self._decode)
                                  for _ in range(4)]
            def _run(self):
                while True:
                    self.sock.recv_multipart()
                    ident = self._ack_q.get_nowait()
                    self.sock.send_multipart([ident, b"ack"])
            def _decode(self):
                while True:
                    self._ack_q.put(b"peer")
        """, "J013")


def test_j013_silent_on_single_thread_and_main_thread_teardown():
    # one thread entry owning the socket + main-thread stop()/close() is
    # the documented migrate-then-use pattern, not a race the rule flags
    assert not fires("""
        import threading
        import zmq
        class Good:
            def __init__(self):
                self.sock = zmq.Context.instance().socket(zmq.REP)
                self._thread = threading.Thread(target=self._serve)
            def _serve(self):
                while True:
                    self.sock.recv()
                    self.sock.send(b"ok")
            def stop(self):
                self.sock.close(linger=0)
        """, "J013")


def test_j013_silent_on_two_threads_two_sockets():
    assert not fires("""
        import threading
        import zmq
        class Good:
            def __init__(self, ctx):
                self.rx = ctx.socket(zmq.PULL)
                self.tx = ctx.socket(zmq.PUSH)
                threading.Thread(target=self._rx_loop).start()
                threading.Thread(target=self._tx_loop).start()
            def _rx_loop(self):
                self.rx.recv()
            def _tx_loop(self):
                self.tx.send(b"x")
        """, "J013")


# -- J014: host numpy op in a lax.scan-scanned env/rollout body --------------

def test_j014_fires_on_np_in_scan_body():
    assert fires("""
        import jax
        import numpy as np
        def rollout(state, keys):
            def body(carry, key):
                pos = np.clip(carry + 1, 0, 10)
                return pos, pos
            return jax.lax.scan(body, state, keys)
        """, "J014")


def test_j014_fires_through_lambda_and_method_closure():
    # the anakin shape: lax.scan(lambda c, x: self._step(...)) — the
    # method and its callees are scanned scope via the call graph
    assert fires("""
        import jax
        import numpy as np
        class Engine:
            def _flush(self, c):
                return np.concatenate([c, c])
            def _step(self, c, x):
                return self._flush(c), x
            def _dispatch(self, c, xs):
                return jax.lax.scan(lambda cc, x: self._step(cc, x),
                                    c, xs)
        """, "J014")


def test_j014_fires_on_float_and_item():
    assert fires("""
        import jax
        def rollout(state, keys):
            def body(carry, key):
                r = float(carry)
                return carry, r
            return jax.lax.scan(body, state, keys)
        """, "J014")
    assert fires("""
        import jax
        def rollout(state, keys):
            def body(carry, key):
                return carry, carry.item()
            return jax.lax.scan(body, state, keys)
        """, "J014")


def test_j014_silent_outside_scan_and_on_static_args():
    # np on the host side of the dispatch is the NORMAL pattern
    assert not fires("""
        import jax
        import numpy as np
        def host_convert(out):
            return np.asarray(out)
        def rollout(state, keys):
            def body(carry, key):
                return carry + 1, carry
            return jax.lax.scan(body, state, keys)
        """, "J014")
    # static shape/config construction at trace time is legitimate
    assert not fires("""
        import jax
        import numpy as np
        class Engine:
            def _step(self, c, x):
                d = np.prod(self.frame_shape)
                ar = np.arange(self.B)
                return c, d
            def _dispatch(self, c, xs):
                return jax.lax.scan(lambda cc, x: self._step(cc, x),
                                    c, xs)
        """, "J014")


def test_j014_silent_on_jnp_in_scan_body():
    assert not fires("""
        import jax
        import jax.numpy as jnp
        def rollout(state, keys):
            def body(carry, key):
                return jnp.clip(carry + 1, 0, 10), carry
            return jax.lax.scan(body, state, keys)
        """, "J014")


# -- J015: literal gauge/family names outside the metric registry ------------

def test_j015_fires_on_unregistered_heartbeat_gauge_key():
    findings = run_rule("""
        from apex_tpu.fleet.heartbeat import Heartbeat
        def beat():
            return Heartbeat("infer-0", gauges={"queue_depth": 1,
                                                "totally_novel_gauge": 2})
        """, "J015")
    assert len(findings) == 1
    assert "totally_novel_gauge" in findings[0].message


def test_j015_fires_on_gauges_fn_lambda_and_named_hook():
    # the run_loadgen shape: gauges_fn=lambda returning a literal dict
    assert fires("""
        from apex_tpu.fleet.heartbeat import HeartbeatEmitter
        def loop():
            beat = HeartbeatEmitter(
                "loadgen-0", gauges_fn=(lambda: {"bogus_counter": 1}))
        """, "J015")
    # the anakin shape: gauges_fn=self.method, method returns a literal
    assert fires("""
        from apex_tpu.fleet.heartbeat import HeartbeatEmitter
        class Pool:
            def my_counters(self):
                return {"not_in_registry": 3}
            def start(self):
                self.hb = HeartbeatEmitter(
                    "x", gauges_fn=self.my_counters)
        """, "J015")


def test_j015_fires_on_unregistered_exposition_family():
    assert fires("""
        from apex_tpu.obs.metrics import render
        def expo():
            labeled = {"my_adhoc_family": [({"x": "y"}, 1.0)]}
            return render(labeled=labeled)
        """, "J015")


def test_j015_silent_on_registered_keys_and_dynamic_names():
    # every key declared in the registry: the normal emitter shape
    assert not fires("""
        from apex_tpu.fleet.heartbeat import Heartbeat
        def beat(depth):
            return Heartbeat("infer-0", gauges={"queue_depth": depth,
                                                "batch_p50": 1.5,
                                                "infer_rt_ms_p99": 2.0})
        """, "J015")
    # dynamic keys are not literal dataflow — scalar tails, per-peer
    # dicts, comprehensions all pass through untouched
    assert not fires("""
        from apex_tpu.obs.metrics import render
        def expo(history):
            gauges = {tag: dq[-1] for tag, dq in history.items()}
            counters = dict(build_counters())
            return render(gauges=gauges, counters=counters)
        """, "J015")


def test_j015_silent_on_gauge_keys_in_plain_dicts():
    # a dict literal that never flows into a gauges/exposition sink is
    # just a dict — the rule follows sinks, not spellings
    assert not fires("""
        def stats():
            return {"anything_goes_here": 1, "free_form": 2}
        """, "J015")


# -- J016: raw epoch/version ordering outside the fencing helpers ------------

def test_j016_fires_on_raw_epoch_ordering():
    # the replay-shard shape: an attribute epoch ordered against a local
    assert fires("""
        class Shard:
            def write_back(self, epoch):
                if epoch < self.learner_epoch:
                    return False
        """, "J016")
    # param_version too, and bare names count as well as attributes
    assert fires("""
        def gate(incoming, param_version):
            return incoming.param_version >= param_version
        """, "J016")


def test_j016_silent_on_equality_literals_and_fence_module():
    # identity checks are not ordering — fencing only cares about </>
    assert not fires("""
        class Shard:
            def seen(self, epoch):
                return epoch == self.learner_epoch
        """, "J016")
    # ordering against a LITERAL (test progress assertions like
    # `param_version >= 2`) cannot smuggle a dead life's value
    assert not fires("""
        def check(trainer):
            assert trainer.param_version >= 2
            assert trainer.learner_epoch > 0
        """, "J016")
    # THE fencing helper module is the one place raw ordering lives
    src = textwrap.dedent("""
        def newer_epoch(epoch, learner_epoch):
            return epoch > learner_epoch
        """)
    findings, _ = analyze_source(
        src, path="apex_tpu/serving/fence.py",
        rules={"J016": all_rules()["J016"]})
    assert not findings


def test_j016_fires_on_epoch_vs_version_cross_compare():
    # the exact wrong-lifetime hazard: ordering a version against an
    # epoch variable as if they shared a scale
    assert fires("""
        def promote(reply, server):
            if reply.learner_epoch >= server.param_version:
                return True
        """, "J016")


# -- J017: tenant-qualified id construction outside tenancy/namespace --------

def test_j017_fires_on_fstring_and_concat_and_join():
    # the qualified-identity shape: tenant joined to a base with "/"
    assert fires("""
        def route(tenant, actor_id):
            return f"{tenant}/actor-{actor_id}"
        """, "J017")
    # the topic shape: tenant between the apxt/ head and the | tail
    assert fires("""
        def topic(tenant):
            return "apxt/" + tenant + "|"
        """, "J017")
    # join and format spellings of the same construction
    assert fires("""
        def ident(spec_tenant, base):
            return "/".join([spec_tenant, base])
        """, "J017")
    assert fires("""
        def ident(spec, base):
            return "{}/{}".format(spec.tenant, base)
        """, "J017")


def test_j017_silent_on_logs_helpers_and_namespace_module():
    # a log line MENTIONING a tenant is not an id — no separator join
    assert not fires("""
        def log(tenant, n):
            print(f"tenant {tenant} admitted ({n} shards)")
        """, "J017")
    # routing through the namespacing helpers is the fix, not a finding
    assert not fires("""
        from apex_tpu.tenancy import namespace
        def route(tenant, base):
            return namespace.qualify(tenant, base)
        """, "J017")
    # a "/" elsewhere in the f-string (not adjacent to the tenant hole)
    # is not a qualified-id join
    assert not fires("""
        def log(tenant, a, b):
            print(f"shards {a}/{b} assigned to tenant {tenant}")
        """, "J017")
    # THE namespacing module is the one construction site
    src = textwrap.dedent("""
        def qualify(tenant, base):
            return f"{tenant}/{base}"
        """)
    findings, _ = analyze_source(
        src, path="apex_tpu/tenancy/namespace.py",
        rules={"J017": all_rules()["J017"]})
    assert not findings


def test_j017_one_finding_per_concat_chain():
    src = """
        def topic(tenant):
            return "apxt/" + tenant + "|"
        """
    assert len(run_rule(src, "J017")) == 1


# -- J018: replay residency/quota accounting outside the shard core ----------

def test_j018_fires_on_handrolled_residency_and_raw_quota_compare():
    # the resident() shape hand-rolled: residency saturates at ring
    # capacity, and a scattered min() is how two planes drift
    assert fires("""
        def admitted(core):
            return min(core.ingested, core.capacity)
        """, "J018")
    assert fires("""
        class Gate:
            def room(self):
                return min(self.ingested, self.replay.capacity)
        """, "J018")
    # quota judged against raw cumulative ingest: wrong once the ring
    # wraps (ingested grows forever, residency stopped at capacity)
    assert fires("""
        class Gate:
            def over(self):
                return self.ingested >= self.quota
        """, "J018")
    assert fires("""
        def over(core, spec):
            return core.ingested > spec.replay_quota
        """, "J018")


def test_j018_silent_on_accessors_literals_and_shard_module():
    # routing through the core's accessors is the fix, not a finding
    assert not fires("""
        def over(core):
            return core.resident() >= core.quota
        """, "J018")
    assert not fires("""
        def over(core):
            return core.over_quota()
        """, "J018")
    # ordering against literals (test progress asserts) is not
    # accounting; min() of unrelated names is just math
    assert not fires("""
        def check(core):
            assert core.ingested >= 100
            return min(1.0, core.ingested / 500)
        """, "J018")
    # equality is identity, not accounting
    assert not fires("""
        def same(core, spec):
            return core.quota == spec.replay_quota
        """, "J018")
    # THE accounting module is the one place residency math lives
    src = textwrap.dedent("""
        class ReplayShardCore:
            def resident(self):
                return min(self.ingested, self.replay.capacity)

            def over_quota(self):
                return self.quota > 0 and self.resident() >= self.quota
        """)
    findings, _ = analyze_source(
        src, path="apex_tpu/replay_service/shard.py",
        rules={"J018": all_rules()["J018"]})
    assert not findings


# -- engine: parse errors, suppressions, baseline ---------------------------

def test_parse_error_is_a_finding():
    findings, _ = analyze_source("def broken(:\n", path="x.py")
    assert [f.rule for f in findings] == ["E001"]


def test_inline_suppression_with_justification():
    src = textwrap.dedent("""
        import jax
        def f(key):
            a = jax.random.normal(key, (2,))
            b = jax.random.normal(key, (2,))  # apexlint: disable=J004 -- deliberate same-draw
            return a + b
        """)
    findings, suppressed = analyze_source(src, path="x.py")
    assert not any(f.rule == "J004" for f in findings)
    assert any(f.rule == "J004" for f in suppressed)


def test_standalone_suppression_covers_next_line():
    src = textwrap.dedent("""
        import jax
        def f(key):
            a = jax.random.normal(key, (2,))
            # apexlint: disable=J004 -- deliberate same-draw
            b = jax.random.normal(key, (2,))
            return a + b
        """)
    findings, suppressed = analyze_source(src, path="x.py")
    assert not any(f.rule == "J004" for f in findings)
    assert len(suppressed) == 1


def test_suppression_is_rule_scoped():
    # a J004 disable must NOT hide a J002 on the same line
    src = textwrap.dedent("""
        import jax
        @jax.jit
        def train_step(ts, key):
            a = jax.random.normal(key, (2,))
            b = float(jax.random.normal(key, (2,)).sum())  # apexlint: disable=J004
            return a, b
        """)
    findings, _ = analyze_source(src, path="x.py")
    assert any(f.rule == "J002" for f in findings)
    assert not any(f.rule == "J004" for f in findings)


def test_baseline_partition_and_staleness():
    src = textwrap.dedent("""
        import jax
        def f(key):
            a = jax.random.normal(key, (2,))
            b = jax.random.normal(key, (2,))
            return a + b
        """)
    findings, _ = analyze_source(src, path="m.py")
    assert findings
    base = Baseline.from_findings(findings)
    new, matched, stale = base.partition(findings)
    assert not new and matched and not stale
    # fixed code -> the entry goes stale (strict mode fails on it)
    new, matched, stale = base.partition([])
    assert not new and not matched and stale


def test_baseline_line_number_drift_still_matches():
    src = ("import jax\n"
           "def f(key):\n"
           "    a = jax.random.normal(key, (2,))\n"
           "    b = jax.random.normal(key, (2,))\n"
           "    return a + b\n")
    findings, _ = analyze_source(src, path="m.py")
    base = Baseline.from_findings(findings)
    shifted, _ = analyze_source("# new header comment\n" + src, path="m.py")
    new, matched, stale = base.partition(shifted)
    assert not new and matched and not stale


# -- J019: learner state mutated from a FleetStatusServer hook ---------------

def test_j019_fires_on_state_mutation_in_ctl_hook():
    # the anti-pattern the rule exists for: the ctl hook applies the
    # weight copy on the status-server thread, racing the hot loop
    assert fires("""
        class Trainer:
            def _serve(self):
                self._fleet_status = FleetStatusServer(
                    comms, self.fleet, ctl_fn=self._on_ctl)

            def _on_ctl(self, cmd):
                self.train_state = self._load(cmd["path"])
                return {"accepted": True}
        """, "J019")
    # calling a trainer-thread applier from the hook is the same race
    assert fires("""
        class Trainer:
            def _serve(self):
                self._fleet_status = FleetStatusServer(
                    comms, self.fleet, ctl_fn=self._on_ctl)

            def _on_ctl(self, cmd):
                self.restore_weights(cmd["path"])
                return {"accepted": True}
        """, "J019")
    # one level of same-class delegation is followed
    assert fires("""
        class Trainer:
            def _serve(self):
                self._fleet_status = FleetStatusServer(
                    comms, self.fleet, snapshot_fn=self._snap)

            def _snap(self):
                return self._refresh()

            def _refresh(self):
                self.replay_state = self._rebuild()
                return {}
        """, "J019")
    # lambda hooks are inspected inline
    assert fires("""
        class Trainer:
            def _serve(self):
                self._fleet_status = FleetStatusServer(
                    comms, self.fleet,
                    ctl_fn=lambda cmd: self.apply_hparams(cmd))
        """, "J019")


def test_j019_silent_on_enqueue_and_drain_pattern():
    # the PR 14 contract: the hook ENQUEUES only; the trainer thread
    # drains on its health tick — reads and queue puts are fine
    assert not fires("""
        class Trainer:
            def _serve(self):
                self._fleet_status = FleetStatusServer(
                    comms, self.fleet, ctl_fn=self._enqueue,
                    metrics_fn=self._metrics, snapshot_fn=self._snap)

            def _enqueue(self, cmd):
                try:
                    self._ctl_queue.put_nowait(dict(cmd))
                except Exception:
                    return {"accepted": False}
                return {"accepted": True, "pending": self._ctl_queue.qsize()}

            def _metrics(self):
                return render(gauges=dict(steps=self.steps_rate.total))

            def _snap(self):
                snap = self.fleet.snapshot()
                snap["metrics"]["learner_epoch"] = self.learner_epoch
                return snap
        """, "J019")
    # state mutation on the TRAINER thread (no hook involvement) is the
    # correct half of the pattern, not a finding
    assert not fires("""
        class Trainer:
            def _drain(self, steps):
                cmd = self._ctl_queue.get_nowait()
                self.train_state = self._load(cmd["path"])
        """, "J019")


# -- CLI --------------------------------------------------------------------

def _write(tmp_path, name, content):
    p = tmp_path / name
    p.write_text(textwrap.dedent(content))
    return str(p)


def test_cli_exit_codes_and_json(tmp_path, capsys):
    bad = _write(tmp_path, "bad.py", """
        import jax
        def f(key):
            a = jax.random.normal(key, (2,))
            b = jax.random.normal(key, (2,))
            return a + b
        """)
    assert main([bad, "--no-baseline", "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["summary"]["new"] == 1
    assert out["findings"][0]["rule"] == "J004"

    good = _write(tmp_path, "good.py", "x = 1\n")
    assert main([good, "--no-baseline"]) == 0
    assert main(["--list-rules"]) == 0
    assert main([str(tmp_path / "missing.py")]) == 2
    assert main([bad, "--disable", "NOPE"]) == 2
    assert main([bad, "--no-baseline", "--disable", "J004"]) == 0


def test_cli_write_baseline_roundtrip(tmp_path, capsys):
    bad = _write(tmp_path, "bad.py", """
        import jax
        def f(key):
            a = jax.random.normal(key, (2,))
            b = jax.random.normal(key, (2,))
            return a + b
        """)
    base = str(tmp_path / "base.json")
    assert main([bad, "--baseline", base, "--write-baseline"]) == 0
    assert main([bad, "--baseline", base]) == 0          # accepted
    capsys.readouterr()


def test_pyproject_config_is_read():
    cfg = load_config(REPO)
    assert "apex_tpu" in cfg.get("paths", [])
    assert cfg.get("baseline") == ".apexlint-baseline.json"


def test_every_rule_has_registry_metadata():
    rules = all_rules()
    assert {"J001", "J002", "J003", "J004", "J005",
            "C001", "C002", "C003", "C004"} <= set(rules)
    for rid, rule in rules.items():
        assert rule.id == rid and rule.name and rule.description


# -- self-check: the repo lints clean against its baseline ------------------

def test_repo_lints_clean_strict():
    """The merge gate: zero unsuppressed findings, zero stale baseline
    entries, over the configured [tool.apexlint] scope — exactly what CI
    runs.  A subprocess so the CLI path (module main, config discovery,
    baseline load) is exercised end to end."""
    proc = subprocess.run(
        [sys.executable, "-m", "apex_tpu.analysis", "--strict"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_acceptance_command_package_scope():
    """`python -m apex_tpu.analysis apex_tpu/` exits 0 (the README/issue
    invocation): the package itself carries zero findings, with no
    baseline help needed."""
    proc = subprocess.run(
        [sys.executable, "-m", "apex_tpu.analysis", "apex_tpu",
         "--no-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr

# -- J020: donation aliasing (whole-program dataflow) -----------------------

def test_j020_fires_on_post_dispatch_read():
    assert fires("""
        import jax

        class Learner:
            def __init__(self, step):
                self._step = jax.jit(step, donate_argnums=(0,))

            def run(self, batch):
                out = self._step(self.train_state, batch)
                return float(self.train_state.loss)
        """, "J020")


def test_j020_silent_on_rebind_epilogue():
    assert not fires("""
        import jax

        class Learner:
            def __init__(self, step):
                self._step = jax.jit(step, donate_argnums=(0,))

            def run(self, batch):
                self.train_state, metrics = self._step(self.train_state,
                                                       batch)
                return metrics
        """, "J020")


def test_j020_fires_on_loop_carried_redispatch():
    found = run_rule("""
        import jax

        class Learner:
            def __init__(self, step):
                self._step = jax.jit(step, donate_argnums=(0,))

            def run(self, batches):
                metrics = None
                for b in batches:
                    metrics = self._step(self.train_state, b)
                return metrics
        """, "J020")
    assert found and "loop iteration" in found[0].message


def test_j020_silent_when_loop_rebinds():
    assert not fires("""
        import jax

        class Learner:
            def __init__(self, step):
                self._step = jax.jit(step, donate_argnums=(0,))

            def run(self, batches):
                for b in batches:
                    self.train_state, m = self._step(self.train_state, b)
                return m
        """, "J020")


def test_j020_tracks_decorated_and_factory_donation():
    # @partial decoration and factory-returned jits both register
    assert fires("""
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def step(state, batch):
            return state

        def drive(state, batch):
            out = step(state, batch)
            return state.params
        """, "J020")
    assert fires("""
        import jax

        def make(step):
            return jax.jit(step, donate_argnums=(0,))

        class T:
            def __init__(self, step):
                self._train = make(step)

            def run(self, batch):
                out = self._train(self.train_state, batch)
                return self.train_state
        """, "J020")


def test_j020_silent_on_undonated_jit():
    assert not fires("""
        import jax

        class Learner:
            def __init__(self, step):
                self._step = jax.jit(step)

            def run(self, batch):
                out = self._step(self.train_state, batch)
                return float(self.train_state.loss)
        """, "J020")


# -- J021: band membership --------------------------------------------------

def test_j021_fires_on_raw_crc32_shard_arith():
    assert fires("""
        import zlib

        def route(identity, n_shards):
            return zlib.crc32(identity.encode()) % n_shards
        """, "J021")


def test_j021_fires_on_wrapped_hash_of_identity():
    assert fires("""
        def route(tenant_id, n):
            return abs(hash(tenant_id)) % n
        """, "J021")


def test_j021_silent_on_constant_modulus_and_round_robin():
    # seed masks / range clamps use literal moduli; round-robin isn't a hash
    assert not fires("""
        import zlib

        def seed_of(name):
            return zlib.crc32(name.encode()) % 2 ** 31
        """, "J021")
    assert not fires("""
        class S:
            def pick(self, n_shards):
                self._seq += 1
                return self._seq % n_shards
        """, "J021")


def test_j021_exempts_the_tenancy_namespace_module():
    src = textwrap.dedent("""
        import zlib

        def shard_in_band(identity, band):
            return band[zlib.crc32(identity.encode()) % len(band)]
        """)
    rules = {"J021": all_rules()["J021"]}
    findings, _ = analyze_source(src, path="apex_tpu/tenancy/namespace.py",
                                 rules=rules)
    assert not findings
    findings, _ = analyze_source(src, path="elsewhere.py", rules=rules)
    assert findings


# -- J022: fence ordering ---------------------------------------------------

def test_j022_fires_on_handbuilt_fence_tuple():
    found = run_rule("""
        class Server:
            def snapshot(self):
                return (self.learner_epoch, self.param_version)
        """, "J022")
    assert found and "fence" in found[0].message
    # transposed pairs are the same hazard (that's the point)
    assert fires("""
        def key(st):
            return (st.param_version, st.learner_epoch)
        """, "J022")


def test_j022_silent_on_parallel_assign_snapshot():
    assert not fires("""
        class Server:
            def read(self):
                pv, epoch = self.param_version, self.learner_epoch
                return pv
        """, "J022")


def test_j022_silent_on_non_fence_tuples_and_fence_module():
    assert not fires("""
        def f(st):
            return (st.learner_epoch, st.other)
        """, "J022")
    src = textwrap.dedent("""
        def fence_key(st):
            return (st.learner_epoch, st.param_version)
        """)
    findings, _ = analyze_source(src, path="apex_tpu/serving/fence.py",
                                 rules={"J022": all_rules()["J022"]})
    assert not findings


# -- J023: codec outside the codec module -----------------------------------

def test_j023_fires_on_raw_zlib_compress_of_payload():
    assert fires("""
        import zlib

        def ship(sock, payload):
            sock.send(zlib.compress(payload))
        """, "J023")
    assert fires("""
        import zlib

        def unship(blob):
            return zlib.decompress(blob)
        """, "J023")


def test_j023_fires_on_handrolled_frame_xor_delta():
    assert fires("""
        import numpy as np

        def delta(frames):
            return frames[1:] ^ frames[:-1]
        """, "J023")
    assert fires("""
        import numpy as np

        def delta(frames, prev):
            return np.bitwise_xor(frames, prev)
        """, "J023")


def test_j023_silent_on_checksums_and_seed_xor():
    # crc32/adler32 are checksums, not compression (J021 owns hash
    # routing) — and XOR over seeds/identities is arithmetic, not a codec
    assert not fires("""
        import zlib

        def route(identity, band):
            return band[zlib.crc32(identity.encode()) % len(band)]
        """, "J023")
    assert not fires("""
        import zlib

        class Chaos:
            def rng(self):
                return self.seed ^ zlib.crc32(self.identity.encode())
        """, "J023")


def test_j023_exempts_the_codec_module():
    src = textwrap.dedent("""
        import zlib

        def _frames_encode(frames):
            return zlib.compress(frames.tobytes())
        """)
    rules = {"J023": all_rules()["J023"]}
    findings, _ = analyze_source(src, path="apex_tpu/runtime/codec.py",
                                 rules=rules)
    assert not findings
    findings, _ = analyze_source(src, path="elsewhere.py", rules=rules)
    assert findings


# -- C006: cross-module thread affinity -------------------------------------

_C006_READER = """
    import jax

    class Engine:
        @jax.jit
        def step(self, x):
            return x + self.core
    """


def _c006_run(tmp_path, ctl_src):
    from apex_tpu.analysis import analyze_paths
    (tmp_path / "ctl.py").write_text(textwrap.dedent(ctl_src))
    (tmp_path / "engine.py").write_text(textwrap.dedent(_C006_READER))
    rules = {"C006": all_rules()["C006"]}
    findings, _ = analyze_paths([str(tmp_path)], rules=rules,
                                root=str(tmp_path))
    return findings


def test_c006_fires_on_thread_reachable_unlocked_mutation(tmp_path):
    found = _c006_run(tmp_path, """
        import threading

        class Ctl:
            def start(self):
                self.t = threading.Thread(target=self._loop)
                self.t.start()

            def _loop(self):
                self.core = None
        """)
    assert [f.rule for f in found] == ["C006"]
    assert "engine.py" in found[0].message


def test_c006_silent_under_lock_and_off_thread(tmp_path):
    assert not _c006_run(tmp_path, """
        import threading

        class Ctl:
            def start(self):
                self.t = threading.Thread(target=self._loop)
                self.t.start()

            def _loop(self):
                with self._state_lock:
                    self.core = None
        """)
    # same mutation NOT reachable from a Thread spawn: trainer-thread code
    assert not _c006_run(tmp_path, """
        class Ctl:
            def reset(self):
                self.core = None
        """)


def test_c006_needs_the_project_context():
    # lone-snippet analysis has no cross-module view: the rule stays quiet
    assert not fires("""
        import threading

        class Ctl:
            def start(self):
                threading.Thread(target=self._loop).start()

            def _loop(self):
                self.core = None
        """, "C006")


# -- ProjectContext: graphs and dataflow ------------------------------------

def test_project_context_import_and_call_graphs():
    from apex_tpu.analysis.graph import ProjectContext
    proj = ProjectContext({
        "pkg/__init__.py": "",
        "pkg/a.py": "from pkg.b import helper\n\n"
                    "def run():\n    return helper()\n",
        "pkg/b.py": "def helper():\n    return 1\n",
    })
    assert "pkg.b" in proj.import_graph["pkg.a"]
    assert "pkg.b.helper" in proj.call_graph["pkg.a.run"]
    assert "pkg.b.helper" in proj.definitions


def test_project_context_thread_reachability():
    from apex_tpu.analysis.graph import ProjectContext
    proj = ProjectContext({
        "m.py": textwrap.dedent("""
            import threading

            def work():
                helper()

            def helper():
                pass

            def main():
                threading.Thread(target=work).start()
            """),
    })
    assert "m.work" in proj.thread_targets
    # the closure follows call-graph edges out of the spawn target
    assert {"m.work", "m.helper"} <= proj.thread_reachable
    assert "m.main" not in proj.thread_reachable


def test_reaching_defs_branch_union_and_params():
    import ast as _a

    from apex_tpu.analysis.dataflow import reaching_defs
    fn = _a.parse(textwrap.dedent("""
        def f(x, cond):
            y = x + 1
            if cond:
                y = 2
            return y
        """)).body[0]
    defs = reaching_defs(fn)
    ret_y = [n for n in defs if n.id == "y"]
    assert ret_y and len(defs[ret_y[-1]]) == 2      # both branches reach
    x_loads = [n for n in defs if n.id == "x"]
    assert x_loads and defs[x_loads[0]] == {fn}     # params reach as fn


def test_donated_callables_resolves_bindings_and_factories():
    from apex_tpu.analysis.core import ModuleContext
    from apex_tpu.analysis.dataflow import donated_callables
    ctx = ModuleContext("m.py", textwrap.dedent("""
        import jax

        def make(step):
            return jax.jit(step, donate_argnums=(0, 1))

        class T:
            def __init__(self, step):
                self._step = jax.jit(step, donate_argnums=(0,))
                self._train = make(step)
        """))
    d = donated_callables(ctx)
    assert d["self._step"].positions == (0,)
    assert d["self._train"].positions == (0, 1)


# -- SARIF artifact ---------------------------------------------------------

def test_sarif_report_shape(tmp_path, capsys):
    bad = _write(tmp_path, "bad.py", """
        import jax
        def f(key):
            a = jax.random.normal(key, (2,))
            b = jax.random.normal(key, (2,))
            return a + b
        """)
    sarif = tmp_path / "out.sarif"
    assert main([bad, "--no-baseline", "--sarif", str(sarif)]) == 1
    capsys.readouterr()
    doc = json.loads(sarif.read_text())
    assert doc["version"] == "2.1.0"
    assert doc["$schema"].endswith("sarif-2.1.0.json")
    run = doc["runs"][0]
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"J001", "J004", "J020", "J021", "J022", "J023", "C006"} <= rule_ids
    res = [r for r in run["results"] if r["ruleId"] == "J004"]
    assert res and res[0]["level"] == "error"
    loc = res[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("bad.py")
    assert loc["region"]["startLine"] > 0


def test_sarif_baselined_findings_are_suppressed_notes(tmp_path, capsys):
    bad = _write(tmp_path, "bad.py", """
        import jax
        def f(key):
            a = jax.random.normal(key, (2,))
            b = jax.random.normal(key, (2,))
            return a + b
        """)
    base = str(tmp_path / "base.json")
    assert main([bad, "--baseline", base, "--write-baseline"]) == 0
    sarif = tmp_path / "out.sarif"
    assert main([bad, "--baseline", base, "--sarif", str(sarif)]) == 0
    capsys.readouterr()
    run = json.loads(sarif.read_text())["runs"][0]
    res = [r for r in run["results"] if r["ruleId"] == "J004"]
    assert res and res[0]["level"] == "note"
    assert res[0]["suppressions"][0]["kind"] == "external"


# -- config reader ----------------------------------------------------------

def test_config_multiline_array_with_comments(tmp_path):
    # regression: a per-item comment used to truncate the folded buffer
    # at its '#' and silently drop the whole key
    (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""
        [tool.apexlint]
        paths = [
            "apex_tpu",     # the package
            "tests",        # and its tests
        ]
        baseline = ".apexlint-baseline.json"
        disable = []

        [tool.other]
        x = "[not # ours]"
        """))
    cfg = load_config(str(tmp_path))
    assert cfg["paths"] == ["apex_tpu", "tests"]
    assert cfg["baseline"] == ".apexlint-baseline.json"
    assert cfg["disable"] == []


def test_config_bad_values_complain_loudly(tmp_path, capsys):
    (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""
        [tool.apexlint]
        paths = not-a-value (
        baseline = ".ok.json"
        """))
    cfg = load_config(str(tmp_path))
    err = capsys.readouterr().err
    assert "paths" in err and "ignored" in err
    assert cfg.get("baseline") == ".ok.json"    # later keys still parse


def test_config_unterminated_array_complains(tmp_path, capsys):
    (tmp_path / "pyproject.toml").write_text(
        "[tool.apexlint]\ndisable = [\n    \"J001\",\n")
    cfg = load_config(str(tmp_path))
    assert "disable" not in cfg
    assert "unterminated" in capsys.readouterr().err


def test_config_hash_inside_quoted_value_survives(tmp_path):
    (tmp_path / "pyproject.toml").write_text(
        '[tool.apexlint]\nbaseline = "base#1.json"  # real comment\n')
    assert load_config(str(tmp_path))["baseline"] == "base#1.json"


# -- catalog / explain ------------------------------------------------------

def test_catalog_covers_every_rule_with_why_and_fix():
    from apex_tpu.analysis import catalog
    entries = {e["id"]: e for e in catalog()}
    assert set(entries) == set(all_rules())
    for e in entries.values():
        assert e["why"] and e["fix"], e["id"]


def test_explain_prints_why_and_fix(capsys):
    assert main(["--explain", "J021"]) == 0
    out = capsys.readouterr().out
    assert "J021" in out and "why:" in out and "fix:" in out
    assert main(["--explain", "NOPE"]) == 2
    capsys.readouterr()


def test_readme_rule_table_is_generated(capsys):
    """The README's rule table is the catalog_markdown() output verbatim
    (between the apexlint-catalog markers) — regenerate it with
    `python -m apex_tpu.analysis --catalog-md` after touching rules."""
    from apex_tpu.analysis import catalog_markdown
    readme = open(os.path.join(REPO, "README.md"), encoding="utf-8").read()
    start = readme.index("<!-- apexlint-catalog:start -->")
    end = readme.index("<!-- apexlint-catalog:end -->")
    block = readme[start:end].split("-->", 1)[1].strip("\n")
    assert block == catalog_markdown().strip("\n")


# -- --changed-only ---------------------------------------------------------

def test_changed_only_lints_just_the_diff_set(tmp_path, capsys):
    git = lambda *a: subprocess.run(
        ["git", "-C", str(tmp_path), *a], check=True, capture_output=True,
        env={**os.environ, "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
             "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"})
    git("init", "-q")
    (tmp_path / "pyproject.toml").write_text("[tool.apexlint]\n"
                                             "paths = [\".\"]\n")
    _write(tmp_path, "committed.py", """
        import jax
        def f(key):
            a = jax.random.normal(key, (2,))
            b = jax.random.normal(key, (2,))
            return a + b
        """)
    git("add", "-A")
    git("commit", "-qm", "seed")
    _write(tmp_path, "fresh.py", "x = 1\n")
    old = os.getcwd()
    os.chdir(tmp_path)
    try:
        # committed.py's J004 is invisible: only fresh.py is linted
        assert main(["--no-baseline", "--changed-only"]) == 0
        assert main(["--no-baseline"]) == 1
    finally:
        os.chdir(old)
    capsys.readouterr()
