#!/usr/bin/env python3
"""Record the small trace ``spans.py`` is checked on: six passes of a loop
that dispatches a small step named ``jit_fused_step`` (even passes) or
``jit_train_step`` (odd passes), each under the five scopes the program's
step carries (``ingest`` only in the fused one; a ``fori_loop`` inside
``sample``, one multiply under no scope), with the loop's annotations
around them as the program's ``TraceRing.span`` writes them
(``loop_iter`` > ``dispatch_key``, ``beta``, ``dispatch``) and the
profiler at ``host_tracer_level = 1``, on whatever device JAX has (the
committed fixture was taken on the TPU v5e).

    python3 benchmark/tests/record_scoped_fixture.py <out.xplane.pb>

writes the trace and, beside it, ``<out>.expected.json``: what was issued
(``recorded``) and what ``spans.reduce_file`` makes of the trace.
"""

import glob
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import spans  # noqa: E402

PASSES = 6


def train_step(w, tree, frames, key):
    w = w * 1.0001                                  # under no scope
    with jax.named_scope("sample"):
        u = jax.random.uniform(key, (32,)) * tree.sum()

        def descend(_, node):                       # a while on the device
            left = tree[jnp.minimum(2 * node, tree.shape[0] - 1)]
            return jnp.where(u < left, 2 * node, 2 * node + 1) % 256

        idx = jax.lax.fori_loop(0, 6, descend, jnp.ones((32,), jnp.int32))
    with jax.named_scope("gather"):
        x = frames[idx].astype(jnp.float32)

    def loss_fn(w):
        y = jax.lax.conv_general_dilated(
            x, w, (2, 2), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return (jnp.tanh(y) ** 2).mean()

    with jax.named_scope("update"):
        with jax.named_scope("loss_grad"):
            loss, g = jax.value_and_grad(loss_fn)(w)
        with jax.named_scope("optimizer"):
            w = w - 0.01 * g
    with jax.named_scope("writeback"):
        tree = tree.at[idx].set(jnp.abs(loss) + 1.0)
    return w, tree, frames


def fused_step(w, tree, frames, chunk, key):
    with jax.named_scope("ingest"):
        frames = jax.lax.dynamic_update_slice(frames, chunk, (0, 0, 0, 0))
    return train_step(w, tree, frames, key)


def main(out: str) -> int:
    w = jnp.ones((4, 4, 4, 8))
    tree = jnp.ones((512,))
    frames = jnp.ones((256, 20, 20, 4), jnp.uint8)
    chunk = jnp.ones((8, 20, 20, 4), jnp.uint8)
    key = jax.random.key(0)
    fused = jax.jit(fused_step, donate_argnums=(0, 1, 2))
    train = jax.jit(train_step, donate_argnums=(0, 1, 2))
    w, tree, frames = fused(w, tree, frames, chunk, key)
    w, tree, frames = train(w, tree, frames, key)
    jax.block_until_ready(w)
    tmp = tempfile.mkdtemp(dir=".")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    recorded = {"loop_iter": 0, "dispatch_key": 0, "beta": 0, "dispatch": 0,
                "programs": {}}
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for it in range(PASSES):
        program = "jit_fused_step" if it % 2 == 0 else "jit_train_step"
        with TraceAnnotation("loop_iter", it=it):
            with TraceAnnotation("dispatch_key", it=it):
                key, k = jax.random.split(key)
            with TraceAnnotation("beta", it=it):
                time.sleep(0.001)
            with TraceAnnotation("dispatch", it=it, program=program):
                if it % 2 == 0:
                    w, tree, frames = fused(w, tree, frames, chunk, k)
                else:
                    w, tree, frames = train(w, tree, frames, k)
            time.sleep(0.002)                       # the pass's own time
        for name in ("loop_iter", "dispatch_key", "beta", "dispatch"):
            recorded[name] += 1
        recorded["programs"][program] = recorded["programs"].get(
            program, 0) + 1
    jax.block_until_ready(w)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")[0], out)
    shutil.rmtree(tmp)
    red = spans.reduce_file(out, ("jit_fused_step", "jit_train_step"))
    with open(out[:-len(".xplane.pb")] + ".expected.json", "w") as f:
        json.dump(dict(device=jax.devices()[0].device_kind,
                       recorded=recorded, reduced=red), f, indent=1)
        f.write("\n")
    print(jax.devices()[0].device_kind, out, os.path.getsize(out), "bytes")
    print(json.dumps(red, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
