#!/usr/bin/env python3
"""Record the small trace ``xplane.py`` is checked on: five calls of a
small conv + matmul step named ``jit_train_step`` and three of
``jit_fused_step`` with a host pause between them, on whatever device
JAX has (the committed fixture was taken on the TPU v5e).

    python3 benchmark/tests/record_fixture.py <out.xplane.pb>
"""

import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    def train_step(w, x):
        y = jax.lax.conv_general_dilated(
            x, w, (2, 2), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.tanh(y).reshape(x.shape[0], -1) @ jnp.ones((y.shape[1] * y.shape[2] * 8, 16))

    def fused_step(w, x):
        return train_step(w + 1.0, x) * 2.0

    w = jnp.ones((4, 4, 4, 8))
    x = jnp.ones((32, 20, 20, 4))
    a, b = jax.jit(train_step), jax.jit(fused_step)
    a(w, x).block_until_ready()
    b(w, x).block_until_ready()
    tmp = tempfile.mkdtemp(dir=".")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for i in range(5):
        a(w, x).block_until_ready()
        if i < 3:
            time.sleep(0.002)
            b(w, x).block_until_ready()
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")[0], out)
    shutil.rmtree(tmp)
    print(jax.devices()[0].device_kind, out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
