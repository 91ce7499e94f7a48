"""The held experts' grouped products ALONE, on the chip: what XLA:TPU's
``lax.ragged_dot`` kernel and the megablox ``gmm`` / ``tgmm`` kernels take
at Nemotron's widths (hidden 2,688, experts 1,856) when they are handed the
published widths or widths zero-padded to a larger tile (PERF.md, PR 34).

A round of ``glm4_moe_lite.MoE.routed`` as ``twotowerq_ondevice`` runs it:
8 groups, 6,144 live rows (735-795 a group) in a buffer of 24,576, bfloat16
operands, float32 accumulation, two-matrix ``relu^2`` experts.  For every
variant it times, by the host clock around queued calls that end in
``block_until_ready``:

* the six kernels one by one (the up and the down product: forward, the
  gradient of the rows ``dlhs`` and of the weights ``drhs``), their
  operands made beforehand at the widths handed;
* the passes that make those operands: the bfloat16 cast of the stacked
  float32 kernels with the padding folded in, the gather of ``xs`` from
  ``h`` with mask and padding, the cut of a weight gradient back to the
  published shape;
* the whole: gather, both products and all four gradients from float32
  kernels of the published shapes, as one program (``round``), which is the
  number the variants are compared by.

    chiprun --timeout 1500 -- python3 scripts/grouped_sweep.py
    JAX_PLATFORMS=cpu python3 scripts/grouped_sweep.py --rehearsal

``--rehearsal`` runs toy sizes on the CPU (the megablox kernels in
interpret mode) to find faults; its times mean nothing and say so.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from apex_tpu.ops import grouped  # noqa: E402

GROUP_SIZES = (735, 795, 760, 776, 748, 790, 770, 770)     # 6,144 live
widen = grouped.widened


def ragged(xs, w, sizes):
    return grouped.product(xs, w, sizes)


def tiled_at(tiling):
    """The shipped wrapper of the megablox kernels
    (:func:`apex_tpu.ops.grouped.tiled`) at ``tiling = (rows, k, n)``."""
    return lambda xs, w, sizes: grouped.tiled(xs, w, sizes, tiling)


def timed(fn, args, calls, repeats=3):
    """Median over ``repeats`` of the seconds a call takes when ``calls``
    are queued one behind the other; the compile is not in it."""
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            y = fn(*args)
        jax.block_until_ready(y)
        out.append((time.perf_counter() - t0) / calls)
    return float(np.median(out))


def variant(name, products, d_to, f_to, shapes, calls):
    """One variant's times: ``products`` are the grouped product of the up
    and of the down matrix, at the widths ``d_to`` / ``f_to`` handed."""
    up_product, down_product = products
    rows, n_tok, d, f, e, sizes = shapes
    dt = jnp.bfloat16
    keys = jax.random.split(jax.random.key(0), 8)
    h = jax.random.normal(keys[0], (n_tok, d), dt)
    tok = jax.random.randint(keys[1], (rows,), 0, n_tok)
    up32 = 0.02 * jax.random.normal(keys[2], (e, d, f), jnp.float32)
    down32 = 0.02 * jax.random.normal(keys[3], (e, f, d), jnp.float32)
    live = (jnp.arange(rows) < sizes.sum())[:, None]
    cot = jax.random.normal(keys[4], (rows, d), jnp.float32)

    def cast(w, shape):
        return widen(w.astype(dt), shape)

    def gather(h, tok):
        return widen(jnp.where(live, h[tok], 0), (rows, d_to))

    def cut(dw):
        return dw[:, :d, :f]

    def one_round(h, up32, down32):
        xs = gather(h, tok)
        u = jnp.where(live, up_product(
            xs, cast(up32, (e, d_to, f_to)), sizes), 0.0)
        mid = jnp.square(jax.nn.relu(u)).astype(dt)
        y = jnp.where(live, down_product(
            mid, cast(down32, (e, f_to, d_to)), sizes), 0.0)
        return jnp.sum(y[:, :d] * cot)

    out = {"name": name, "hidden": d_to, "width": f_to,
           "mac_ratio": d_to * f_to / (d * f)}
    xs = jax.jit(gather)(h, tok)
    up, down = (jax.jit(cast, static_argnums=1)(w, s) for w, s in (
        (up32, (e, d_to, f_to)), (down32, (e, f_to, d_to))))
    mid = jax.random.normal(keys[5], (rows, f_to), dt)
    dy_u = jax.random.normal(keys[6], (rows, f_to), jnp.float32)
    dy_d = jax.random.normal(keys[7], (rows, d_to), jnp.float32)
    for tag, product, lhs, w, dy in (("up", up_product, xs, up, dy_u),
                                     ("down", down_product, mid, down,
                                      dy_d)):
        def f_(a, b, product=product):
            return product(a, b, sizes)
        out[f"{tag}_fwd_ms"] = 1e3 * timed(jax.jit(f_), (lhs, w), calls)
        for which, i in (("dlhs", 0), ("drhs", 1)):
            def g_(a, b, dy, i=i, f_=f_):
                return jax.vjp(f_, a, b)[1](dy)[i]
            out[f"{tag}_{which}_ms"] = 1e3 * timed(
                jax.jit(g_), (lhs, w, dy), calls)
    out["six_kernels_ms"] = sum(v for k, v in out.items()
                                if k.endswith(("fwd_ms", "dlhs_ms",
                                               "drhs_ms")))
    out["cast_up_ms"] = 1e3 * timed(
        jax.jit(functools.partial(cast, shape=(e, d_to, f_to))), (up32,),
        calls)
    out["gather_ms"] = 1e3 * timed(jax.jit(gather), (h, tok), calls)
    dw = jnp.zeros((e, d_to, f_to), jnp.float32)
    out["cut_ms"] = 1e3 * timed(jax.jit(cut), (dw,), calls) \
        if (d_to, f_to) != (d, f) else 0.0
    grad = jax.jit(jax.value_and_grad(one_round, argnums=(0, 1, 2)))
    out["round_ms"] = 1e3 * timed(grad, (h, up32, down32), calls)
    # the same pairs through every variant: the loss to compare by
    out["loss"] = float(grad(h, up32, down32)[0])
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes on the CPU; the times mean nothing")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--only", default="",
                    help="comma-separated variant names to run")
    args = ap.parse_args()
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearsal:
        sys.exit(f"a sweep measures the chip; this is {platform} "
                 "(--rehearsal runs toy sizes to find faults)")
    if platform != "tpu":           # the kernels through Pallas's interpreter
        real = grouped._megablox
        grouped._megablox = types.SimpleNamespace(
            gmm=functools.partial(real.gmm, interpret=True),
            tgmm=functools.partial(real.tgmm, interpret=True))
    if args.rehearsal:
        d, f, e, rows, n_tok = 336, 232, 8, 1024, 512
        sizes = jnp.array([30, 34, 31, 33, 29, 35, 32, 32], jnp.int32)
        plans = [("a_published", "ragged", d, f, None),
                 ("b_pad_64", "ragged", 384, 256, None),
                 ("d_gmm", "gmm", d, 256, (128, 128, 128))]
        calls = 1
    else:
        d, f, e, rows, n_tok = 2688, 1856, 8, 24576, 16384
        sizes = jnp.array(GROUP_SIZES, jnp.int32)
        plans = [("a_published", "ragged", d, f, None),
                 ("b_pad_512", "ragged", 3072, 2048, None),
                 ("c_pad_256", "ragged", 2816, 2048, None),
                 ("d_gmm_512_384_384", "gmm", d, 1920, (512, 384, 384)),
                 ("d_gmm_512_896_640", "gmm", d, 1920, (512, 896, 640)),
                 ("d_gmm_256_896_640", "gmm", d, 1920, (256, 896, 640)),
                 ("d_gmm_512_512_512_padded", "gmm", 3072, 2048,
                  (512, 512, 512))]
        calls = args.calls
    shapes = (rows, n_tok, d, f, e, sizes)
    only = set(filter(None, args.only.split(",")))
    results = []
    for name, impl, d_to, f_to, tiling in plans:
        if only and name not in only:
            continue
        if impl == "ragged":
            products = (ragged, ragged)
        else:                       # tiling = (rows, hidden, width)
            tm, th, tw = tiling
            products = tuple(tiled_at(t)
                             for t in ((tm, th, tw), (tm, tw, th)))
        try:
            row = variant(name, products, d_to, f_to, shapes, calls)
        except Exception as err:            # a variant the compiler refuses
            row = {"name": name, "error": f"{type(err).__name__}: "
                   f"{str(err)[:400]}"}
        row["platform"] = platform
        if args.rehearsal:
            row["rehearsal"] = True
        results.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/grouped_sweep.json", "w") as fh:
        json.dump(results, fh, indent=1)


if __name__ == "__main__":
    main()
