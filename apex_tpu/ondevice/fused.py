"""The fused Sebulba train step: the whole Ape-X cycle in one dispatch.

PR 10's ``--rollout ondevice`` fused acting (env + policy + chunk
assembly in one scan) but still woke the host per chunk: poll -> ingest
dispatch -> train dispatch -> write-back, with the replay-ratio loop in
between.  :class:`FusedStep` closes the remaining hops — ONE jitted
program per dispatch scans ``steps_per_dispatch`` macro steps of

    rollout segment (AnakinRollout._dispatch, verbatim)
    -> acting-TD priorities (device twin of the numpy epilogue)
    -> masked ingest of every sealed chunk (FramePoolReplay.add, valid=)
    -> [warm] P x (prioritized sample -> update_from_batch
                   -> priority write-back)

donating the train state AND the replay state so HBM never
double-buffers.  The host wakes once per dispatch for the epilogue:
episode stats, counters, publish/checkpoint/obs cadence.

Contracts (pinned in tests/test_ondevice_replay.py):

* **fused == serial.**  A ``steps_per_dispatch=N`` dispatch is
  bit-identical to N ``steps_per_dispatch=1`` dispatches — same macro
  body, same pre-split key chains — so the scan composition is pure
  dispatch-latency amortization (the ``scan_fused_steps`` contract,
  lifted to the whole training cycle).
* **device priorities are self-consistent, not host-identical.**  The
  acting-TD priorities compute in-program, where XLA's backend contracts
  ``reward + discount*max`` into one FMA rounding; the host builder's
  numpy rounds twice (the 1-ulp drift :mod:`apex_tpu.training.anakin`
  documents — measured to survive ``lax.optimization_barrier``, bitcast
  round-trips, and f64 detours on XLA:CPU, which is why PR 10 put its
  priorities in the host epilogue).  The fused plane's replay is fed
  exclusively by this program, so the contract that matters — the same
  priorities on every path that can meet in one tree — holds by
  construction; the <= 1-ulp envelope vs the numpy epilogue is pinned.
* **masked ingest.**  Unsealed slots of the fixed ``[B, M]`` chunk grid
  ingest with ``valid=False`` — a bit-exact no-op on every replay field
  (see :meth:`FramePoolReplay.add`).

Differences from the host loop, by design: acting params are the LIVE
``train_state.params`` (zero staleness — the Anakin end-state), the
replay ratio is STRUCTURAL by default (``B * rollout_len`` transitions
ingested per ``train_per_step`` updates) unless ``train_ratio`` is set —
then a device-side budget (f32 saturating at 2**24, exact-integer range)
accumulates ``ratio`` per ingested transition, spends ``batch_size`` per
update, and gates each train slot with ``lax.cond`` so the one host knob
serves fused and serial modes alike.  Warmup gates training via
``lax.cond`` on the device ingest counter, and beta anneals on-device in
f32 off that same counter (which saturates at ``max(warmup,
beta_anneal)+1`` — past both thresholds the exact count is irrelevant,
so i32 never wraps).

**dp mesh (PR 17).**  With ``mesh=`` the whole macro-scan runs under
``shard_map`` over the ``dp`` axis: env lanes partition as contiguous
blocks (chip ``s`` owns lanes ``[s*B/dp, (s+1)*B/dp)``), each chip
feeds its OWN replay-pool partition (the replay state arrives stacked
``[dp, ...]`` from :meth:`ShardedLearner.shard_replay_state`), each
train slot samples ``batch_size/dp`` per chip and ``pmean``s gradients
inside ``update_from_batch(axis_name="dp")``, and the warm/anneal
counter ``psum``s the per-chip ingest so warmup/beta stay GLOBAL
quantities.  Per-chip PRNG chains are split host-side with the serial
discipline (one ``split`` per macro / per train slot, then fanned
``split(key, dp)`` across chips), so the dp=1 chain is the dp=N chain's
prefix and the scan-composition parity holds at every width.
"""

from __future__ import annotations

import time

import numpy as np

from apex_tpu.config import ApexConfig
from apex_tpu.training.apex import ApexTrainer

#: metric keys td_update returns — the cond's cold branch must mirror
#: the structure exactly
_METRIC_KEYS = ("loss", "grad_norm", "q_mean", "td_mean")


def acting_priorities(out):
    """Device twin of ``AnakinRollout.rollout``'s numpy priority
    epilogue: ``|reward + discount*max(qn) - q_taken| + 1e-6`` over the
    ``[B, M, K]`` chunk grid.  XLA contracts the multiply-add into one
    FMA rounding where numpy rounds twice — a <= 1-ulp divergence the
    module docstring scopes (the fused replay never mixes these with
    host-computed priorities for the same transition)."""
    import jax.numpy as jnp

    target = out["reward"] + out["discount"] * out["qn_max"]
    return jnp.abs(target - out["q_taken"]) + jnp.float32(1e-6)


class FusedStep:
    """The jitted dispatch program plus its host-side chain/counters.

    ``core`` is the family's :class:`~apex_tpu.training.learner.
    LearnerCore` (``update_from_batch`` is the one family hook — AQL's
    proposal sampler and R2D2's carry slot in behind it), ``replay`` the
    :class:`FramePoolReplay` spec, ``engine`` a PR 10
    :class:`~apex_tpu.training.anakin.AnakinRollout` whose carry/key
    this object now owns.
    """

    def __init__(self, core, replay, engine, *, warmup: int,
                 beta: float, beta_anneal: int,
                 steps_per_dispatch: int = 4, train_per_step: int = 1,
                 mesh=None, train_ratio: float | None = None):
        import jax
        import jax.numpy as jnp

        if steps_per_dispatch < 1 or train_per_step < 1:
            raise ValueError(
                f"steps_per_dispatch={steps_per_dispatch} and "
                f"train_per_step={train_per_step} must be >= 1 "
                f"(--steps-per-dispatch / APEX_STEPS_PER_DISPATCH)")
        self.core = core
        self.replay = replay
        self.engine = engine
        self.mesh = mesh
        self.n_dp = 1 if mesh is None else int(mesh.shape["dp"])
        self._axis = None if self.n_dp == 1 else "dp"
        self.ratio = None if train_ratio is None else float(train_ratio)
        if core.batch_size % self.n_dp:
            raise ValueError(
                f"learner.batch_size={core.batch_size} must be divisible "
                f"by the dp axis (dp={self.n_dp}, from learner.mesh_shape "
                f"/ --mesh-dp) — raise batch_size or shrink the mesh")
        self._batch_chip = core.batch_size // self.n_dp
        if engine.B % self.n_dp:
            raise ValueError(
                f"fused dp={self.n_dp} shards the env lanes: "
                f"B={engine.B} envs (actor.n_actors x "
                f"actor.n_envs_per_actor) % dp={self.n_dp} != 0 — align "
                f"--n-envs-per-actor with the mesh (--mesh-dp / "
                f"APEX_MESH_DP) so every chip gets whole lanes")
        self.N = int(steps_per_dispatch)
        self.P = int(train_per_step)
        self.warmup = int(warmup)
        self.beta0 = float(beta)
        self.anneal = max(1, int(beta_anneal))
        # the device warm/anneal counter saturates here: beyond both
        # thresholds the exact count no longer matters, so i32 is safe
        # for arbitrarily long runs
        self._ing_cap = np.int32(max(self.warmup, self.anneal) + 1)
        self.ingested_dev = jnp.int32(0)
        # train_ratio budget: f32 stays integer-exact below 2**24, and a
        # budget that far ahead means training is the bottleneck anyway
        self._bud_cap = np.float32(2 ** 24)
        self.budget_dev = jnp.float32(0.0)
        if mesh is not None:
            import copy

            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            # per-chip engine: the device program depends on B alone
            # among the per-instance sizes (epsilons/slot_ids are
            # host-epilogue surfaces), so a shallow copy with B = B/dp
            # IS the chip's rollout program
            chip = copy.copy(engine)
            chip.B = engine.B // self.n_dp
            self._chip_engine = chip
            shard = NamedSharding(mesh, P("dp"))
            self._eps_dev = jax.device_put(
                np.asarray(jax.device_get(engine.epsilons)), shard)
            # lay the engine carries out on the mesh once, lane-sharded;
            # every later dispatch rebinds them from (sharded) results
            engine.carry = jax.device_put(engine.carry, shard)
            engine.carry_frames = jax.device_put(engine.carry_frames,
                                                 shard)
        else:
            self._chip_engine = engine
            self._eps_dev = None
        self._build_jit()
        # host counters (fleet_summary "ondevice" block; CI asserts)
        self.dispatches = 0
        self.macro_steps = 0
        self.train_steps = 0
        self.prio_writebacks = 0
        self.chunks = 0
        self.frames = 0
        self.transitions = 0
        self.external_ingest = 0

    # -- device program ----------------------------------------------------

    def _beta_at(self, ing):
        import jax.numpy as jnp
        frac = jnp.minimum(jnp.float32(1.0),
                           ing.astype(jnp.float32) / self.anneal)
        return (jnp.float32(self.beta0)
                + jnp.float32(1.0 - self.beta0) * frac)

    def _train_block(self, ts, rs, keys, ing, bud):
        import jax.numpy as jnp
        from jax import lax
        beta = self._beta_at(ing)

        def train1(ts2, rs2, k):
            batch, weights, idx = self.replay.sample(
                rs2, k, self._batch_chip, beta, axis_name=self._axis)
            ts2, prios, metrics = self.core.update_from_batch(
                ts2, batch, weights, axis_name=self._axis)
            rs2 = self.replay.update_priorities(rs2, idx, prios)
            return ts2, rs2, metrics

        if self.ratio is None:
            def body(carry, k):
                ts2, rs2 = carry
                ts2, rs2, metrics = train1(ts2, rs2, k)
                return (ts2, rs2), metrics

            (ts, rs), metrics = lax.scan(body, (ts, rs), keys)
            smask = jnp.ones((self.P,), bool)
            return ts, rs, bud, metrics, smask

        def body(carry, k):
            ts2, rs2, bud2 = carry
            go = bud2 > jnp.float32(0.0)

            def step(args):
                ts3, rs3 = args
                return train1(ts3, rs3, k)

            def hold(args):
                ts3, rs3 = args
                zero = jnp.float32(0.0)
                return ts3, rs3, {m: zero for m in _METRIC_KEYS}

            ts2, rs2, metrics = lax.cond(go, step, hold, (ts2, rs2))
            bud2 = bud2 - jnp.where(go, jnp.float32(self.core.batch_size),
                                    jnp.float32(0.0))
            return (ts2, rs2, bud2), (metrics, go)

        (ts, rs, bud), (metrics, smask) = lax.scan(body, (ts, rs, bud),
                                                   keys)
        return ts, rs, bud, metrics, smask

    def _macro(self, eng, eps, carry, xs):
        import jax
        import jax.numpy as jnp
        from jax import lax

        ts, rs, c, cf, ing, bud = carry
        rkey, skeys = xs
        # trace scopes: ``rollout`` here; the masked chunk ingests, the
        # sampling, the update and the write-back below carry the step
        # program's five names from where that work lives (replay/,
        # training/learner.td_update)
        with jax.named_scope("rollout"):
            c, cf, out = eng._dispatch(ts.params, eps, c, cf, rkey)
            prios = acting_priorities(out)                   # [B, M, K]
        B, M = eng.B, eng.M
        sealed = out["sealed"]                               # [B]
        mask = jnp.arange(M, dtype=jnp.int32)[None, :] < sealed[:, None]

        def flat(a):
            return a.reshape((B * M,) + a.shape[2:])

        slots = {k: flat(out[k]) for k in
                 ("frames", "action", "reward", "discount",
                  "obs_ref", "next_ref", "nf", "nt")}

        def ingest(carry2, xs2):
            rs2, d2 = carry2
            sl, pr, do = xs2
            chunk = dict(frames=sl["frames"], n_frames=sl["nf"],
                         n_trans=sl["nt"], action=sl["action"],
                         reward=sl["reward"], discount=sl["discount"],
                         obs_ref=sl["obs_ref"], next_ref=sl["next_ref"])
            rs2 = self.replay.add(rs2, chunk, pr, valid=do)
            d2 = d2 + jnp.where(do, sl["nt"], 0)
            return (rs2, d2), ()

        (rs, delta), _ = lax.scan(ingest, (rs, jnp.int32(0)),
                                  (slots, flat(prios), mask.reshape(-1)))

        sealed_n = sealed.sum()
        sealed_mx = sealed.max()
        n_trans = jnp.where(mask, out["nt"], 0).sum()
        if self._axis is not None:
            # warmup/anneal/ratio are GLOBAL quantities: count every
            # chip's ingest (the collectives also make these ys leaves
            # honestly replicated for the out_specs=P() assembly)
            delta = lax.psum(delta, self._axis)
            sealed_n = lax.psum(sealed_n, self._axis)
            n_trans = lax.psum(n_trans, self._axis)
            sealed_mx = lax.pmax(sealed_mx, self._axis)
        # end-of-macro min == the per-chunk saturating add (i32, d >= 0)
        ing = jnp.minimum(ing + delta, self._ing_cap)
        if self.ratio is not None:
            bud = jnp.minimum(
                bud + delta.astype(jnp.float32) * jnp.float32(self.ratio),
                self._bud_cap)

        warm = ing >= jnp.int32(self.warmup)

        def do_train(args):
            ts2, rs2, bud2 = args
            return self._train_block(ts2, rs2, skeys, ing, bud2)

        def skip(args):
            ts2, rs2, bud2 = args
            zero = jnp.zeros((self.P,), jnp.float32)
            return (ts2, rs2, bud2, {k: zero for k in _METRIC_KEYS},
                    jnp.zeros((self.P,), bool))

        ts, rs, bud, metrics, smask = lax.cond(warm, do_train, skip,
                                               (ts, rs, bud))
        done, ep_ret, ep_len = out["stepped"]
        ys = dict(metrics=metrics, trained=warm, step_mask=smask,
                  sealed=sealed_n, sealed_max=sealed_mx,
                  n_trans=n_trans,
                  done=done, ep_ret=ep_ret, ep_len=ep_len)
        return (ts, rs, c, cf, ing, bud), ys

    def _scan_dispatch(self, eng, eps, ts, rs, c, cf, ing, bud,
                       rkeys, skeys):
        import functools

        from jax import lax
        (ts, rs, c, cf, ing, bud), ys = lax.scan(
            functools.partial(self._macro, eng, eps),
            (ts, rs, c, cf, ing, bud), (rkeys, skeys))
        return ts, rs, c, cf, ing, bud, ys

    def _build_jit(self):
        """(Re)build the jitted dispatch — plain jit at dp=1, a
        ``shard_map`` over the dp mesh otherwise.  The donation set is
        the device-resident carry (ts, rs, carries, ingest counter); the
        budget scalar and the lane-sharded epsilons are NOT donated (the
        epsilons buffer is reused every dispatch)."""
        import jax

        if self.mesh is None:
            def run(ts, rs, c, cf, ing, bud, rkeys, skeys):
                return self._scan_dispatch(
                    self.engine, self.engine.epsilons,
                    ts, rs, c, cf, ing, bud, rkeys, skeys)

            self._jit = jax.jit(run, donate_argnums=(0, 1, 2, 3, 4))
            return

        from jax.sharding import PartitionSpec as P

        chip = self._chip_engine

        def per_chip(ts, rs, c, cf, ing, bud, eps, rkeys, skeys):
            # replay state arrives stacked [dp, ...] sharded on axis 0:
            # strip this chip's partition, restore the axis on the way
            # out (the ShardedLearner per-chip idiom); the engine
            # carries shard on their native lane axis, no strip needed
            rs = jax.tree.map(lambda x: x[0], rs)
            rk = jax.random.wrap_key_data(rkeys[:, 0])
            sk = jax.random.wrap_key_data(skeys[:, :, 0])
            ts, rs, c, cf, ing, bud, ys = self._scan_dispatch(
                chip, eps, ts, rs, c, cf, ing, bud, rk, sk)
            rs = jax.tree.map(lambda x: x[None], rs)
            return ts, rs, c, cf, ing, bud, ys

        repl, shard = P(), P("dp")
        lanes = P(None, None, "dp")       # [N, T, B] episode-lane leaves
        ys_spec = dict(metrics=repl, trained=repl, step_mask=repl,
                       sealed=repl, sealed_max=repl, n_trans=repl,
                       done=lanes, ep_ret=lanes, ep_len=lanes)
        mapped = jax.shard_map(
            per_chip, mesh=self.mesh,
            in_specs=(repl, shard, shard, shard, repl, repl, shard,
                      P(None, "dp"), P(None, None, "dp")),
            out_specs=(repl, shard, shard, shard, repl, repl, ys_spec),
            check_vma=False)
        self._jit = jax.jit(mapped, donate_argnums=(0, 1, 2, 3, 4))

    # -- host surface ------------------------------------------------------

    def dispatch(self, train_state, replay_state, sample_key):
        """One device program: N macro steps.  Advances the engine's
        rollout chain and the caller's sample chain with the exact split
        discipline a serial run would, returns ``(train_state,
        replay_state, sample_key, info)``."""
        import jax
        import jax.numpy as jnp

        from apex_tpu.actors.pool import EpisodeStat

        eng = self.engine
        fan = self.n_dp
        rkeys, skeys = [], []
        for _ in range(self.N):
            # ONE split per macro step off the engine chain — the serial
            # discipline at every dp width; dp>1 fans the macro key into
            # per-chip keys shipped as raw key data ([N, dp, 2] u32,
            # lane-sharded), re-wrapped per chip inside the shard_map
            eng.key, rk = jax.random.split(eng.key)
            rkeys.append(np.asarray(jax.random.key_data(
                jax.random.split(rk, fan))) if fan > 1 else rk)
            row = []
            for _ in range(self.P):
                sample_key, k = jax.random.split(sample_key)
                row.append(np.asarray(jax.random.key_data(
                    jax.random.split(k, fan))) if fan > 1 else k)
            skeys.append(np.stack(row) if fan > 1 else jnp.stack(row))
        rk_arr = np.stack(rkeys) if fan > 1 else jnp.stack(rkeys)
        sk_arr = np.stack(skeys) if fan > 1 else jnp.stack(skeys)
        args = [train_state, replay_state, eng.carry, eng.carry_frames,
                self.ingested_dev, self.budget_dev]
        if fan > 1:
            args.append(self._eps_dev)
        (train_state, replay_state, eng.carry, eng.carry_frames,
         self.ingested_dev, self.budget_dev, ys) = self._jit(
            *args, rk_arr, sk_arr)
        got = jax.device_get(ys)
        if int(got["sealed_max"].max(initial=0)) > eng.M - 1:
            raise RuntimeError(
                f"fused outbox overflow: {int(got['sealed_max'].max())} "
                f"seals > {eng.M - 1} sealed slots — raise rollout_len "
                f"headroom")
        done, ep_ret, ep_len = got["done"], got["ep_ret"], got["ep_len"]
        stats = [EpisodeStat(eng.slot_ids[b], float(ep_ret[m, t, b]),
                             int(ep_len[m, t, b]))
                 for m in range(self.N) for t in range(eng.T)
                 for b in range(eng.B) if done[m, t, b]]
        # [N, P] per-slot mask: all warm slots without train_ratio, the
        # budget-gated subset with it — identical aggregation either way
        smask = np.asarray(got["step_mask"], bool)
        trained = int(smask.sum())
        metrics = None
        if trained:
            metrics = {k: float(np.asarray(v)[smask].mean())
                       for k, v in got["metrics"].items()}
        transitions = int(got["n_trans"].sum())
        self.dispatches += 1
        self.macro_steps += self.N
        self.train_steps += trained
        self.prio_writebacks += trained
        self.chunks += int(got["sealed"].sum())
        self.frames += self.N * eng.T * eng.B
        self.transitions += transitions
        info = dict(stats=stats, metrics=metrics, train_steps=trained,
                    transitions=transitions,
                    frames=self.N * eng.T * eng.B)
        return train_state, replay_state, sample_key, info

    def note_external_ingest(self, n: int) -> None:
        """Host-path chunks (hybrid socket actors) ingested outside the
        fused program still advance the device warm/anneal counter (and
        the train-ratio budget, when one is live)."""
        import jax.numpy as jnp
        self.ingested_dev = jnp.minimum(
            self.ingested_dev + jnp.int32(n), self._ing_cap)
        if self.ratio is not None:
            self.budget_dev = jnp.minimum(
                self.budget_dev + jnp.float32(float(n) * self.ratio),
                self._bud_cap)
        self.external_ingest += int(n)

    def sync_ingested(self, n: int, steps: int = 0) -> None:
        """Re-seed the device counters after a checkpoint restore —
        ``n`` transitions ingested, ``steps`` learner updates taken."""
        import jax.numpy as jnp
        self.ingested_dev = jnp.minimum(jnp.int32(min(n, 2 ** 31 - 1)),
                                        self._ing_cap)
        if self.ratio is not None:
            self.budget_dev = jnp.minimum(
                jnp.float32(float(n) * self.ratio
                            - float(steps) * self.core.batch_size),
                self._bud_cap)

    def rebind(self, core) -> None:
        """Re-jit against a rebuilt core (live lr application — one
        recompile per explore, the apply_hparams contract)."""
        self.core = core
        self._build_jit()

    def counters(self) -> dict:
        """``fleet_summary.json``'s ``ondevice`` block (the fused-smoke
        CI job asserts these are nonzero)."""
        return {"dispatches": self.dispatches,
                "macro_steps": self.macro_steps,
                "train_steps": self.train_steps,
                "prio_writebacks": self.prio_writebacks,
                "chunks": self.chunks, "frames": self.frames,
                "transitions": self.transitions,
                "external_ingest": self.external_ingest,
                "steps_per_dispatch": self.N,
                "train_per_step": self.P,
                "dp": self.n_dp,
                "train_ratio": float(self.ratio or 0.0),
                "rollout_len": self.engine.T, "n_envs": self.engine.B}


class _IdlePool:
    """The in-host fused topology has no actor plane at all: rollouts
    live inside the dispatch.  This is the minimal pool surface the
    ConcurrentTrainer helpers probe."""

    def start(self) -> None:
        pass

    def cleanup(self) -> None:
        pass

    def publish_params(self, version: int, params) -> None:
        pass

    def poll_chunks(self, max_chunks: int, timeout: float = 0.0) -> list:
        return []

    def poll_stats(self) -> list:
        return []


class FusedApexTrainer(ApexTrainer):
    """``--rollout fused``: the ConcurrentTrainer-path driver whose hot
    loop is one :class:`FusedStep` dispatch per iteration.

    Reuses the whole ApexTrainer substrate — model/replay/optimizer
    construction, checkpoint bundle (``replay_state`` IS the on-device
    pool, so the PR 8 machinery host-spills it for free), fleet
    registry/status/ctl surface, SLO engine, publish cadence — and
    replaces only the chunk-driven drain with the fused dispatch.  The
    socket pool (when one is attached) keeps serving evaluators and the
    param channel; any host-actor chunks that arrive are absorbed into
    the same replay state between dispatches (hybrid mode).

    A dp>1 learner mesh shards the WHOLE fused program (env lanes,
    replay partitions, pmean'd updates — see :class:`FusedStep`); the
    honest capability limits left are divisibility (lanes and batch must
    split evenly over the mesh) and their ValueErrors name both knobs.
    Graceful refusals otherwise: non-jittable envs fail in
    ``make_jax_env``'s ValueError and non-DQN families fail in the
    CLI/role wiring.
    """

    _loop_track = "learner-fused-loop"

    def __init__(self, config: ApexConfig | None = None,
                 logdir: str | None = None, verbose: bool = False,
                 publish_min_seconds: float = 0.2,
                 train_ratio=None, min_train_ratio=None,
                 checkpoint_dir: str | None = None, pool=None,
                 respawn_workers: bool = True,
                 rollout_len: int | None = None,
                 steps_per_dispatch: int = 4, train_per_step: int = 1):
        cfg = config or ApexConfig()
        # non-jittable env ids refuse HERE, before any pool/worker spawns
        from apex_tpu.envs.registry import make_jax_env
        make_jax_env(cfg.env.env_id, cfg.env)
        super().__init__(cfg, logdir=logdir, verbose=verbose,
                         publish_min_seconds=publish_min_seconds,
                         train_ratio=train_ratio,
                         min_train_ratio=min_train_ratio,
                         checkpoint_dir=checkpoint_dir,
                         pool=pool if pool is not None else _IdlePool(),
                         respawn_workers=respawn_workers)
        from apex_tpu.training.anakin import make_anakin_engine
        engine = make_anakin_engine(cfg, rollout_len=rollout_len)
        # dp>1: ApexTrainer._init_sharded already built the mesh, the
        # stacked per-chip replay partitions, and the replicated train
        # state — the fused program rides the same layout
        mesh = self.sharded.mesh if getattr(self, "n_dp", 1) > 1 else None
        self.fused = FusedStep(
            self.core, self.replay, engine,
            warmup=cfg.replay.warmup, beta=cfg.replay.beta,
            beta_anneal=cfg.replay.beta_anneal,
            steps_per_dispatch=steps_per_dispatch,
            train_per_step=train_per_step,
            mesh=mesh, train_ratio=train_ratio)

    # -- the fused hot loop ------------------------------------------------

    def train(self, total_steps: int, max_seconds: float = 3600.0,
              log_every: int = 200):
        """Run (at least) ``total_steps`` MORE learner updates — the
        dispatch granularity means up to ``steps_per_dispatch *
        train_per_step - 1`` overshoot."""
        import jax.numpy as jnp

        from apex_tpu.fleet.heartbeat import HeartbeatEmitter
        from apex_tpu.fleet.registry import FleetRegistry
        from apex_tpu.obs import spans as obs_spans
        from apex_tpu.obs.trace import get_ring, set_process_label
        from apex_tpu.utils.profiling import DispatchGapTimer

        cfg = self.cfg
        pool = self.pool
        target_steps = self.steps_rate.total + total_steps
        if self.actor_timing is None:
            self.actor_timing = {}
        set_process_label("learner")
        ring = self._ring = get_ring()
        if self._obs is None:
            self._obs = obs_spans.LearnerObs(ring=ring)
        gap = self._dispatch_gap = DispatchGapTimer(
            ring=ring, track=self._loop_track)
        if self.fleet is None:
            self.fleet = FleetRegistry(cfg.comms)
        pool.start()
        set_epoch = getattr(pool, "set_learner_epoch", None)
        if set_epoch is not None:
            set_epoch(self.learner_epoch)
        self._start_status_server()
        # the fused plane beats into the registry like AnakinPool's
        # ondevice-0 does, so the status table shows it next to any
        # socket peers
        beat = HeartbeatEmitter(
            "fused-0", role="rollout",
            interval_s=cfg.comms.heartbeat_interval_s,
            gauges_fn=self.fused.counters)
        try:
            self._publish()
            last_publish = time.monotonic()
            t_end = last_publish + max_seconds
            last_pub_step = self.steps_rate.total
            last_health = last_publish
            self._episode_idx = 0
            metrics = None

            while self.steps_rate.total < target_steps:
                now = time.monotonic()
                stop = self._stop_requested
                if now > t_end or (stop is not None and stop.is_set()):
                    break
                self._pass += 1
                with self._span("loop_iter", kind="fused"):
                    with self._dispatch(
                            "fused", self.fused.dispatch,
                            program="jit_" + self.fused._jit.__name__) as call:
                        (self.train_state, self.replay_state, self.key,
                         info) = call(self.train_state, self.replay_state,
                                      self.key)
                    if info["train_steps"]:
                        self.steps_rate.tick(info["train_steps"])
                        if info["metrics"] is not None:
                            metrics = info["metrics"]
                    self.ingested += info["transitions"]
                    self.frames_rate.tick(info["transitions"])
                    for stat in info["stats"]:
                        self.log.scalars(
                            {"episode_reward": stat.reward,
                             "episode_length": stat.length,
                             "actor_id": stat.actor_id}, self._episode_idx)
                        self._episode_idx += 1
                    # hybrid: host-actor chunks absorb between dispatches
                    # (ingest-only — the fused program owns the train cadence)
                    with self._span("poll_slot"):
                        msgs = pool.poll_chunks(64, timeout=0)
                    for msg in msgs:
                        self.replay_state = self._ingest(
                            self.replay_state, msg["payload"],
                            jnp.asarray(msg["priorities"]))
                        n_new = int(msg["n_trans"])
                        self.ingested += n_new
                        self.frames_rate.tick(n_new)
                        self.fused.note_external_ingest(n_new)
                    beat.tick(info["frames"])
                    hb = beat.maybe_beat(self.param_version)
                    if hb is not None:
                        self.fleet.observe(hb)

                    steps = self.steps_rate.total
                    if (self.checkpointer is not None
                            and steps - self._last_save
                            >= cfg.learner.save_interval):
                        with self._span("checkpoint"):
                            self.save_checkpoint()
                        self._last_save = steps
                    if steps:
                        due = (now - last_publish >= self.publish_min_seconds
                               and (steps - last_pub_step
                                    >= cfg.learner.publish_interval
                                    or now - last_publish
                                    > 10 * self.publish_min_seconds))
                    else:
                        due = (getattr(pool, "needs_warmup_republish", False)
                               and now - last_publish
                               > 10 * self.publish_min_seconds)
                    if due:
                        self._publish()
                        last_publish = now
                        last_pub_step = steps
                    with self._span("drain_stats"):
                        self._drain_stats(steps)  # before the tick (apex.py)
                    if self.respawn_workers and now - last_health >= 5.0:
                        with self._span("health_tick"):
                            self._health_tick(steps)
                        last_health = now
                    if metrics is not None \
                            and steps - self._last_log >= log_every:
                        with self._span("log_scalars"):
                            extra = gap.snapshot()
                            if self._obs is not None:
                                extra |= self._obs.scalars()
                            self.log.scalars(
                                {k: float(v) for k, v in metrics.items()}
                                | {"bps": self.steps_rate.rate,
                                   "fps": self.frames_rate.rate,
                                   "param_version": self.param_version,
                                   "ingested": self.ingested} | extra,
                                steps)
                        self._last_log = steps
        finally:
            if self._fleet_status is not None:
                self._fleet_status.stop()
                self._fleet_status = None
            self._dump_fleet_summary()
            pool.cleanup()
            stop = self._stop_requested
            if stop is not None:
                stop.clear()
        return self

    # -- surface integration ----------------------------------------------

    def fleet_summary(self):
        snap = super().fleet_summary()
        if snap is not None and getattr(self, "fused", None) is not None:
            import jax

            # the fused-smoke CI drills assert these from the persisted
            # summary (dispatches/chunks/transitions + >=1 write-back;
            # the dp drill additionally checks one live pool per shard)
            ond = self.fused.counters()
            ond["pool_size_per_shard"] = [
                int(v) for v in np.asarray(
                    jax.device_get(self.replay_state.size)).reshape(-1)]
            snap["metrics"]["ondevice"] = ond
        return snap

    def _apply_counters(self, meta: dict) -> None:
        super()._apply_counters(meta)
        self.fused.sync_ingested(self.ingested,
                                 steps=self.steps_rate.total)

    def apply_hparams(self, h: dict) -> dict:
        applied = super().apply_hparams(h)
        if "lr" in applied:
            # the fused program closed over the old core's optimizer —
            # rebind + re-jit (one recompile per explore, same contract
            # as the host loop's hot-fn rebuild)
            self.fused.rebind(self.core)
        return applied
