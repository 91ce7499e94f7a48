"""Loss and update rules as pure functions.

Parity with the reference's ``utils.compute_loss`` (``utils.py:64-81``) and
``update_parameters`` (``utils.py:84-97``):

* n-step double-DQN TD target: online net argmax picks the action, target net
  evaluates it (``utils.py:71-74``).
* Huber (delta=1) elementwise, weighted by IS weights, mean-reduced
  (``utils.py:79-80``).
* Replay priorities via the mixed-max heuristic
  ``0.9*max(|td|) + 0.1*|td| + 1e-6`` (``utils.py:77``).
* Gradient clipping by global norm (max_norm=40, ``arguments.py:65-66``) and
  centered RMSprop (``ApeX.py:37``) — composed as one optax chain so the whole
  update fuses into the learner's XLA step.

Like the reference this runs THREE forward passes (online(s), online(s'),
target(s') — ``utils.py:67-69``).  online(s') only picks the bootstrap
action, so it runs on parameters cut out of the differentiated graph: it
saves no residuals and is never back-propagated (folded into one batched
pass over concatenated states, as it was before PR 29, its half of the
batch kept its activations and took a backward pass of zeros).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax


class TDOutput(NamedTuple):
    loss: jax.Array          # scalar
    td_abs: jax.Array        # (B,) |TD error|
    priorities: jax.Array    # (B,) mixed-max heuristic priorities
    q_taken: jax.Array       # (B,) Q(s0, a0) — mean logged as learner/q
    stats: dict | None = None  # scalars a model counted inside its forward
                               # passes (expert routing), by pass


class AQLOutput(NamedTuple):
    loss: jax.Array
    td_abs: jax.Array
    priorities: jax.Array
    q_taken: jax.Array
    best_idx: jax.Array      # (B,) argmax candidate of the CURRENT state —
                             # the proposal loss target, returned here so the
                             # update never re-scores the candidate set


def huber(x: jax.Array, delta: float = 1.0) -> jax.Array:
    """Elementwise Huber written exactly as the reference's branchless form
    (``utils.py:79``)."""
    absx = jnp.abs(x)
    return jnp.where(absx < delta, 0.5 * x * x, delta * (absx - 0.5 * delta))


# max/mean (or max/per-item) priority mix weight — ONE constant shared by
# the batch-level heuristic below, the sequence loss (r2d2_loss), and the
# acting-time sequence priorities (training/r2d2.py:SequenceBuilder) so
# learner write-back and actor inserts can't drift onto different mixes
PRIORITY_ETA = 0.9


def mixed_max_priorities(td_abs: jax.Array, eps: float = 1e-6) -> jax.Array:
    return (PRIORITY_ETA * td_abs.max()
            + (1.0 - PRIORITY_ETA) * td_abs + eps)


def _q_and_stats(out, suffix: str) -> tuple[jax.Array, dict]:
    """``apply_fn`` gives ``q`` or ``(q, stats)``: the stats of one pass
    under that pass's suffix."""
    if isinstance(out, tuple):
        return out[0], {k + suffix: v for k, v in out[1].items()}
    return out, {}


def double_dqn_loss(
    apply_fn: Callable[..., jax.Array],
    params: Any,
    target_params: Any,
    batch: dict[str, jax.Array],
    weights: jax.Array,
) -> tuple[jax.Array, TDOutput]:
    """IS-weighted n-step double-DQN Huber loss.

    ``batch['reward']`` is the pre-accumulated n-step return,
    ``batch['next_obs']`` the bootstrap state, and ``batch['discount']`` the
    per-transition bootstrap coefficient (the actor-side accumulator builds
    all three, mirroring ``memory.py:415-440``): ``gamma ** n`` for full
    windows (``utils.py:74``), ``gamma ** k`` for truncated tails, and ``0``
    at true terminals — replacing the reference's ``gamma ** n * (1 - done)``
    with truncation-correct bootstrapping.
    """
    obs, next_obs = batch["obs"], batch["next_obs"]
    q_values, stats = _q_and_stats(apply_fn(params, obs), "")
    next_q_values, s = _q_and_stats(
        apply_fn(jax.lax.stop_gradient(params), next_obs), "_next")
    stats |= s
    tgt_next_q_values, s = _q_and_stats(apply_fn(target_params, next_obs),
                                        "_target")
    stats |= s

    actions = batch["action"].astype(jnp.int32)
    q_taken = jnp.take_along_axis(q_values, actions[:, None], axis=1)[:, 0]
    next_actions = next_q_values.argmax(axis=1)
    next_q_taken = jnp.take_along_axis(
        tgt_next_q_values, next_actions[:, None], axis=1)[:, 0]

    target = batch["reward"] + batch["discount"] * next_q_taken
    td = jax.lax.stop_gradient(target) - q_taken
    td_abs = jnp.abs(td)

    loss = (huber(td) * weights).mean()
    return loss, TDOutput(loss=loss, td_abs=td_abs,
                          priorities=mixed_max_priorities(td_abs),
                          q_taken=q_taken, stats=stats or None)


def r2d2_loss(
    apply_fn: Callable[..., tuple],
    params: Any,
    target_params: Any,
    batch: dict[str, jax.Array],
    weights: jax.Array,
    *,
    burn_in: int,
    n_steps: int,
    eta: float = PRIORITY_ETA,
    eps: float = 1e-6,
) -> tuple[jax.Array, TDOutput]:
    """Sequence double-DQN loss for the recurrent family (R2D2 recipe on
    the reference's TD conventions).

    ``apply_fn(params, obs_seq [B, L, *obs], carry) -> (q [B, L, A],
    carry)`` is the recurrent network.  ``batch``: ``obs [B, T, *obs]``,
    ``action``/``reward`` ``[B, T]``, ``discount [B, T]`` =
    ``gamma * (1 - done)`` per STEP (0 at terminals — padded steps also
    carry 0, so n-step products truncate naturally), ``mask [B, T]`` = 1
    on real steps, ``state_c``/``state_h`` ``[B, H]`` — the actor's
    recurrent state at sequence start (R2D2 stored-state).  Sequence
    geometry: ``T = burn_in + unroll + n_steps``; the loss covers the
    ``unroll`` positions after burn-in.

    Burn-in: both nets unroll the prefix from the stored state and the
    resulting carries are ``stop_gradient``-ed — the prefix only warms
    the state, contributing no gradient and no loss terms.

    Per-sequence priorities use R2D2's mix ``eta * max_t |td| +
    (1 - eta) * mean_t |td|`` — the sequence analogue of the reference's
    mixed-max heuristic (``utils.py:77``).
    """
    obs = batch["obs"]
    t_total = obs.shape[1]
    unroll = t_total - burn_in - n_steps
    if unroll < 1:
        raise ValueError(
            f"sequence length {t_total} too short for burn_in={burn_in} "
            f"+ n_steps={n_steps} + at least one unroll step")

    carry0 = (batch["state_c"], batch["state_h"])
    if burn_in:
        _, carry_on = apply_fn(params, obs[:, :burn_in], carry0)
        _, carry_tg = apply_fn(target_params, obs[:, :burn_in], carry0)
        carry_on = jax.lax.stop_gradient(carry_on)
        carry_tg = jax.lax.stop_gradient(carry_tg)
    else:
        carry_on = carry_tg = carry0

    body = obs[:, burn_in:]                       # [B, unroll + n, *obs]
    q_seq, _ = apply_fn(params, body, carry_on)   # [B, unroll + n, A]
    qt_seq, _ = apply_fn(target_params, body, carry_tg)

    r = batch["reward"][:, burn_in:]
    d = batch["discount"][:, burn_in:]
    m = batch["mask"][:, burn_in:]

    # n-step returns per unroll position; discount 0 at terminals/padding
    # truncates every product past end-of-episode
    returns = jnp.zeros(r.shape[:1] + (unroll,), jnp.float32)
    disc_prod = jnp.ones_like(returns)
    for i in range(n_steps):
        returns = returns + disc_prod * r[:, i:i + unroll]
        disc_prod = disc_prod * d[:, i:i + unroll]

    next_online = q_seq[:, n_steps:n_steps + unroll]
    next_target = qt_seq[:, n_steps:n_steps + unroll]
    a_star = next_online.argmax(axis=-1)
    bootstrap = jnp.take_along_axis(next_target, a_star[..., None],
                                    axis=-1)[..., 0]
    target = returns + disc_prod * bootstrap

    actions = batch["action"][:, burn_in:burn_in + unroll].astype(jnp.int32)
    q_taken = jnp.take_along_axis(q_seq[:, :unroll], actions[..., None],
                                  axis=-1)[..., 0]
    td = jax.lax.stop_gradient(target) - q_taken
    lmask = m[:, :unroll]
    n_valid = jnp.maximum(lmask.sum(axis=1), 1.0)

    loss = ((huber(td) * lmask).sum(axis=1) / n_valid * weights).mean()

    td_abs = jnp.abs(td) * lmask
    seq_max = td_abs.max(axis=1)
    seq_mean = td_abs.sum(axis=1) / n_valid
    priorities = eta * seq_max + (1.0 - eta) * seq_mean + eps
    q_mean = (q_taken * lmask).sum(axis=1) / n_valid
    return loss, TDOutput(loss=loss, td_abs=seq_mean,
                          priorities=priorities, q_taken=q_mean)


def make_optimizer(lr: float = 6.25e-5, decay: float = 0.95,
                   eps: float = 1.5e-7, centered: bool = True,
                   max_grad_norm: float = 40.0,
                   lr_decay_steps: int | None = 1000,
                   lr_decay_rate: float = 0.99) -> optax.GradientTransformation:
    """Clip-then-RMSprop chain matching ``ApeX.py:37`` + ``utils.py:95``,
    with the single-host drivers' ``StepLR(step_size=1000, gamma=0.99)``
    reproduced as a staircase exponential decay (``DQN.py:39,71``,
    ``ApeX.py:38,60``): lr(step) = lr * rate^(step // steps), stepped once
    per optimizer update exactly like ``scheduler.step()`` per learner
    iteration.  ``lr_decay_steps=0``/``None`` = constant lr (the
    reference's distributed learner, ``origin_repo/learner.py:145``)."""
    schedule = (optax.exponential_decay(lr, lr_decay_steps, lr_decay_rate,
                                        staircase=True)
                if lr_decay_steps else lr)
    return optax.chain(
        optax.clip_by_global_norm(max_grad_norm),
        optax.rmsprop(schedule, decay=decay, eps=eps, centered=centered),
    )


# -- AQL (proposal-action Q-learning) --------------------------------------

def aql_q_loss(
    score_fn: Callable[..., jax.Array],
    params: Any,
    target_params: Any,
    batch: dict[str, jax.Array],
    weights: jax.Array,
    online_noise: jax.Array,
    target_noise: jax.Array,
) -> tuple[jax.Array, AQLOutput]:
    """Double-DQN TD loss over the stored candidate set (reference
    ``compute_loss_AQL``, ``utils.py:44-61``).

    ``batch['action']`` is the INDEX into ``batch['a_mu'] [B, T, A]``; both
    current and next state are scored against the SAME stored candidate set
    (the reference reuses the transition's ``a_mu`` for ``next_states`` too,
    ``utils.py:47-49`` — by design: the set that produced the acted action
    stays the comparison basis).  ``online_noise``/``target_noise`` pin one
    NoisyNet draw per network per update, matching the
    reset-once-per-step buffer semantics (``AQL_dis.py:104-105``).
    """
    obs, next_obs, a_mu = batch["obs"], batch["next_obs"], batch["a_mu"]
    both = jnp.concatenate([obs, next_obs], axis=0)
    a_both = jnp.concatenate([a_mu, a_mu], axis=0)
    q_both = score_fn(params, both, a_both, online_noise)
    q_values, next_q_values = jnp.split(q_both, 2, axis=0)
    tgt_next_q_values = score_fn(target_params, next_obs, a_mu, target_noise)

    idx = batch["action"].astype(jnp.int32)
    q_taken = jnp.take_along_axis(q_values, idx[:, None], axis=1)[:, 0]
    next_idx = next_q_values.argmax(axis=1)
    next_q_taken = jnp.take_along_axis(
        tgt_next_q_values, next_idx[:, None], axis=1)[:, 0]

    target = batch["reward"] + batch["discount"] * next_q_taken
    td = jax.lax.stop_gradient(target) - q_taken
    td_abs = jnp.abs(td)
    loss = (huber(td) * weights).mean()
    return loss, AQLOutput(loss=loss, td_abs=td_abs,
                           priorities=mixed_max_priorities(td_abs),
                           q_taken=q_taken,
                           best_idx=jax.lax.stop_gradient(
                               q_values.argmax(axis=1)))


def aql_proposal_loss(
    log_prob_fn: Callable[..., tuple[jax.Array, jax.Array]],
    params: Any,
    batch: dict[str, jax.Array],
    best_idx: jax.Array,
    entropy_coef: float,
) -> jax.Array:
    """Entropy-regularized NLL of the argmax-Q candidate (reference
    ``AQL_dis.py:79-86``): pull the proposal mean toward the action the Q
    head currently ranks best.  ``best_idx`` comes from the Q pass and is
    treated as data (no gradient through the argmax)."""
    best_action = jnp.take_along_axis(
        batch["a_mu"], best_idx[:, None, None], axis=1)[:, 0, :]
    log_prob, entropy = log_prob_fn(params, batch["obs"],
                                    jax.lax.stop_gradient(best_action))
    return jnp.mean(-log_prob - entropy_coef * entropy)


def aql_param_labels(params: Any) -> Any:
    """'proposal' / 'q' label tree for the two-optimizer split
    (``AQL.py:41-42``).

    The state-embedding trunk belongs to the PROPOSAL group: it feeds only
    the proposal mean (the Q score path reads raw observations through
    ``q_feature``, reference ``model.py:294-320``).  The reference
    accidentally freezes this trunk forever — its Q optimizer owns but never
    gradients it, and its proposal optimizer gradients but never owns it
    (``AQL_dis.py:87-101``).  Training it under the proposal optimizer is a
    deliberate fix, not drift."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: "proposal"
        if any(str(getattr(k, "key", k)).startswith(("proposal", "embed"))
               for k in path) else "q",
        params)


def make_aql_optimizer(q_lr: float = 1e-4, proposal_lr: float = 1e-4,
                       max_grad_norm: float = 40.0,
                       cosine_steps: int | None = None
                       ) -> optax.GradientTransformation:
    """Per-group clip + Adam, split by :func:`aql_param_labels` (reference
    clips and steps the two parameter sets independently,
    ``AQL_dis.py:87-101``, Adam opts ``AQL.py:41-42``).

    ``cosine_steps`` reproduces the reference's
    ``CosineAnnealingLR(T_max=max_step, eta_min=lr/1000)`` on both groups
    (``AQL.py:48-49``; ``max_step`` defaults to 1e6, ``AQL.py:18``);
    ``None``/0 = constant lr (the distributed ``AQL_dis`` path, which
    never constructs schedulers)."""
    def group(lr):
        if cosine_steps:
            lr = cosine_annealing(lr, cosine_steps, lr / 1000.0)
        return optax.chain(optax.clip_by_global_norm(max_grad_norm),
                           optax.adam(lr))
    return optax.multi_transform(
        {"q": group(q_lr), "proposal": group(proposal_lr)},
        aql_param_labels)


def cosine_annealing(lr: float, t_max: int, eta_min: float):
    """torch ``CosineAnnealingLR`` value curve: eta_min + (lr - eta_min) *
    (1 + cos(pi * t / T_max)) / 2, held at eta_min past ``T_max`` (the
    closed form; the reference never steps past max_step)."""
    def schedule(count):
        t = jnp.minimum(count, t_max)
        return eta_min + (lr - eta_min) * 0.5 * (
            1.0 + jnp.cos(jnp.pi * t / t_max))
    return schedule
