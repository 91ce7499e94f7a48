"""Model FLOP/s utilization of the whole step: operations the forward and
backward passes need per learner update (acting forwards included where
the rollout is on the device) x updates per second of the window, over
chips x peak."""

from benchmark import costs


def read(ctx):
    peaks = ctx["peaks"]
    if peaks is None:
        return None
    steps = ctx["close"]["steps"] - ctx["open"]["steps"]
    per_step = costs.program_cost(ctx["config"], dict(
        learner_steps=1, acting_forwards=ctx["traffic"].get(
            "acting_forwards_per_learner_step", 0)))
    rate = steps / ctx["window_s"]
    return 100.0 * per_step["flops"] * rate / (
        ctx["chips"] * peaks["flops_per_s"])
