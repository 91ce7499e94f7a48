"""Mean policy-wait fraction over the actor workers' latest
``ActorTimingStat`` (host clock in the worker); the env-step and drain
fractions and the workers' own frames/s on an earlier line."""


def read(ctx):
    stats = list(ctx["actor_timing"].values())
    if not stats:
        return None

    def mean(field):
        return sum(getattr(s, field) for s in stats) / len(stats)

    ctx["say"](f"actors ({len(stats)} workers): policy-wait "
               f"{100 * mean('policy_wait_frac'):.1f}%, env-step "
               f"{100 * mean('env_step_frac'):.1f}%, drain "
               f"{100 * mean('drain_frac'):.1f}%, "
               f"{sum(s.frames_per_sec for s in stats):.0f} frames/s by "
               f"their own clocks")
    return 100.0 * mean("policy_wait_frac")
