"""The on-device rollout program's device seconds as a share of the
device's busy seconds in the traced span: what acting on the learner's
chip costs the learner."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    acting = [trace["programs"][name] for name, prog
              in ctx["traffic"]["step_programs"].items()
              if prog.get("acting_forwards") and not prog.get("learner_steps")
              and name in trace["programs"]]
    if not acting:
        return None
    calls = sum(p["calls"] for p in acting)
    seconds = sum(p["seconds"] for p in acting)
    ctx["say"](f"rollout program: {calls} calls, {seconds:.4f} s of the "
               f"device's {trace['busy_s']:.4f} busy seconds")
    return 100.0 * seconds / trace["busy_s"]
