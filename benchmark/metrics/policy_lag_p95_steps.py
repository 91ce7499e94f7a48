"""95th percentile, in learner steps, of how old the policy was that a
consumed chunk was sent under: the ``lag_steps`` of the window's
``consume`` events (``apex_tpu.obs.spans.LearnerObs``).  The version is
the one the worker held when it SENT the chunk, so this is the lag of the
chunk's last transitions and a lower bound for its first.  Median and
count on an earlier line."""

import statistics

from benchmark import spans


def read(ctx):
    lags = spans.policy_lag(spans.load(ctx)["ring"])
    if not lags:
        return None
    ctx["say"](f"policy lag over {len(lags)} consumed chunks: median "
               f"{statistics.median(lags):.0f} steps, max {max(lags):.0f}")
    if len(lags) < 200:
        return None
    return statistics.quantiles(lags, n=20)[-1]
