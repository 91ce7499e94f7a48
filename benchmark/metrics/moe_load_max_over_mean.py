"""The busiest held expert's load over the mean of the held experts',
in the differentiated pass of an update (pairs summed over the expert
layers): the window's median of the program's counter, 1.0 = even."""

from benchmark import torso_scopes


def read(ctx):
    return torso_scopes.counter_median(ctx, "moe_load_max_over_mean")
