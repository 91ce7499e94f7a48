"""The double-gated short convolution's share of its roofline: the
operations and bytes the block needs for the passes the compiled update
makes of it (``costs_<family>.shortconv_macs`` / ``shortconv_bytes`` over
batch x context tokens, ``SHORTCONV_UNITS`` forward-sized passes a conv
layer: the differentiated pass's forward, the same made again where the
layer is rematerialised and a backward of twice a forward, and one forward
each for the next-state and target passes, times the conv layers), over
the chip's peaks, over the ``shortconv`` scope's device time (``conv``
inside it included) in the update programs.  Every pass runs the block on
every token, so the count does not depend on the run; it is of the two
projections, the two gates and the three taps, and of the least traffic
any implementation moves (its weights, ``h`` in and ``out`` out), so a
fused kernel reads the same work."""

from benchmark import costs, family_scopes


def read(ctx):
    peaks = ctx["peaks"]
    got = family_scopes.scope_seconds(ctx, "shortconv")
    if peaks is None or got is None:
        return None
    seconds, calls = got
    fam = costs.family_costs(ctx["config"]["family"])
    shapes = ctx["config"]["shapes"]
    tokens = shapes["batch"] * shapes["context"]
    passes = calls * fam.SHORTCONV_UNITS * fam.shortconv_layers(shapes)
    t_flops = (2 * passes * fam.shortconv_macs(shapes, tokens)
               / peaks["flops_per_s"])
    t_bytes = (fam.shortconv_bytes(shapes, tokens, passes)
               / peaks["bytes_per_s"])
    ctx["say"](f"shortconv: {seconds:.4f} s on the device in {calls} update "
               f"calls for {passes} forward-sized passes of {tokens} tokens;"
               f" least {t_flops:.4f} s by operations, {t_bytes:.4f} s by "
               f"bytes")
    return 100.0 * max(t_flops, t_bytes) / seconds
