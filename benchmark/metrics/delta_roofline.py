"""The chunked delta rule's share of its roofline: the operations and
bytes the rule needs for the passes the compiled update makes of it
(``costs_<family>.delta_macs`` / ``delta_bytes`` over batch x context
tokens, ``DELTA_UNITS`` forward-sized passes a Gated DeltaNet layer: the
differentiated pass's forward, the same made again when the layer is
rematerialised and a backward of twice a forward, and one forward each
for the next-state and target passes), over the chip's peaks, over the
``delta`` scope's device time in the update programs.  Every pass runs the
rule on every token, so the count does not depend on the run; it is of the
chunked algorithm at the config's ``chunk_size``, causal inside a chunk,
the triangular system SOLVED for its two right-hand sides (not an inverse
formed by repeated squaring), and of the least traffic any implementation
moves, so plain XLA reads low and a kernel that keeps a chunk's blocks on
the chip reads the same work."""

from benchmark import costs, family_scopes


def read(ctx):
    peaks = ctx["peaks"]
    got = family_scopes.scope_seconds(ctx, "delta")
    if peaks is None or got is None:
        return None
    seconds, calls = got
    fam = costs.family_costs(ctx["config"]["family"])
    shapes = ctx["config"]["shapes"]
    tokens = shapes["batch"] * shapes["context"]
    passes = calls * fam.DELTA_UNITS * fam.gdn_layers(shapes)
    t_flops = (2 * passes * fam.delta_macs(shapes, tokens)
               / peaks["flops_per_s"])
    t_bytes = fam.delta_bytes(shapes, tokens, passes) / peaks["bytes_per_s"]
    ctx["say"](f"delta: {seconds:.4f} s on the device in {calls} update "
               f"calls for {passes} forward-sized passes of {tokens} tokens; "
               f"least {t_flops:.4f} s by operations, {t_bytes:.4f} s by "
               f"bytes")
    return 100.0 * max(t_flops, t_bytes) / seconds
