"""The chunked scan's share of its roofline: the operations and bytes the
scan needs for the passes the compiled update makes of it
(``costs_<family>.ssd_macs`` / ``ssd_bytes`` over batch x context tokens,
``SSD_UNITS`` forward-sized passes a Mamba-2 layer: the differentiated
pass's forward, the same made again when the layer is rematerialised and a
backward of twice a forward, and one forward each for the next-state and
target passes), over the chip's peaks, over the ``ssd`` scope's device
time in the update programs.  Every pass runs the scan on every token, so
the count does not depend on the run; it is of the chunked algorithm at
the config's ``chunk_size``, causal inside a chunk, and of the least
traffic any implementation moves, so plain XLA reads low and a kernel that
keeps a chunk's blocks on the chip reads the same work."""

from benchmark import costs, nemotron_h_scopes


def read(ctx):
    peaks = ctx["peaks"]
    got = nemotron_h_scopes.scope_seconds(ctx, "ssd")
    if peaks is None or got is None:
        return None
    seconds, calls = got
    fam = costs.family_costs(ctx["config"]["family"])
    shapes = ctx["config"]["shapes"]
    tokens = shapes["batch"] * shapes["context"]
    passes = calls * fam.SSD_UNITS * fam.mamba_layers(shapes)
    t_flops = 2 * passes * fam.ssd_macs(shapes, tokens) / peaks["flops_per_s"]
    t_bytes = fam.ssd_bytes(shapes, tokens, passes) / peaks["bytes_per_s"]
    ctx["say"](f"ssd: {seconds:.4f} s on the device in {calls} update calls "
               f"for {passes} forward-sized passes of {tokens} tokens; least "
               f"{t_flops:.4f} s by operations, {t_bytes:.4f} s by bytes")
    return 100.0 * max(t_flops, t_bytes) / seconds
