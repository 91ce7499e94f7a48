"""The step programs' share of their roofline: the least time the chip
could take for the operations and bytes their calls need (benchmark's own
shape functions), over their device time in the trace.  An earlier line
says which bound it is."""

from benchmark import costs


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    if trace is None or peaks is None:
        return None
    flops = nbytes = seconds = 0.0
    for name, prog in ctx["traffic"]["step_programs"].items():
        seen = trace["programs"].get(name)
        if not seen:
            continue
        cost = costs.program_cost(ctx["config"], prog)
        flops += seen["calls"] * cost["flops"]
        nbytes += seen["calls"] * cost["bytes"]
        seconds += seen["seconds"]
    if seconds <= 0.0:
        return None
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["bytes_per_s"]
    ctx["say"](f"step programs: {seconds:.4f} s on the device; least "
               f"{t_flops:.4f} s by operations, {t_bytes:.4f} s by bytes: "
               f"{'compute' if t_flops >= t_bytes else 'memory'}-bound")
    return 100.0 * max(t_flops, t_bytes) / seconds
