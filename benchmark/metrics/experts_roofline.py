"""The routed experts' grouped products' share of their roofline: the
operations and bytes the pairs the run really routed to held experts need
(``costs_<family>.expert_macs`` over the program's ``moe_local_pairs``
counters, each pass weighed by the forward-sized runs of the products the
compiled update makes for it, ``EXPERT_UNITS``: 4 for the differentiated
pass, rematerialisation included, 1 for each forward-only pass), over the
chip's peaks, over the ``experts`` scope's device time in the update
programs (the grouped kernels, placed by name, and the masks, ``silu`` and
converts around them).  It follows the routing the run had, so uneven
routing cannot push it past 100.  The kernel XLA:TPU makes of
``lax.ragged_dot`` is what the scope holds today; a Pallas kernel would
read the same."""

from benchmark import costs, torso_scopes


def read(ctx):
    peaks, red = ctx["peaks"], torso_scopes.load(ctx)
    seen = torso_scopes.counters(ctx)
    if peaks is None or red is None or not seen:
        return None
    progs = torso_scopes.update_programs(ctx, red)
    calls = sum(p["calls"] for p in progs)
    seconds = sum(p["scopes"]["experts"] for p in progs)
    if not calls or seconds <= 0.0:
        return None
    fam = costs.family_costs(ctx["config"]["family"])
    shapes = ctx["config"]["shapes"]
    # an update's weighted pairs, the window's mean, times the updates the
    # trace holds
    units = fam.EXPERT_UNITS
    rows = [c for c in seen if all(k in c for k in units)]
    if not rows:
        return None
    pairs = calls * sum(n * c[k] for c in rows
                        for k, n in units.items()) / len(rows)
    m = shapes["model"]
    expert_layers = m["num_hidden_layers"] - m["first_k_dense_replace"]
    t_flops = 2 * fam.expert_macs(shapes, pairs) / peaks["flops_per_s"]
    t_bytes = fam.expert_bytes(
        shapes, pairs, calls * sum(units.values()) * expert_layers) \
        / peaks["bytes_per_s"]
    ctx["say"](f"experts: {seconds:.4f} s on the device in {calls} update "
               f"calls for {pairs:.0f} weighted pairs; least "
               f"{t_flops:.4f} s by operations, {t_bytes:.4f} s by bytes")
    return 100.0 * max(t_flops, t_bytes) / seconds
