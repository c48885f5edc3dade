"""The routed experts' grouped products' share of their roofline: the
least time the chip could take for the products the trace shows, over
the device time they took.

The least time of the WINDOW's products follows from what the program
counted between its two ends (``health()``: the assignments that landed
on held experts, the (layer, expert) pairs that got any, the
layer-steps) through ``lib/counts_afmoe.py::expert_products``, the same
work whatever implements it; the trace holds ``events /
events_per_layer_step`` of the window's layer-steps, and that share of
the least time stands over the events' device time.

``args``: ``patterns`` (substrings that an event's label must all
hold) and ``events_per_layer_step`` (products a layer runs a step).
``None`` off a chip, where the trace holds no such operation and where
the program's ``health()`` has no such counter."""

from lib import counts, counts_afmoe, trace


def read(args, run):
    if run["peaks"] is None:
        return None
    n, _, seconds = trace.events_matching(run["trace"], args["patterns"])
    before = run["facts"]["health_before"]
    after = run["facts"]["health_after"]
    keys = ("expert_assignments", "experts_active", "expert_layer_steps")
    if not n or not seconds or any(k not in after for k in keys):
        return None
    assigned, active, layer_steps = (after[k] - before[k] for k in keys)
    if not layer_steps:
        return None
    ops, nbytes = counts_afmoe.expert_products(run["config"], assigned,
                                               active)
    least, _ = counts.least_seconds(ops, nbytes, run["peaks"])
    traced = n / args["events_per_layer_step"] / layer_steps
    return 100.0 * least * traced / seconds
