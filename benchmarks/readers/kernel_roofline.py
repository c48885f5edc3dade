"""A kernel's share of its roofline: the least time the chip could take
for the calls the trace shows, over the device time they took.

``args``: ``patterns`` (substrings that an event's label must all hold)
and where the operations and bytes come from, one of

``per_step`` (functions of lib/counts.py whose least times add up to
one step's share of one layer) with ``shape`` (the driver's fact that
holds their arguments and ``layers``).  Each distinct instruction that
matches runs once a step, so events over distinct instructions is the
number of steps the trace holds, whole or cut at its edges.

``traced_rows`` (a function of lib/counts.py that takes the
configuration, a sum of contexts and a number of rows) with ``times``
and ``contexts`` (the driver's facts: when each row's token arrived, and
its live context).  The rows that arrived inside the traced part of the
window are counted, once for every layer of the configuration."""

from lib import counts, trace


def read(args, run):
    if run["peaks"] is None:
        return None
    n, names, seconds = trace.events_matching(run["trace"], args["patterns"])
    if not n or not seconds:
        return None
    facts = run["facts"]
    if "per_step" in args:
        shape = dict(facts[args["shape"]])
        calls = shape.pop("layers") * n / names
        work = [getattr(counts, fn)(**shape) for fn in args["per_step"]]
    else:
        a, b = run["traced"]
        inside = (facts[args["times"]] >= a) & (facts[args["times"]] <= b)
        rows = int(inside.sum())
        if not rows:
            return None
        calls = run["config"]["num_hidden_layers"]
        work = [getattr(counts, args["traced_rows"])(
            run["config"], int(facts[args["contexts"]][inside].sum()), rows)]
    least = sum(counts.least_seconds(ops, nbytes, run["peaks"])[0]
                for ops, nbytes in work)
    return 100.0 * least * calls / seconds
