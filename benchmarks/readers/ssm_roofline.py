"""A state-space kernel's share of its roofline: the least time the chip
could take for the calls the trace shows, over the device time they
took.  Every event that matches is one call: one layer of one step.

``args``: ``patterns`` (substrings that an event's label must all
hold), ``count`` (the function of lib/counts_falcon_h1.py that gives
one call's operations and bytes) and ``shape`` (the driver's fact that
holds its arguments).  ``None`` off a chip, and where the trace holds
no such kernel (a program without it)."""

from lib import counts, counts_falcon_h1, trace


def read(args, run):
    if run["peaks"] is None:
        return None
    n, _, seconds = trace.events_matching(run["trace"], args["patterns"])
    shape = run["facts"].get(args["shape"])
    if not n or not seconds or shape is None:
        return None
    ops, nbytes = getattr(counts_falcon_h1, args["count"])(**shape)
    least, _ = counts.least_seconds(ops, nbytes, run["peaks"])
    return 100.0 * least * n / seconds
