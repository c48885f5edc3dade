"""Median device time of one run of a program (the trace's ``XLA
Modules`` line), in milliseconds, for the programs whose name holds
``args["pattern"]``."""

import statistics


def read(args, run):
    runs = [s for name, v in run["trace"]["modules"].items()
            if args["pattern"] in name for s in v]
    return 1e3 * statistics.median(runs) if runs else None
