"""Share of the device's busy time that the operations whose label
holds every substring of ``args["patterns"]`` took, in per cent.
``None`` where the trace holds no such operation."""

from lib import trace


def read(args, run):
    n, _, seconds = trace.events_matching(run["trace"], args["patterns"])
    if not n or not run["trace"]["busy_s"]:
        return None
    return 100.0 * seconds / run["trace"]["busy_s"]
