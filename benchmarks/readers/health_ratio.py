"""A ratio of differences of ``InferenceServer.health()`` between the
window's two ends: ``scale x (sum of num - sum of minus) / den``.

``args``: ``num`` and optionally ``minus`` (lists of dotted paths into
the health dict, such as ``spans.apex/engine/plan.s`` or ``prefill_s``),
``den`` (one path; left out, the difference itself is the number) and
``scale`` (default 1).  ``None`` where ``den`` did not move, and where
the program's ``health()`` has no such field at all (the parent of the
PR that brought the field).  A path that is misspelt below a field that
is there is an error, not 0."""


def lookup(health, path):
    for key in path.split("."):
        health = health[key]
    return health


def read(args, run):
    before = run["facts"]["health_before"]
    after = run["facts"]["health_after"]
    num, minus = args["num"], args.get("minus", [])
    paths = num + minus + ([args["den"]] if "den" in args else [])
    if any(p.split(".")[0] not in after for p in paths):
        return None

    def moved(path):
        return lookup(after, path) - lookup(before, path)

    value = sum(map(moved, num)) - sum(map(moved, minus))
    if "den" in args:
        den = moved(args["den"])
        if not den:
            return None
        value /= den
    return args.get("scale", 1) * value
