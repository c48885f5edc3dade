"""Numbers from ``InferenceServer.health()`` read at the window's two
ends: ``engine_step_ms`` (window over steps), ``occupancy`` (tokens
emitted over steps x slots), ``preempts``."""


def read(args, run):
    f = run["facts"]
    d = {k: f["health_after"][k] - f["health_before"][k]
         for k in ("steps", "tokens_emitted", "preempts")}
    kind = args["kind"]
    if kind == "preempts":
        return d["preempts"]
    if not d["steps"]:
        return None
    if kind == "engine_step_ms":
        return 1e3 * f["measured_s"] / d["steps"]
    if kind == "occupancy":
        return 100.0 * d["tokens_emitted"] / (d["steps"] * f["max_slots"])
    raise ValueError(f"unknown counter metric {kind!r}")
