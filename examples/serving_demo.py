"""Continuous-batching inference server, end to end on a tiny GPT.

Starts an :class:`apex_tpu.serving.InferenceServer` over a randomly
initialized tiny GPT, submits a handful of requests with mixed prompt
lengths, budgets and sampling configs, streams each request's tokens as
they decode, and prints the server's throughput/occupancy metrics.

The interesting property on display: every request shape/config mix
runs through ONE compiled decode step and ONE mixed prefill step
(per-slot sampling params are device arrays, prompts ride as
fixed-width chunks) — the engine's retrace guards would raise if
anything recompiled mid-traffic.

With ``--replicas N`` (N > 1) the same traffic goes through a
:class:`apex_tpu.serving.FleetRouter` front door instead: N
replica servers, least-loaded health-gated routing by the
blocks-occupancy gauge, and per-replica metrics aggregated into one
fleet view (docs/fleet.md).

With ``--kv-dtype int8`` (or ``fp8`` where the jax build has
``float8_e4m3fn``) the server's KV pool is quantized: 1-byte pages +
per-page amax scales, ~2–4× the token capacity at equal HBM admitted
as occupancy (docs/serving.md).

With ``--tp M`` (M > 1) each replica spans M chips (tensor-parallel
serving, docs/serving.md): the KV pool shards on kv_heads, the
matmuls ride the GSPMD TP layers, and everything above — sharing,
drafting, quantized pages, the fleet router — is unchanged.  Composes
with ``--replicas N`` into an N×M fleet, each replica on its own
device slice.

With ``--plan auto`` (ISSUE 15) the replicas×tp split itself stops
being hand-set: ``apex_tpu.plan(cfg, devices, objective="serve")``
enumerates every equal-chip-count split through the GQA divisibility
gate, scores them on the unified traffic model (per-chip tokens/s,
the Gemma-paper unit), and the demo serves the winner.  Explicit
``--tp`` / ``--replicas`` flags still win; ``--chips`` bounds the
device budget the planner may spend (default: all attached).

Run (CPU works; --tp needs
XLA_FLAGS=--xla_force_host_platform_device_count=8 on CPU):
    python examples/serving_demo.py [--max-slots 2] [--requests 5]
    python examples/serving_demo.py --replicas 3 --requests 8
    python examples/serving_demo.py --kv-dtype int8 --requests 5
    python examples/serving_demo.py --tp 2 --replicas 2 --requests 6
    python examples/serving_demo.py --plan auto --chips 2
"""

from __future__ import annotations

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-slots", type=int, default=2)
    ap.add_argument("--requests", type=int, default=5)
    ap.add_argument("--replicas", type=int, default=None,
                    help="N > 1 serves through a FleetRouter over N "
                         "paged replica servers (unset + --plan auto "
                         "= planner's choice; defaults to 1)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-dtype", default=None,
                    choices=("int8", "fp8"),
                    help="quantize the paged KV pool (1-byte pages + "
                         "per-page amax scales; implies the paged "
                         "datapath on the single-server run)")
    ap.add_argument("--tp", type=int, default=None,
                    help="chips per replica (M > 1 = tensor-parallel "
                         "paged serving: the KV pool shards on "
                         "kv_heads, one replica spans M chips; "
                         "implies the paged datapath and composes "
                         "with --replicas into an NxM fleet; unset + "
                         "--plan auto = planner's choice; defaults "
                         "to 1)")
    ap.add_argument("--plan", choices=("auto",), default=None,
                    help="auto = route the replicas x tp split "
                         "through apex_tpu.plan(cfg, objective="
                         "'serve'); an explicit --tp/--replicas PINS "
                         "that axis and the planner picks among the "
                         "scored splits consistent with it")
    ap.add_argument("--chips", type=int, default=0,
                    help="with --plan auto: the chip budget the "
                         "planner may spend (0 = all attached "
                         "devices)")
    args = ap.parse_args()
    from apex_tpu.utils import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serving import FleetRouter, InferenceServer, tp_mesh
    from apex_tpu.utils import MetricsWriter

    cfg = GPTConfig.tiny(position_embedding="learned",
                         scan_layers=True)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    params = {"params": params["params"]}

    rng = np.random.default_rng(args.seed)
    metrics = MetricsWriter(sink=lambda step, row: print(
        f"metrics step={step} " + " ".join(
            f"{k}={v:.3g}" for k, v in sorted(row.items()))))

    # mixed traffic: lengths from under one prefill chunk to several,
    # greedy and sampled tenants side by side in the same compiled step
    configs = [
        {"length": 3, "max_new_tokens": 6, "temperature": 0.0},
        {"length": 7, "max_new_tokens": 4, "temperature": 0.8,
         "top_k": 20},
        {"length": 12, "max_new_tokens": 5, "temperature": 1.2,
         "top_k": 5},
        {"length": 2, "max_new_tokens": 7, "temperature": 0.0},
        {"length": 9, "max_new_tokens": 3, "temperature": 0.5},
    ]
    configs = [configs[i % len(configs)] for i in range(args.requests)]

    def submit_and_stream(front):
        handles = []
        for i, c in enumerate(configs):
            prompt = rng.integers(0, cfg.vocab_size,
                                  size=(c["length"],))
            h = front.submit(
                prompt,
                max_new_tokens=c["max_new_tokens"],
                temperature=c["temperature"],
                top_k=c.get("top_k"),
                seed=i)
            handles.append((i, prompt, h))
        for i, prompt, h in handles:
            toks = list(h.stream(timeout=600))
            print(f"req {i} prompt={prompt.tolist()} -> {toks}")
        return handles

    if args.tp is not None and args.tp < 1:
        raise SystemExit(f"--tp must be >= 1, got {args.tp}")
    devices = jax.devices()
    if (args.tp or 1) > len(devices):
        raise SystemExit(
            f"--tp {args.tp} needs {args.tp} devices, found "
            f"{len(devices)} (on CPU run with XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8)")

    block_size = 8                 # the demo's page size
    if args.plan == "auto" and (args.tp is None
                                or args.replicas is None):
        # ISSUE 15: enumerate the replicas×tp splits over the chip
        # budget, score per-chip tokens/s on the unified traffic
        # model, and serve the winner.  An explicit flag PINS its
        # axis: the choice is then made among the planner's own
        # scored splits consistent with the pin — never a grafted
        # split no score ever evaluated.
        import apex_tpu

        chips = args.chips or len(devices)
        if chips < 1 or chips > len(devices):
            raise SystemExit(
                f"--chips {args.chips} must be between 1 and the "
                f"{len(devices)} attached device(s)")
        planned = apex_tpu.plan(cfg, devices=devices[:chips],
                                objective="serve",
                                slots=args.max_slots)
        cands = [planned.score] + planned.alternatives
        if args.tp is not None:
            cands = [s for s in cands
                     if s["layout"].tp == args.tp]
        if args.replicas is not None:
            cands = [s for s in cands
                     if s["layout"].dp == args.replicas]
        if not cands:
            raise SystemExit(
                f"--plan auto: no feasible {chips}-chip split "
                f"matches the pinned flags (tp={args.tp}, "
                f"replicas={args.replicas}) — scored splits: "
                + ", ".join(s["layout"].describe()
                            for s in [planned.score]
                            + planned.alternatives))
        best = cands[0]           # already sorted best-first
        print(f"plan: auto -> {best['layout'].describe()} "
              f"({best['value']:.0f} tokens/s/chip modeled, "
              f"{len(planned.alternatives)} alternatives scored)")
        args.tp = best["layout"].tp
        args.replicas = best["layout"].dp
        tuned = best.get("autotune") or {}
        if tuned.get("autotuned") and args.kv_dtype in (
                None, tuned["kv_dtype"]):
            # serve the pool the score (and the feasibility gate) was
            # computed with — dropping the tuned (block_size,
            # kv_dtype) would launch an engine up to ~2-4x the
            # modeled pool bytes on the very split those bytes
            # approved.  An explicit --kv-dtype that DISAGREES with
            # the tuned storage dtype wins whole: block sizes are
            # swept per storage dtype (the engine's own key
            # discipline), so the tuned block must not be mixed with
            # a different pool width.
            block_size = tuned["block_size"]
            if args.kv_dtype is None:
                args.kv_dtype = tuned["kv_dtype"]
    args.tp = args.tp or 1
    args.replicas = args.replicas or 1

    if args.replicas > 1:
        import itertools

        replica_idx = itertools.count()

        def factory():
            mesh = None
            if args.tp > 1:
                # each replica gets its own tp-wide device slice
                # (wrapping when the fleet overcommits the host —
                # fine on CPU smoke, a real pod sizes N*M to fit)
                off = next(replica_idx) * args.tp
                mesh = tp_mesh(args.tp, [
                    devices[(off + j) % len(devices)]
                    for j in range(args.tp)])
            return InferenceServer(
                model, params, max_slots=args.max_slots,
                block_size=block_size, prefill_chunk=4,
                pool_tokens=args.max_slots * cfg.max_seq_len,
                kv_dtype=args.kv_dtype, mesh=mesh,
                metrics_interval=4)

        router = FleetRouter(factory, replicas=args.replicas,
                             probe_interval=0.1, metrics=metrics,
                             metrics_interval=1)
        with router:
            handles = submit_and_stream(router)
            stats = router.stats()
            health = router.health()
            print(f"fleet: replicas={args.replicas} "
                  f"ready={health['replicas_ready']} "
                  f"chips_per_replica={health['chips_per_replica']} "
                  f"chips_total={health['chips_total']} "
                  f"migrated={stats['migrated']}")
        print(f"done: {len(handles)} requests, "
              f"{stats['tokens_total']} tokens across "
              f"{args.replicas} replicas x "
              f"{health['chips_per_replica']} chips")
        return

    server = InferenceServer(
        model, params, max_slots=args.max_slots,
        block_size=block_size, prefill_chunk=4,
        kv_dtype=args.kv_dtype, tp=args.tp if args.tp > 1 else 0,
        metrics=metrics, metrics_interval=4)
    with server:
        handles = submit_and_stream(server)
        if args.kv_dtype is not None:
            h = server.health()
            print(f"kv: dtype={h['kv_dtype']} bits={h['kv_bits']} "
                  f"pool_tokens={server.engine.pool_tokens}")
        if args.tp > 1:
            h = server.health()
            print(f"tp: chips_per_replica={h['chips_per_replica']} "
                  f"mesh_shape={h['mesh_shape']}")
    print(f"done: {len(handles)} requests, "
          f"{server.tokens_emitted} tokens in {server.steps} steps")


if __name__ == "__main__":
    main()
