"""Per-config BASELINE benchmarks (BASELINE.json configs[0..4]).

One config per process: a chip belongs to one process at a time, and
a fresh process starts every leg from an empty HBM.  The orchestrating
parents therefore never touch jax; only the per-leg children do.
Measurement hygiene is shared with bench.py (multi-window best-of,
agreement retry), and every row names the device it ran on.

Usage:
    python bench_configs.py resnet50_o1            # one leg, real chip
    python bench_configs.py gpt2_tp8_full_step     # CPU full-size step
    python bench_configs.py all                    # drives each leg in
                                                   # a fresh subprocess,
                                                   # writes BENCH_CONFIGS.json

Legs (reference workloads per BASELINE.json):
  resnet50_o1        ResNet-50, amp O1 + FusedSGD           (configs[0])
  resnet50_syncbn    + DDP shard_map step + SyncBatchNorm   (configs[1..2])
  bert_o1            BERT-Large, amp O1 interceptor + FusedAdam, +
                     grad-sync bytes-on-wire model and the measured
                     bert_o1_ddp int8-allreduce A/B child (ROADMAP 2b)
  bert_o1_zero       ZeRO-2 A/B child (ISSUE 11): replicated vs
                     sharded optimizer state at O2 — hbm_peak +
                     state-bytes drop, grown-batch samples/sec, and
                     the _zero_bytes_on_wire wire/residency model
  gpt2_1p3b          GPT-2 1.3B-family single-chip proxy    (configs[3])
                     (BENCH_GPT_VARIANT: base/noselect/fused_cast —
                     the round-5 optimizer-overlap experiment)
  gpt2_tp8_full_step full 1.3B TP=8+SP step EXECUTED, CPU   (configs[3])
  gpt2_3d_full_step  full 1.3B tp2×pp2×dp2 1F1B step, CPU   (configs[3])
  mistral7b_tp8_full_step  full 7.24B GQA step EXECUTED, CPU mesh
  llama_1b           1.03B GQA+SwiGLU recipe + GQA/MLP A/B rows
  decode             llama_1b generate(): prefill + decode tokens/s,
                     bytes/token roofline, blocked-vs-einsum A/B
  prefix_spec_serving  CoW prefix sharing A/B at equal HBM (tokens/s,
                     TTFT, pool capacity shared vs unshared) + the
                     prompt-lookup speculative-decoding tokens/step
  quantized_kv_serving  int8 KV pages at equal HBM: 2x slots in the
                     same bytes (capacity >= 1.9x asserted), tokens/s
                     + TTFT A/B vs the unquantized paged pool
  resilience_overhead  ResilientLoop + async rolling checkpoints vs
                     the bare train loop (target <2% at ckpt-every-100)
  fleet_serving      multi-replica FleetRouter tokens/s + TTFT p50/p99
                     per chip at fixed SLO, 1 vs 3 replicas, plus a
                     kill-at-midpoint resilience row
  vit_huge_lamb      ViT-H/14, amp O2 + FusedLAMB           (configs[4])
  long_context       8k/16k/32k/32k-windowed ladder, phase-sum bounds
  group_norm         GN+SiLU fwd+bwd achieved GB/s
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys

import bench

# ISSUE-15: the analytic cost models below grew up bench-local (each
# beside the leg that measured it); they are now LIBRARY code — the
# apex_tpu.plan planner scores layouts with the same arithmetic — so
# the single implementation lives in apex_tpu/plan/costs.py and the
# bench imports it back under the historical names.  Zero drift is
# regression-gated: tests/test_plan.py::TestCostModelDedup
# byte-compares these functions' outputs (and the recorded bench
# rows' model blocks) against the lifted implementations.  Importing
# apex_tpu does not initialize a jax backend — the orchestrator
# parent still never holds the chip; only the per-leg children do.
from apex_tpu.plan.costs import (           # noqa: E402
    ddp_bytes_on_wire as _ddp_bytes_on_wire,
    resnet_traffic_model as _resnet_traffic_model,
    serving_traffic_model as _serving_traffic_model,
    zero_bytes_on_wire as _zero_bytes_on_wire,
)


def _backend_initialised() -> bool:
    """Whether THIS process has asked jax for devices (and so holds
    the chip, where there is one)."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    return bridge is not None and bool(bridge._backends)


def _child_devices(row):
    """The distinct ``device`` blocks of the child rows nested in
    ``row`` (an orchestrating parent's own row names those)."""
    found = []
    for v in row.values():
        if isinstance(v, dict):
            for dev in ([v["device"]] if "device" in v
                        else _child_devices(v)):
                if dev not in found:
                    found.append(dev)
    return found


def _errors_in(row):
    """Every ``error`` recorded in ``row`` or the child rows nested
    in it, as ``(path, message)`` pairs."""
    out = []
    for k, v in row.items():
        if k == "error":
            out.append(("", str(v)))
        elif isinstance(v, dict):
            out += [(f"{k}/{p}".rstrip("/"), m)
                    for p, m in _errors_in(v)]
    return out


def _emit(d):
    """Print one result row, naming the device it ran on: this
    process's, or — from an orchestrating parent, which never touches
    jax — its children's."""
    if _backend_initialised():
        device = bench.device_fields()
    else:
        devices = _child_devices(d)
        device = devices[0] if len(devices) == 1 else devices
    print(json.dumps(dict(d, device=device)))


def _run_child(leg, env_overrides=None, timeout=1800):
    """Run one leg in a fresh subprocess and parse its last JSON line.

    The single implementation behind every orchestrator (llama_1b /
    decode / long_context rows and _run_all): a fresh process per
    measurement starts from an empty HBM.  Returns a result dict;
    timeouts and non-zero exits become ``{"error": ...}`` rows so
    sibling measurements are never lost (``_run_all`` then exits
    non-zero)."""
    env = dict(os.environ)
    for k, v in (env_overrides or {}).items():
        if v is None:
            env.pop(k, None)            # None = remove from child env
        else:
            env[k] = v
    # one process per chip: a parent that has touched jax holds the
    # chip, and a child that needs it then fails or hangs
    if env.get("JAX_PLATFORMS") != "cpu" and _backend_initialised():
        raise RuntimeError(
            f"leg {leg!r} needs the chip but this parent process has "
            f"initialised a jax backend and holds it — orchestrate "
            f"from a process that never asks jax for devices")
    try:
        proc = subprocess.run(
            [sys.executable, __file__, leg], env=env,
            capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {timeout}s"}
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    if proc.returncode != 0 or not lines:
        return {"error": (proc.stderr or proc.stdout or "?")[-2000:]}
    return json.loads(lines[-1])


def _measure(state, step, batch, samples_per_step, extra=None,
             measured_tflops=None, phase_bounds=None):
    n_steps = int(os.environ.get("BENCH_STEPS", "20"))
    k_windows = max(1, int(os.environ.get("BENCH_WINDOWS", "3")))
    # AOT-compile: the executable doubles as the memory/cost analysis
    # source (fills hbm_peak on backends without memory_stats, and the
    # roofline self-check fields)
    compiled = bench._aot_compile(step, state, *batch)
    dt, dts, loss, finite, _ = bench._measure_step(
        state, compiled, batch, n_steps, k_windows)
    out = {
        "value": round(samples_per_step / dt, 3),
        "unit": "samples/sec/chip",
        "step_ms": round(dt * 1e3, 2),
        "window_ms": [round(d * 1e3, 2) for d in dts],
        "loss_finite": finite,
    }
    out.update(bench._memory_fields(compiled))
    out.update(bench._roofline_fields(compiled, dt,
                                      measured_tflops=measured_tflops,
                                      phase_bounds=phase_bounds))
    out.update(extra or {})
    return out


# ----------------------------------------------------------------- ResNet-50

# (lifted to apex_tpu/plan/costs.py — imported back above as _resnet_traffic_model)


def _build_resnet(opt_level, sync_bn):
    """ResNet-50 train state (examples/imagenet/main_amp.py workload).

    BENCH_RESNET_FUSED_BN=1 routes BN through the fused kernels
    (ops/batch_norm.py); BENCH_RESNET_STEM=s2d swaps in the MLPerf
    space-to-depth stem — the ISSUE-3 A/B levers.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.models.resnet import ResNet, ResNetConfig
    from apex_tpu.optim import fused_sgd

    # b=128 measured fastest (round-3 sweep: 64 -> 2184, 128 -> 2461,
    # 256 -> 2363 samples/s) — bigger batches amortize the BN stat
    # passes until activations blow the ~10 GB working set
    b = int(os.environ.get("BENCH_BATCH", "128"))
    size = int(os.environ.get("BENCH_IMAGE", "224"))
    cfg = ResNetConfig(
        num_classes=1000,
        bn_axis_names=("data",) if sync_bn else None,
        dtype=jnp.bfloat16 if opt_level in ("O1", "O2", "O3")
        else jnp.float32,
        fused_bn=os.environ.get("BENCH_RESNET_FUSED_BN") == "1",
        stem=os.environ.get("BENCH_RESNET_STEM", "conv"))
    model = ResNet(cfg)

    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.normal(size=(b, size, size, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 1000, size=(b,)))

    variables = model.init(jax.random.PRNGKey(0), images[:2], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    def apply_fn(p, x, bs):
        return model.apply({"params": p, "batch_stats": bs}, x,
                           train=True, mutable=["batch_stats"])

    state = amp.initialize(
        apply_fn, params,
        fused_sgd(0.1, momentum=0.9, weight_decay=1e-4),
        opt_level=opt_level)
    return model, state, batch_stats, (images, labels), b


# the fused-BN × s2d-stem A/B grid (ISSUE 3): each row runs in a fresh
# child process (HBM is not reclaimed promptly across builds)
_RESNET_VARIANTS = {
    # both keys always explicit (None = remove from the child env) so
    # an ambient BENCH_RESNET_* can't leak into the wrong row
    "base": {"BENCH_RESNET_FUSED_BN": None, "BENCH_RESNET_STEM": None},
    "fused_bn": {"BENCH_RESNET_FUSED_BN": "1",
                 "BENCH_RESNET_STEM": None},
    "s2d": {"BENCH_RESNET_FUSED_BN": None,
            "BENCH_RESNET_STEM": "s2d"},
    "fused_bn_s2d": {"BENCH_RESNET_FUSED_BN": "1",
                     "BENCH_RESNET_STEM": "s2d"},
}


def _resnet_ab(leg, variants):
    """Orchestrate the fused/s2d A/B rows for a resnet leg; the main
    row is the fully-fused config (the production recommendation), and
    ``ab`` quantifies each lever against base on the shared bn_real
    bound."""
    rows = {}
    for name in variants:
        rows[name] = _run_child(
            leg, dict(_RESNET_VARIANTS[name],
                      BENCH_RESNET_VARIANT="1"), timeout=2700)
    main = dict(rows.get("fused_bn_s2d") or {})
    ab = {}
    base = rows.get("base") or {}
    for name in variants:
        row = rows.get(name) or {}
        if name != "base" and row.get("value") and base.get("value"):
            ab[f"{name}_vs_base_speedup"] = round(
                row["value"] / base["value"], 3)
        if row.get("roofline_frac") is not None:
            ab[f"{name}_frac_of_bn_real"] = row["roofline_frac"]
    _emit({
        "metric": main.get("metric", leg) + "_ab",
        "value": main.get("value"),
        "unit": "samples/sec/chip (fused_bn + s2d stem)",
        "rows": rows,
        "ab": ab,
    })


def bench_resnet50_o1():
    import jax
    import jax.numpy as jnp

    if not os.environ.get("BENCH_RESNET_VARIANT"):
        _resnet_ab("resnet50_o1",
                   ("base", "fused_bn", "s2d", "fused_bn_s2d"))
        return

    _, state, batch_stats, (images, labels), b = _build_resnet("O1", False)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(carry, x, y):
        state, bs = carry

        def loss_fn(p):
            logits, mut = state.apply_fn(p, x, bs)
            onehot = jax.nn.one_hot(y, 1000)
            loss = -jnp.mean(jnp.sum(
                jax.nn.log_softmax(logits.astype(jnp.float32)) * onehot,
                axis=-1))
            return state.scale_loss(loss), (loss, mut["batch_stats"])

        grads, (loss, new_bs) = jax.grad(
            loss_fn, has_aux=True)(state.compute_params())
        new_state, finite = state.apply_gradients(grads=grads)
        return (new_state, new_bs), loss, finite

    out = _measure((state, batch_stats), step, (images, labels), b,
                   {"batch": b})
    _resnet_rescore(out, b)
    out["metric"] = "resnet50_imagenet_O1_fusedsgd_samples_per_sec_per_chip"
    _emit(out)


def _resnet_rescore(out, b):
    """Re-score roofline_frac against the analytic traffic model (see
    :func:`_resnet_traffic_model`); the XLA cost-model frac stays as a
    diagnostic.  Guarantees frac ≤ 1 up to clock noise and makes the
    near-ceiling resnet captures certify something real.  The frac is
    ALWAYS vs ``bn_real`` (so fused/unfused A/B rows share one bound);
    fused rows additionally record their kernels' own mandated bytes
    (``bn_fused_kernel``)."""
    import jax

    fused = os.environ.get("BENCH_RESNET_FUSED_BN") == "1"
    out["fused_bn"] = fused
    out["stem"] = os.environ.get("BENCH_RESNET_STEM", "conv")
    if jax.default_backend() != "tpu":
        return          # rooflines are chip certifications; CPU runs
    tm = _resnet_traffic_model(
        b, int(os.environ.get("BENCH_IMAGE", "224")), fused_bn=fused)
    dt = out["step_ms"] / 1e3
    t_hbm_real = tm["bn_real"] / (bench.chip_peaks()["hbm_gbs"] * 1e9)
    t_mxu = out.get("mxu_bound_frac", 0.0) * dt
    out["roofline_frac_costmodel"] = out.get("roofline_frac")
    out["roofline_frac"] = round(max(t_mxu, t_hbm_real) / dt, 3)
    out["roofline_bound"] = ("analytic_traffic_bn_real"
                             if t_hbm_real >= t_mxu else "mxu")
    out["analytic_traffic_bytes"] = tm
    out["traffic_model_note"] = (
        "frac scored vs the architecture's analytic bn_real traffic "
        "bound (conv act passes + unfusable BN stat passes + "
        "param/optimizer state); the XLA cost-model frac "
        "(roofline_frac_costmodel) overcounts fusion-internal bytes "
        "and is diagnostic only")


def bench_resnet50_syncbn():
    """The DDP + SyncBatchNorm leg: the full shard_map data-parallel
    step (explicit grad all-reduce, cross-replica BN stats) on the
    ``data`` mesh axis — world size = however many chips the process
    has (1 on a one-chip machine; the multi-device path is exercised
    on the 8-device CPU mesh in tests/test_parallel.py)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.core import mesh as mesh_lib
    from apex_tpu.parallel import all_reduce_mean_grads

    if not os.environ.get("BENCH_RESNET_VARIANT"):
        # 2-row A/B (base vs fully fused): the per-lever split is the
        # o1 leg's job; this leg certifies the psum'd fused-stats path
        _resnet_ab("resnet50_syncbn", ("base", "fused_bn_s2d"))
        return

    mesh = mesh_lib.initialize_mesh(data_parallel_size=-1)
    _, state, batch_stats, (images, labels), b = _build_resnet("O1", True)

    def shard_step(carry, x, y):
        state, bs = carry

        def loss_fn(p):
            logits, mut = state.apply_fn(p, x, bs)
            onehot = jax.nn.one_hot(y, 1000)
            loss = -jnp.mean(jnp.sum(
                jax.nn.log_softmax(logits.astype(jnp.float32)) * onehot,
                axis=-1))
            return state.scale_loss(loss), (loss, mut["batch_stats"])

        grads, (loss, new_bs) = jax.grad(
            loss_fn, has_aux=True)(state.compute_params())
        grads = all_reduce_mean_grads(grads)   # explicit DDP all-reduce
        new_state, finite = state.apply_gradients(grads=grads)
        return (new_state, new_bs), loss, finite

    sharded = jax.shard_map(
        shard_step, mesh=mesh,
        in_specs=((P(), P()), P("data"), P("data")),
        out_specs=((P(), P()), P(), P()),
        check_vma=False)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(carry, x, y):
        return sharded(carry, x, y)

    world = mesh.shape["data"]
    with mesh:
        # per-chip throughput: the global batch is sharded over `world`
        out = _measure((state, batch_stats), step, (images, labels),
                       b / world, {"batch": b, "world": world})
    # per-chip traffic: each chip streams the activations of its own
    # b/world shard (param/optimizer traffic is batch-independent)
    _resnet_rescore(out, b // world)
    out["metric"] = ("resnet50_ddp_syncbn_O1_fusedsgd_"
                     "samples_per_sec_per_chip")
    _emit(out)


# ----------------------------------------------------------------- GPT-2

def _gpt_cfg(num_layers, scan):
    import jax.numpy as jnp

    from apex_tpu.models import GPTConfig

    return GPTConfig.gpt2_1p3b(
        num_layers=num_layers, dtype=jnp.bfloat16, remat=True,
        scan_layers=scan)


def bench_gpt2_1p3b():
    """Single-chip proxy: the 1.3B architecture at BENCH_GPT_LAYERS of
    its 24 layers (full state for 24 layers needs ~13 GB of optimizer
    state alone — most of one v5e chip's 16 GB of HBM).  The
    reported number is the *proxy's* measured throughput, not an
    extrapolation; the full-size model is EXECUTED on the 8-device mesh
    by the ``gpt2_tp8_full_step`` / ``gpt2_3d_full_step`` legs.

    BENCH_GPT_VARIANT (round-4 verdict item 4 — the optimizer-overlap
    experiment; results + mechanism in BASELINE.md round-5 section):
      base       the production step (apply_gradients).
      noselect   per-leaf Adam applied UNconditionally (no DLS
                 step-skip select): removes the only data dependency
                 that could serialize the update behind the global
                 finite-flag, and removes the select's 3-pass master
                 traffic — an UPPER BOUND on what any finite-flag
                 restructuring could buy.
      fused_cast the state carries the bf16 compute copy; each update
                 emits (new master, new copy) in one fusion, so the
                 forward never re-reads the 5.3 GB fp32 masters — a
                 pure traffic-elimination lever (O2 semantics intact:
                 the copy equals cast_to_compute(master) bit-exactly,
                 and on overflow both are rolled back).
    The optimizer-only probe (t_opt_alone) is measured in every
    variant: step_ms vs fwd_bwd_ms + t_opt_alone quantifies how much
    of the optimizer's HBM streaming XLA actually hides under the
    backward (TPU executes one op at a time — overlap can only come
    from fusion, not concurrent kernels)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.core.loss_scale import all_finite
    from apex_tpu.models import GPTModel, gpt_loss_fn
    from apex_tpu.optim import fused_adam
    from apex_tpu.utils.tree import tree_select

    variant = os.environ.get("BENCH_GPT_VARIANT", "base")
    layers = int(os.environ.get("BENCH_GPT_LAYERS", "12"))
    # b=8 measured +10.7% over round-3's b=4 (29.4 vs 26.5 samples/s
    # at full settings, round 4): the ~21 GB/step of per-param state
    # (optimizer/master) traffic amortizes over twice the samples,
    # exactly as the BASELINE.md balanced-roofline analysis of this
    # leg predicts — and it still fits the chip
    b = int(os.environ.get("BENCH_BATCH", "8"))
    s = int(os.environ.get("BENCH_SEQ", "1024"))
    cfg = _gpt_cfg(layers, scan=False)
    model = GPTModel(cfg)

    ids = jax.random.randint(
        jax.random.PRNGKey(0), (b, s + 1), 0, cfg.vocab_size, jnp.int32)
    inputs, labels = ids[:, :-1], ids[:, 1:]
    tx = fused_adam(1e-4, moment_dtype=jnp.bfloat16)

    def make_state():
        params = model.init(jax.random.PRNGKey(0), inputs[:2])
        return amp.initialize(
            model.apply, params, tx, opt_level="O2",
            half_dtype=jnp.bfloat16)

    state = make_state()

    def loss_of(state, cp, inputs, labels):
        logits = state.apply_fn(cp, inputs)
        loss = gpt_loss_fn(logits.astype(jnp.float32), labels)
        return state.scale_loss(loss), loss

    import optax as _optax

    # each variant defines grad_of (how the step differentiates) and
    # apply_opt (its post-grad optimizer sequence); step AND both
    # probes are assembled from the SAME two functions, so the probes
    # time exactly the computation the step runs (no probe drift)
    if variant in ("base", "noselect"):
        def grad_of(carry, inputs, labels):
            state = carry

            def loss_fn(p):
                return loss_of(state, state.policy.cast_to_compute(p),
                               inputs, labels)

            return jax.grad(loss_fn, has_aux=True)(state.params)

        if variant == "base":
            def apply_opt(carry, grads):
                return carry.apply_gradients(grads=grads)
        else:
            def apply_opt(state, grads):
                ls = state.loss_scaler
                grads = jax.tree.map(
                    lambda g, p: g.astype(p.dtype), grads,
                    state.params)
                grads = ls.unscale(state.loss_scale_state, grads)
                finite = all_finite(grads)
                updates, new_opt = state.tx.update(
                    grads, state.opt_state, state.params)
                new_params = _optax.apply_updates(state.params,
                                                  updates)
                new_state = state.replace(
                    step=state.step + 1, params=new_params,
                    opt_state=new_opt,
                    loss_scale_state=ls.adjust(
                        state.loss_scale_state, finite))
                return new_state, finite
        carry = state
    elif variant == "fused_cast":
        # the copy casts EVERY leaf to bf16 (unlike cast_to_compute,
        # which keeps norm params fp32 and would alias those buffers
        # between master and copy — an illegal double-donation): this
        # is a traffic experiment, and the ~0.1% of params that are
        # norms don't move the numbers either way
        def to_copy(p):
            return jax.tree.map(
                lambda x: x.astype(jnp.bfloat16), p)

        def grad_of(carry, inputs, labels):
            state, copy = carry
            return jax.grad(
                lambda cp: loss_of(state, cp, inputs, labels),
                has_aux=True)(copy)

        def apply_opt(carry, grads):
            state, copy = carry
            # O2 grads arrive in bf16 (w.r.t. the compute copy) —
            # upcast+unscale exactly as apply_gradients does
            ls = state.loss_scaler
            grads = jax.tree.map(
                lambda g, p: g.astype(p.dtype), grads, state.params)
            grads = ls.unscale(state.loss_scale_state, grads)
            finite = all_finite(grads)
            updates, new_opt = state.tx.update(
                grads, state.opt_state, state.params)
            new_params = _optax.apply_updates(state.params, updates)
            # the next step's bf16 copy comes out of the same fusion
            # that writes the new master — one master read total
            new_copy = to_copy(new_params)
            new_params = tree_select(finite, new_params, state.params)
            new_copy = tree_select(finite, new_copy, copy)
            new_opt = tree_select(finite, new_opt, state.opt_state)
            new_state = state.replace(
                step=state.step + 1, params=new_params,
                opt_state=new_opt,
                loss_scale_state=ls.adjust(state.loss_scale_state,
                                           finite))
            return (new_state, new_copy), finite
    else:
        raise ValueError(f"unknown BENCH_GPT_VARIANT {variant!r}")

    def make_carry(st):
        return (st, to_copy(st.params)) if variant == "fused_cast" \
            else st

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(carry, inputs, labels):
        grads, loss = grad_of(carry, inputs, labels)
        new_carry, finite = apply_opt(carry, grads)
        return new_carry, loss, finite

    # optimizer-only probe: the un-overlapped cost of THIS variant's
    # post-grad sequence.  Grads ride in as real arguments in the
    # dtype grad_of produces, the probe returns the FULL new carry
    # (all moment/master writes must materialize — returning scalars
    # would let XLA shrink the streaming to per-leaf slices), and
    # carry+grads are donated and threaded through the window loop so
    # the probe never holds two full states (the grads input rides
    # back out as an aliased passthrough).  The probed carry is
    # consumed; a fresh state is built for the probes/step after.
    import time as _time

    gdtype = (jnp.bfloat16 if variant == "fused_cast"
              else jnp.float32)
    gprobe = jax.tree.map(
        lambda p: jnp.full(p.shape, 1e-4, gdtype), state.params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def opt_only(carry, grads):
        new_carry, _finite = apply_opt(carry, grads)
        return new_carry, grads

    n_steps = int(os.environ.get("BENCH_STEPS", "20"))
    k_windows = max(1, int(os.environ.get("BENCH_WINDOWS", "3")))
    n_probe = max(n_steps // 2, 5)
    box = [make_carry(state), gprobe]
    del state, gprobe
    box[:] = opt_only(*box)                    # warm + compile
    bench._sync(box[0])

    def opt_window():
        c, g = box
        t0 = _time.perf_counter()
        for _ in range(n_probe):
            c, g = opt_only(c, g)
        bench._sync(c)
        box[:] = [c, g]
        return (_time.perf_counter() - t0) / n_probe

    t_opt, _ = bench._time_windows(opt_window, k_windows)
    del box

    carry = make_carry(make_state())

    @jax.jit
    def fwd_bwd(carry, inputs, labels):
        grads, loss = grad_of(carry, inputs, labels)
        return bench._probe_reduce(grads, loss)

    t_fb = bench._measure_fn(
        fwd_bwd, carry, (inputs, labels), n_probe, k_windows)

    out = _measure(carry, step, (inputs, labels), b,
                   {"batch": b, "seq": s, "num_layers": layers,
                    "variant": variant,
                    "tokens_per_sec": None})
    out["tokens_per_sec"] = round(out["value"] * s, 1)
    out["fwd_bwd_ms"] = round(t_fb * 1e3, 2)
    out["opt_alone_ms"] = round(t_opt * 1e3, 2)
    out["overlap_hidden_ms"] = round(
        max(t_fb + t_opt - out["step_ms"] / 1e3, 0.0) * 1e3, 2)
    out["metric"] = (f"gpt2_1p3b_proxy{layers}L_O2_fusedadam_"
                     "samples_per_sec_per_chip")
    if variant != "base":
        out["metric"] += f"_{variant}"
    _emit(out)


def bench_gpt2_tp8_full_step():
    """EXECUTE (not just compile) one full O2+FusedAdam+DLS train step
    of the whole 24-layer 1.316B-param GPT-2 under TP=8 + sequence
    parallelism (BASELINE.json configs[3] topology) on the 8-device
    virtual CPU mesh, asserting a finite loss.  The wall time is
    host-CPU execution time (1 core, 8 virtual devices) — a
    works-at-scale proof, NOT a throughput claim; per-device memory is
    XLA's analysis of the sharded program.  Run with JAX_PLATFORMS=cpu
    XLA_FLAGS=--xla_force_host_platform_device_count=8."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import flax.linen as nn
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.core import mesh as mesh_lib
    from apex_tpu.models import GPTModel, gpt_loss_fn
    from apex_tpu.optim import fused_adam

    # sequential dispatch (CPU-only flag, must be set BEFORE the first
    # backend query initializes the client): see the cross-program
    # rendezvous note in bench_gpt2_3d_full_step
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    mesh = mesh_lib.initialize_mesh(tensor_model_parallel_size=8)
    cfg = _gpt_cfg(24, scan=True)
    cfg = __import__("dataclasses").replace(cfg, sequence_parallel=True)
    model = GPTModel(cfg)
    # batch sized for single-core CPU execution (~20 TFLOP/step); the
    # model is the full 1.3B — only the token count is small
    b = int(os.environ.get("BENCH_BATCH", "2"))
    s = int(os.environ.get("BENCH_SEQ", "1024"))
    ids0 = jnp.zeros((b, s), jnp.int32)
    tx = fused_adam(1e-4)

    def create_state():
        params = model.init(jax.random.PRNGKey(0), ids0)
        return amp.initialize(model.apply, params, tx,
                              opt_level="O2", half_dtype=jnp.bfloat16)

    state_shape = jax.eval_shape(create_state)
    specs = nn.get_partition_spec(state_shape)
    shardings = jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs,
        is_leaf=lambda x: isinstance(x, P))
    data_sharding = NamedSharding(mesh, P("data"))

    def train_step(state, inputs, labels):
        def loss_fn(p):
            cp = state.policy.cast_to_compute(p)
            logits = state.apply_fn(cp, inputs)
            loss = gpt_loss_fn(logits.astype(jnp.float32), labels)
            return state.scale_loss(loss), loss

        grads, loss = jax.grad(loss_fn, has_aux=True)(state.params)
        new_state, finite = state.apply_gradients(grads=grads)
        return new_state, loss, finite

    n_params = sum(
        x.size for x in jax.tree.leaves(state_shape.params)
        if hasattr(x, "size"))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(b, s + 1))
    ln_v = float(np.log(cfg.vocab_size))
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            train_step,
            in_shardings=(shardings, data_sharding, data_sharding),
            donate_argnums=(0,))
        compiled = jitted.lower(
            state_shape,
            jax.ShapeDtypeStruct((b, s), jnp.int32),
            jax.ShapeDtypeStruct((b, s), jnp.int32)).compile()
        mem = compiled.memory_analysis()
        state = jax.jit(create_state, out_shardings=shardings)()
        inputs = jax.device_put(
            jnp.asarray(tokens[:, :-1], jnp.int32), data_sharding)
        labels = jax.device_put(
            jnp.asarray(tokens[:, 1:], jnp.int32), data_sharding)
        t0 = time.perf_counter()
        state, loss, finite = compiled(state, inputs, labels)
        loss = float(loss)
        dt = time.perf_counter() - t0
    assert np.isfinite(loss), f"non-finite loss {loss}"
    # init-loss plausibility (round-3 verdict item 4): a correctly
    # wired fresh model scores ≈ uniform over the vocab
    assert 0.8 * ln_v <= loss <= 1.6 * ln_v, (
        f"init loss {loss} implausible vs ln(V)={ln_v:.3f}")
    _emit({
        "metric": "gpt2_1p3b_tp8_sp_train_step_executed",
        "value": 1,
        "unit": "ok",
        "executed": True,
        "loss": round(loss, 4),
        "loss_over_ln_vocab": round(loss / ln_v, 3),
        "loss_plausibility_checked": "0.8 <= loss/ln(V) <= 1.6",
        "grads_finite": bool(finite),
        "batch": b, "seq": s,
        "host_cpu_step_seconds": round(dt, 1),
        "num_params": int(n_params),
        "mesh": dict(mesh.shape),
        "per_device_argument_bytes": getattr(mem, "argument_size_in_bytes",
                                             None),
        "per_device_temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "per_device_output_bytes": getattr(mem, "output_size_in_bytes",
                                           None),
    })


def bench_gpt2_3d_full_step():
    """EXECUTE one full-model train step of the 24-layer 1.3B GPT-2
    composed TP=2 × PP=2 × DP=2 *through the 1F1B schedule*: stages
    from ``build_model`` (12 layers each, TP/SP inside), embedding +
    learned positions + untied head closed over the pipelined region
    via ``loss_params``/``return_input_cotangents``, O2 master weights
    + FusedAdam + dynamic loss scaling on the whole pytree.  Finite
    loss asserted; wall time is host-CPU execution (works-at-scale
    proof, not throughput).  Embed/head are replicated here (their
    GSPMD vocab sharding is exercised by the TP=8 leg); compute dtype
    is f32 on CPU (XLA:CPU crashes on bf16 all-reduce inside
    partial-manual shard_map) and bf16 on TPU."""
    import dataclasses
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.core import mesh as mesh_lib
    from apex_tpu.models import TransformerConfig, ParallelTransformerLayer
    from apex_tpu.optim import fused_adam
    from apex_tpu.transformer.pipeline_parallel import (
        build_model,
        forward_backward_pipelining_without_interleaving,
    )

    # async dispatch lets two programs' collectives interleave in
    # different per-device orders — a cross-program rendezvous deadlock
    # on the in-process CPU communicator (observed: a resharding
    # all-to-all racing the step's all-reduces).  CPU-only flag; must
    # be set BEFORE the first backend query initializes the client.
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    mesh = mesh_lib.initialize_mesh(
        tensor_model_parallel_size=2,
        pipeline_model_parallel_size=2,
        data_parallel_size=2)
    gcfg = _gpt_cfg(24, scan=False)
    # s=256 keeps the peak inside the 125 GB host (the model is the
    # full 1.3B either way; only the token count is small)
    s = int(os.environ.get("BENCH_SEQ", "256"))
    m, mb = 2, 2
    cfg = TransformerConfig(
        vocab_size=gcfg.vocab_size, hidden_size=gcfg.hidden_size,
        num_layers=1, num_heads=gcfg.num_heads, max_seq_len=s,
        sequence_parallel=True, causal=True,
        dtype=jnp.float32 if jax.default_backend() == "cpu"
        else jnp.bfloat16)
    layer = ParallelTransformerLayer(cfg)
    x0 = jnp.zeros((mb, s, cfg.hidden_size), jnp.float32)
    stage_fn, stages, stage_spec = build_model(
        layer, num_layers=24, pipeline_model_parallel_size=2,
        rng=jax.random.PRNGKey(0), sample_input=x0,
        # one layer's residuals at a time when the 1F1B backward unit
        # recomputes its 12-layer stage — without this the per-tick vjp
        # holds all 12 layers' residuals (~24 GB across the 8 virtual
        # devices) and the leg OOMs the 125 GB host
        layer_remat=True)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(m * mb, s + 1))
    half = (jnp.float32 if jax.default_backend() == "cpu"
            else jnp.bfloat16)

    with jax.set_mesh(mesh):
        embed = jnp.asarray(
            rng.normal(size=(cfg.vocab_size, cfg.hidden_size)) * 0.02,
            jnp.float32)
        pos = jnp.asarray(
            rng.normal(size=(s, cfg.hidden_size)) * 0.02, jnp.float32)
        head = jnp.asarray(
            rng.normal(size=(cfg.hidden_size, cfg.vocab_size)) * 0.02,
            jnp.float32)
        # final pre-head LayerNorm, exactly as GPTModel applies after
        # the layer stack — round 3 omitted it from this hand-rolled
        # closure model, which is why the leg's init loss read 22.6
        # (≈ 2x ln(V)): 24 unnormalized residual additions grow the
        # stream's scale, inflating the logit variance.  Its params
        # ride loss_params so their grads close over the pipeline.
        fln_scale = jnp.ones((cfg.hidden_size,), jnp.float32)
        fln_bias = jnp.zeros((cfg.hidden_size,), jnp.float32)
        params = {"embed": embed, "pos": pos, "stages": stages,
                  "head": head, "fln_scale": fln_scale,
                  "fln_bias": fln_bias}
        n_params = sum(x.size for x in jax.tree.leaves(params))
        # bf16 moments (as the gpt2_1p3b proxy leg): XLA:CPU does not
        # honor buffer donation, so the step materializes a second
        # optimizer state — fp32 moments put the peak past 125 GB
        state = amp.initialize(
            None, params,
            fused_adam(1e-4, moment_dtype=jnp.bfloat16),
            opt_level="O2", half_dtype=half)

        # placement: stages sharded per build_model's spec; embed/head
        # masters+moments ZeRO-sharded over (data, tensor) — on 8
        # virtual CPU devices a replicated 412 MB f32 leaf materializes
        # 8 host copies, and with masters+2 moments+grads that alone
        # OOMs the 125 GB host
        emb_spec = {"embed": P(("data", "tensor"), None), "pos": P(),
                    "head": P(None, ("data", "tensor")),
                    "fln_scale": P(), "fln_bias": P()}

        # storage spec: additionally ZeRO-shard the per-stage axis over
        # `data` (distributed_fused_adam semantics) — XLA:CPU does not
        # honor donation, so the step materializes a second state and
        # the un-data-sharded x2 replication would put the peak past
        # the 125 GB host
        stage_storage = jax.tree.map(
            lambda sp: P(sp[0], "data", *sp[2:]), stage_spec,
            is_leaf=lambda v: isinstance(v, P))

        def place(tree):
            out = dict(tree)
            out["stages"] = jax.tree.map(
                lambda sp, l: jax.device_put(
                    l, NamedSharding(mesh, sp)),
                stage_storage, tree["stages"],
                is_leaf=lambda v: isinstance(v, P))
            for k, sp in emb_spec.items():
                out[k] = jax.device_put(
                    tree[k], NamedSharding(mesh, sp))
            return out

        opt = state.opt_state
        state = state.replace(
            params=place(state.params),
            opt_state=opt._replace(
                exp_avg=place(opt.exp_avg),
                exp_avg_sq=place(opt.exp_avg_sq)))
        # free the pre-placement unsharded copies (~20 GB of zombies:
        # build_model's stacked stages, amp.initialize's master copy
        # and moment inits all stay alive through these references)
        del stages, params, opt, embed, pos, head
        import gc

        gc.collect()
        # token ids/labels replicated: with them data-sharded, GSPMD
        # emits all-to-alls (in-tick label indexing, embedding
        # scatter-add) and XLA:CPU's in-process AllToAll thunk
        # deadlocks under the concurrent thunk executor — every fatal
        # trace of this leg died in InProcessCommunicator::AllToAll.
        # The data-sharded input path is exercised by the dryrun
        # dp×tp×sp×pp leg and tests/test_parallel.py; on TPU this leg
        # would run with P("data") inputs unchanged.
        inputs = jax.device_put(
            jnp.asarray(tokens[:, :-1], jnp.int32),
            NamedSharding(mesh, P()))
        labels = jax.device_put(
            jnp.asarray(tokens[:, 1:], jnp.int32),
            NamedSharding(mesh, P()))

        def train_step(state, inputs, labels):
            cp = state.policy.cast_to_compute(state.params)
            lab_mb = labels.reshape(m, mb, s)

            def loss_fn(lp, y, i):
                hd, g, be = lp
                # final LN (as GPTModel's post-stack norm), fp32
                yf = y.astype(jnp.float32)
                mu = jnp.mean(yf, axis=-1, keepdims=True)
                var = jnp.var(yf, axis=-1, keepdims=True)
                yn = (yf - mu) * jax.lax.rsqrt(var + 1e-5) * g + be
                logits = (yn.astype(y.dtype) @ hd).astype(jnp.float32)
                lab = jax.lax.dynamic_index_in_dim(
                    lab_mb, jnp.clip(i, 0, m - 1), axis=0,
                    keepdims=False)
                logp = jax.nn.log_softmax(logits)
                nll = -jnp.take_along_axis(
                    logp, lab[..., None], axis=-1)[..., 0]
                return state.scale_loss(jnp.mean(nll))

            h = (jnp.take(cp["embed"], inputs, axis=0)
                 + cp["pos"][None]).astype(cfg.dtype)
            # distribute_inputs=False: M=2 needs no feed ring, and the
            # cyclic reshard's all-to-all is the one collective the
            # XLA:CPU in-process communicator deadlocks on
            sloss, sgrads, aux = \
                forward_backward_pipelining_without_interleaving(
                    stage_fn, loss_fn, cp["stages"], h, mesh=mesh,
                    num_microbatches=m,
                    loss_params=(cp["head"], cp["fln_scale"],
                                 cp["fln_bias"]),
                    return_input_cotangents=True,
                    distribute_inputs=False)
            cts = aux["input_cotangents"].astype(jnp.float32)
            cts = cts.reshape(m * mb, s, cfg.hidden_size)
            d_embed = jnp.zeros_like(cp["embed"]).at[inputs].add(cts)
            d_head, d_flns, d_flnb = aux["loss_params_grads"]
            grads = {"embed": d_embed, "pos": cts.sum(0),
                     "stages": sgrads, "head": d_head,
                     "fln_scale": d_flns, "fln_bias": d_flnb}
            new_state, finite = state.apply_gradients(grads=grads)
            loss = state.loss_scaler.unscale(
                state.loss_scale_state, sloss)
            return new_state, loss, finite

        step = jax.jit(train_step, donate_argnums=(0,))
        t0 = time.perf_counter()
        state, loss, finite = step(state, inputs, labels)
        loss = float(loss)
        dt = time.perf_counter() - t0
    assert np.isfinite(loss), f"non-finite loss {loss}"
    # init-loss plausibility (round-3 verdict item 4): with the final
    # LN restored this leg must agree with the TP=8 leg's ≈ ln(V)
    ln_v = float(np.log(cfg.vocab_size))
    assert 0.8 * ln_v <= loss <= 1.6 * ln_v, (
        f"init loss {loss} implausible vs ln(V)={ln_v:.3f}")
    _emit({
        "metric": "gpt2_1p3b_tp2pp2dp2_1f1b_train_step_executed",
        "value": 1,
        "unit": "ok",
        "executed": True,
        "loss": round(loss, 4),
        "loss_over_ln_vocab": round(loss / ln_v, 3),
        "loss_plausibility_checked": "0.8 <= loss/ln(V) <= 1.6",
        "grads_finite": bool(finite),
        "microbatches": m, "microbatch_size": mb, "seq": s,
        "host_cpu_step_seconds": round(dt, 1),
        "num_params": int(n_params),
        "mesh": dict(mesh.shape),
        "inputs_replicated_on_cpu": True,
    })


def bench_mistral7b_tp8_full_step():
    """EXECUTE one full O2+FusedAdam+DLS train step of the 7.24B
    ``mistral_7b`` preset — GQA (8 kv heads over TP=8 → exactly one kv
    head per shard, the divisibility edge), SwiGLU gated MLP, RMSNorm,
    untied head — under TP=8 + sequence parallelism on the 8-device
    virtual CPU mesh, asserting a finite, ln(V)-plausible init loss
    (round-4 verdict item 3: promote the 7B presets + GQA sharding
    from config-file claims to executed capability).

    CPU-host memory shape: XLA:CPU does not honor buffer donation for
    SHARDED computations (re-probed this round: an 8 GB donated
    mesh-sharded array peaks at 17 GB; single-device peaks at 8.6 GB),
    so a one-jit state→state step would materialize the 7B O2 state
    twice (2 × 58 GB) plus transients — past the 125 GB host.  The leg
    therefore runs the step in two phases with IDENTICAL math:
    (1) one sharded jit computing scaled-loss grads w.r.t. the fp32
    masters, (2) the optimizer/DLS sequence of
    ``MixedPrecisionTrainState.apply_gradients`` applied leaf-wise
    (upcast → unscale → finite-AND → FusedAdam update → select →
    scale-adjust), bounding live temps to one stacked leaf.  Per-leaf
    unscaled finiteness equals after-unscale finiteness (x/scale with
    scale ≥ 1 preserves inf/nan and finiteness).  On a real TPU mesh
    the same step runs as ONE jit with donation — this split is a
    host-RAM accommodation, not a framework limitation."""
    import functools as ft
    import resource
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import flax.linen as nn
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.core import mesh as mesh_lib
    from apex_tpu.models import LlamaConfig, LlamaModel, gpt_loss_fn
    from apex_tpu.optim import fused_adam

    jax.config.update("jax_cpu_enable_async_dispatch", False)
    mesh = mesh_lib.initialize_mesh(tensor_model_parallel_size=8)
    b = int(os.environ.get("BENCH_BATCH", "1"))
    s = int(os.environ.get("BENCH_SEQ", "512"))
    cfg = LlamaConfig.mistral_7b(
        max_seq_len=s, dtype=jnp.bfloat16, remat=True,
        scan_layers=True, sequence_parallel=True,
        # full 32 layers by default; override only for smoke tests
        num_layers=int(os.environ.get("BENCH_7B_LAYERS", "32")))
    model = LlamaModel(cfg)
    ids0 = jnp.zeros((b, s), jnp.int32)
    # bf16 moments as the gpt2 legs: fp32 moments alone are 58 GB
    tx = fused_adam(1e-4, moment_dtype=jnp.bfloat16)

    def create_state():
        params = model.init(jax.random.PRNGKey(0), ids0)
        return amp.initialize(model.apply, params, tx,
                              opt_level="O2", half_dtype=jnp.bfloat16)

    state_shape = jax.eval_shape(create_state)
    specs = nn.get_partition_spec(state_shape)
    shardings = jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs,
        is_leaf=lambda x: isinstance(x, P))
    data_sharding = NamedSharding(mesh, P("data"))
    n_params = sum(
        x.size for x in jax.tree.leaves(state_shape.params)
        if hasattr(x, "size"))

    def grad_step(state, inputs, labels):
        def loss_fn(p):
            cp = state.policy.cast_to_compute(p)
            logits = state.apply_fn(cp, inputs)
            loss = gpt_loss_fn(logits, labels)
            return state.scale_loss(loss), loss

        grads, loss = jax.grad(loss_fn, has_aux=True)(state.params)
        return grads, loss

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(b, s + 1))
    ln_v = float(np.log(cfg.vocab_size))
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            grad_step,
            in_shardings=(shardings, data_sharding, data_sharding),
            out_shardings=(shardings.params, None))
        compiled = jitted.lower(
            state_shape,
            jax.ShapeDtypeStruct((b, s), jnp.int32),
            jax.ShapeDtypeStruct((b, s), jnp.int32)).compile()
        mem = compiled.memory_analysis()
        state = jax.jit(create_state, out_shardings=shardings)()
        inputs = jax.device_put(
            jnp.asarray(tokens[:, :-1], jnp.int32), data_sharding)
        labels = jax.device_put(
            jnp.asarray(tokens[:, 1:], jnp.int32), data_sharding)

        t0 = time.perf_counter()
        grads, sloss = compiled(state, inputs, labels)
        sloss = float(sloss)        # sync: grads materialized
        t_grads = time.perf_counter() - t0

        # phase 2: apply_gradients leaf-wise (identical sequence) ----
        ls, ls_state = state.loss_scaler, state.loss_scale_state
        scale = ls_state.loss_scale

        @jax.jit
        def leaf_finite(g, scale):
            return jnp.isfinite(g.astype(jnp.float32) / scale).all()

        finite = jnp.asarray(True)
        for g in jax.tree.leaves(grads):
            finite = finite & leaf_finite(g, scale)

        @jax.jit
        def leaf_update(p, m, v, g, count, scale, finite):
            g = g.astype(p.dtype) / scale          # upcast → unscale
            upd, new = tx.update(
                {"x": g},
                type(state.opt_state)(
                    count=count, exp_avg={"x": m}, exp_avg_sq={"x": v}),
                {"x": p})
            new_p = p + upd["x"]
            sel = lambda a, b: jnp.where(finite, a, b)
            return (sel(new_p, p), sel(new.exp_avg["x"], m),
                    sel(new.exp_avg_sq["x"], v), new.count)

        params = state.params
        opt = state.opt_state
        flat_p, treedef = jax.tree.flatten(params)
        flat_m = treedef.flatten_up_to(opt.exp_avg)
        flat_v = treedef.flatten_up_to(opt.exp_avg_sq)
        flat_g = treedef.flatten_up_to(grads)
        del grads, params
        new_count = opt.count
        for i in range(len(flat_p)):
            flat_p[i], flat_m[i], flat_v[i], new_count = leaf_update(
                flat_p[i], flat_m[i], flat_v[i], flat_g[i],
                opt.count, scale, finite)
            flat_g[i] = None                       # free as we go
        new_params = jax.tree.unflatten(treedef, flat_p)
        new_opt = type(opt)(
            count=jnp.where(finite, new_count, opt.count),
            exp_avg=jax.tree.unflatten(treedef, flat_m),
            exp_avg_sq=jax.tree.unflatten(treedef, flat_v))
        new_ls_state = ls.adjust(ls_state, finite)
        state = state.replace(
            step=state.step + 1, params=new_params, opt_state=new_opt,
            loss_scale_state=new_ls_state)
        jax.block_until_ready(state.params)
        dt = time.perf_counter() - t0
        loss = float(ls.unscale(ls_state, sloss))
        finite = bool(finite)

    assert np.isfinite(loss), f"non-finite loss {loss}"
    assert 0.8 * ln_v <= loss <= 1.6 * ln_v, (
        f"init loss {loss} implausible vs ln(V)={ln_v:.3f}")
    peak_rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    _emit({
        "metric": "mistral_7b_tp8_sp_train_step_executed",
        "value": 1,
        "unit": "ok",
        "executed": True,
        "loss": round(loss, 4),
        "loss_over_ln_vocab": round(loss / ln_v, 3),
        "loss_plausibility_checked": "0.8 <= loss/ln(V) <= 1.6",
        "grads_finite": finite,
        "batch": b, "seq": s,
        "host_cpu_step_seconds": round(dt, 1),
        "host_cpu_grad_seconds": round(t_grads, 1),
        "num_params": int(n_params),
        "kv_heads_per_shard": cfg.kv_heads // mesh.shape["tensor"],
        "mesh": dict(mesh.shape),
        "host_peak_rss_bytes": int(peak_rss),
        "two_phase_cpu_note": (
            "grad jit + leaf-wise optimizer (XLA:CPU ignores donation "
            "for sharded buffers; one-jit form exceeds host RAM at 7B "
            "O2 x2 state — TPU runs the one-jit form)"),
        "per_device_argument_bytes": getattr(
            mem, "argument_size_in_bytes", None),
        "per_device_temp_bytes": getattr(mem, "temp_size_in_bytes",
                                         None),
        "per_device_output_bytes": getattr(
            mem, "output_size_in_bytes", None),
    })


def bench_moe_mixtral():
    """Measured MoE throughput leg (ISSUE-3 satellite / round-5
    verdict Missing #2: MoE was dryrun-correct and parity-tested but
    had no on-chip row).  A Mixtral-geometry proxy — the 8x7b recipe
    (hidden 4096, 8 SwiGLU experts, top-2 token-choice routing, GQA,
    sliding window) at BENCH_MOE_LAYERS of its 32 layers, the same
    full-geometry-proxy convention as ``gpt2_1p3b`` — trained one real
    O2+FusedAdam+DLS step per measurement under the standard
    best-of-window/agreement hygiene.  The router trains through
    ``moe_aux_loss`` exactly as production would.

    ``moe_capacity_factor`` defaults to the *training* value 1.25
    (token drop is routine when training from scratch; the drop-free
    parity default cf=4 makes the dispatch masks quadratic in S and is
    an import-parity concern, not a throughput recipe) — override with
    BENCH_MOE_CF.  BENCH_MOE_PRESET=tiny swaps in LlamaConfig.tiny
    with the same expert structure for CPU smoke tests."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.models import (
        LlamaConfig,
        LlamaModel,
        gpt_loss_fn,
        moe_aux_loss,
    )
    from apex_tpu.optim import fused_adam

    preset = os.environ.get("BENCH_MOE_PRESET", "mixtral")
    b = int(os.environ.get("BENCH_BATCH", "1"))
    s = int(os.environ.get("BENCH_SEQ", "1024"))
    cf = float(os.environ.get("BENCH_MOE_CF", "1.25"))
    if preset == "tiny":
        cfg = LlamaConfig.tiny(
            max_seq_len=s, num_moe_experts=4, moe_top_k=2,
            moe_capacity_factor=cf, scan_layers=False)
    else:
        cfg = LlamaConfig.mixtral_8x7b(
            max_seq_len=s, dtype=jnp.bfloat16, remat=True,
            scan_layers=False, moe_capacity_factor=cf,
            # 2 of 32 layers fits the chip beside the O2 state; the
            # per-layer geometry (the thing measured) is full-size
            num_layers=int(os.environ.get("BENCH_MOE_LAYERS", "2")))
    model = LlamaModel(cfg)

    ids = jax.random.randint(
        jax.random.PRNGKey(0), (b, s + 1), 0, cfg.vocab_size, jnp.int32)
    inputs, labels = ids[:, :-1], ids[:, 1:]
    params = model.init(jax.random.PRNGKey(0), inputs[:1, :8])
    n_params = sum(x.size for x in jax.tree.leaves(params))
    state = amp.initialize(
        model.apply, params,
        fused_adam(1e-4, moment_dtype=jnp.bfloat16),
        opt_level="O2", half_dtype=jnp.bfloat16)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, inputs, labels):
        def loss_fn(p):
            cp = state.policy.cast_to_compute(p)
            logits, mut = state.apply_fn(cp, inputs,
                                         mutable=["losses"])
            loss = gpt_loss_fn(logits, labels) + moe_aux_loss(mut)
            return state.scale_loss(loss), loss

        grads, loss = jax.grad(loss_fn, has_aux=True)(state.params)
        new_state, finite = state.apply_gradients(grads=grads)
        return new_state, loss, finite

    out = _measure(state, step, (inputs, labels), b,
                   {"batch": b, "seq": s,
                    "num_layers": cfg.num_layers,
                    "num_experts": cfg.num_moe_experts,
                    "moe_top_k": cfg.moe_top_k,
                    "moe_capacity_factor": cf,
                    "num_params": int(n_params)})
    out["tokens_per_sec"] = round(out["value"] * s, 1)
    out["metric"] = (f"moe_mixtral_proxy{cfg.num_layers}L_O2_fusedadam"
                     "_samples_per_sec_per_chip")
    _emit(out)


# ----------------------------------------------------------------- BERT O1

# (lifted to apex_tpu/plan/costs.py — imported back above as _ddp_bytes_on_wire)


def bench_bert_o1():
    """BERT-Large under O1 — per-op cast interceptor (amp/o1.py clone
    mechanism + amp/lists.py tables) + FusedAdam — so O1 has a measured
    number like O2 (round-1 verdict item 5).  The model is built with
    ``dtype=None`` (modules promote with their fp32 params) and every
    MXU op is routed to bf16 by the interceptor, the reference's O1
    semantics (fp32 masters, per-op half compute).

    ISSUE-8 satellite (ROADMAP 2b): the emission now carries the
    ``_ddp_bytes_on_wire`` model for this model's grad sync (int8
    all-reduce ≈ 4× fewer ICI bytes than fp32), and the leg
    orchestrates a measured ``bert_o1_ddp`` child — an 8-way
    virtual-CPU-mesh DDP A/B of ``allreduce_dtype`` None vs ``"int8"``
    on a layer-shrunk proxy (BENCH_BERT_DDP=0 skips it; on-chip, run
    the child leg directly on the real mesh)."""
    from apex_tpu.utils import numcheck
    from apex_tpu.utils.metrics import counters as _counters

    # ISSUE-10 satellite: the leg rides the runtime numerics sanitizer
    # in observe mode — the emission carries the grad underflow-to-zero
    # fraction and the loss-scale growth/backoff event counts, so the
    # loss-trajectory band tests can correlate precision events with
    # divergence.  One scalar reduction + async callback per step;
    # BENCH_NUMCHECK=0 opts out for on-chip wall-clock purity.
    observe_numerics = os.environ.get("BENCH_NUMCHECK", "1") != "0"
    if observe_numerics:
        numcheck.reset()
        numcheck.instrument(strict=False)
    events_before = _counters.snapshot()
    try:
        out = _bench_bert_o1_measured(observe_numerics, events_before)
    finally:
        # the wrappers are process-wide: never leak them into later
        # legs run in this process if the measurement raises
        if observe_numerics:
            numcheck.uninstrument()
    if os.environ.get("BENCH_BERT_DDP", "1") != "0":
        # measured companion: 8-way virtual-CPU-mesh DDP A/B of
        # allreduce_dtype None vs "int8" on a layer-shrunk proxy
        out["ddp_int8_ab"] = _run_child("bert_o1_ddp", {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device"
                            "_count=8").strip(),
        }, timeout=1500)
    if os.environ.get("BENCH_BERT_ZERO", "1") != "0":
        # ISSUE-11 companion: replicated-vs-ZeRO-2 optimizer-state A/B
        # on the same virtual mesh (hbm_peak drop, grown-batch row)
        out["zero_ab"] = _run_child("bert_o1_zero", {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device"
                            "_count=8").strip(),
        }, timeout=1500)
    _emit(out)


def _bench_bert_o1_measured(observe_numerics, events_before):
    """The measured body of :func:`bench_bert_o1` (split out so the
    numcheck instrumentation wraps it in one try/finally)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.amp import o1
    from apex_tpu.models import BertConfig, BertModel, bert_mlm_loss_fn
    from apex_tpu.optim import fused_adam
    from apex_tpu.utils import numcheck
    from apex_tpu.utils.metrics import counters as _counters

    b = int(os.environ.get("BENCH_BATCH", "16"))
    cfg = BertConfig.bert_large(remat=True, dtype=None, scan_layers=False)
    model = BertModel(cfg)
    s = int(os.environ.get("BENCH_SEQ", str(min(cfg.max_seq_len, 512))))
    p = min(max(8, int(0.15 * s / 8 + 0.5) * 8), s)

    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (b, s), 0, cfg.vocab_size)
    positions = jnp.argsort(jax.random.uniform(rng, (b, s)), axis=-1)[:, :p]
    mlm_labels = jnp.take_along_axis(ids, positions, axis=1)

    def apply_fn(params, ids, **kw):
        with o1.o1_intercept(jnp.bfloat16):
            return model.apply(params, ids, **kw)

    params = model.init(jax.random.PRNGKey(0), ids[:2])
    state = amp.initialize(apply_fn, params, fused_adam(1e-4),
                           opt_level="O1")

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, ids, positions, mlm_labels):
        def loss_fn(p):
            logits, _ = state.apply_fn(
                p, ids, mlm_positions=positions, deterministic=True)
            loss = bert_mlm_loss_fn(logits.astype(jnp.float32), mlm_labels)
            return state.scale_loss(loss), loss

        grads, loss = jax.grad(
            loss_fn, has_aux=True)(state.compute_params())
        new_state, finite = state.apply_gradients(grads=grads)
        return new_state, loss, finite

    n_params = sum(x.size for x in jax.tree.leaves(params))
    replicas = int(os.environ.get("BENCH_DDP_REPLICAS", "8"))
    out = _measure(state, step, (ids, positions, mlm_labels), b,
                   {"batch": b, "seq": s})
    out["metric"] = "bert_large_O1_fusedadam_samples_per_sec_per_chip"
    # ISSUE-8 / ROADMAP 2b: what the grad sync of THIS model costs on
    # the wire per step, fp32 vs bf16 vs the ddp.py int8 path
    out["ddp_bytes_on_wire"] = _ddp_bytes_on_wire(n_params, replicas)
    # ISSUE-10: precision-event telemetry beside the throughput number
    if observe_numerics:
        jax.effects_barrier()
        stats = numcheck.summary()
        after = _counters.snapshot()
        out["numcheck"] = {
            "grad_underflow_frac": round(
                stats["grad_underflow_frac"], 6),
            "nonfinite_grad_steps": stats["nonfinite_grad_steps"],
            "loss_scale_growth": (
                after.get("amp.loss_scale.growth", 0)
                - events_before.get("amp.loss_scale.growth", 0)),
            "loss_scale_backoff": (
                after.get("amp.loss_scale.backoff", 0)
                - events_before.get("amp.loss_scale.backoff", 0)),
        }
    return out


def bench_bert_o1_ddp():
    """Measured ROADMAP-2b row: the BERT O1 recipe under 8-way DDP
    (``shard_map`` + ``all_reduce_mean_grads``), A/B'ing the exact
    fp32 grad all-reduce against the EQuARX-style int8 one
    (``parallel/ddp.py``).  Virtual-CPU-mesh proxy by default (the
    layer count shrinks via BENCH_BERT_DDP_LAYERS — protocol and
    LOSS-AGREEMENT are the artifact; on real ICI the int8 row's win
    tracks the 4× wire-byte reduction in ``_ddp_bytes_on_wire``,
    while CPU "wire" is memcpy so the wall ratio here only prices the
    quantize/dequant arithmetic).  Emits samples/sec + final-loss
    agreement + the bytes model for the measured size.

    Env: BENCH_BERT_DDP_LAYERS (2), BENCH_BATCH (16 global),
    BENCH_SEQ (128), BENCH_DDP_STEPS (8)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu import parallel as apx_parallel
    from apex_tpu.amp import o1
    from apex_tpu.models import BertConfig, BertModel, bert_mlm_loss_fn
    from apex_tpu.optim import fused_adam

    n_dev = jax.device_count()
    if n_dev < 2:
        _emit({"metric": "bert_o1_ddp", "value": None,
               "skipped": f"needs >= 2 devices, have {n_dev}"})
        return
    layers = int(os.environ.get("BENCH_BERT_DDP_LAYERS", "2"))
    b = int(os.environ.get("BENCH_BATCH", "16"))
    b -= b % n_dev                     # divisible global batch
    b = max(b, n_dev)
    cfg = BertConfig.bert_large(remat=True, dtype=None,
                                scan_layers=False, num_layers=layers)
    model = BertModel(cfg)
    s = int(os.environ.get("BENCH_SEQ", str(min(cfg.max_seq_len, 128))))
    p = min(max(8, int(0.15 * s / 8 + 0.5) * 8), s)
    steps = int(os.environ.get("BENCH_DDP_STEPS", "8"))

    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (b, s), 0, cfg.vocab_size)
    positions = jnp.argsort(jax.random.uniform(rng, (b, s)),
                            axis=-1)[:, :p]
    mlm_labels = jnp.take_along_axis(ids, positions, axis=1)

    def apply_fn(params, ids, **kw):
        with o1.o1_intercept(jnp.bfloat16):
            return model.apply(params, ids, **kw)

    init = model.init(jax.random.PRNGKey(0), ids[:2])
    n_params = sum(x.size for x in jax.tree.leaves(init))
    # raw mesh, NOT registered with core.mesh: the step is fully
    # manual inside shard_map, so maybe_constrain stays a no-op
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n_dev]),
                             ("data",))

    def run(allreduce_dtype):
        # private param copy: the donated step consumes the state's
        # buffers, and both A/B runs must start from the same init
        state = amp.initialize(apply_fn,
                               jax.tree.map(jnp.copy, init),
                               fused_adam(1e-4), opt_level="O1")

        def dp_step(state, ids, positions, mlm_labels):
            def loss_fn(p):
                logits, _ = state.apply_fn(
                    p, ids, mlm_positions=positions,
                    deterministic=True)
                loss = bert_mlm_loss_fn(
                    logits.astype(jnp.float32), mlm_labels)
                return state.scale_loss(loss), loss

            grads, loss = jax.grad(
                loss_fn, has_aux=True)(state.compute_params())
            grads = apx_parallel.all_reduce_mean_grads(
                grads, "data", allreduce_dtype=allreduce_dtype)
            new_state, finite = state.apply_gradients(grads=grads)
            return new_state, jax.lax.pmean(loss, "data"), finite

        step = jax.jit(jax.shard_map(
            dp_step, mesh=mesh,
            in_specs=(P(), P("data"), P("data"), P("data")),
            out_specs=(P(), P(), P()), check_vma=False),
            donate_argnums=(0,))
        state, loss, _ = step(state, ids, positions, mlm_labels)
        bench._sync(loss)              # compile + warm
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss, finite = step(state, ids, positions,
                                       mlm_labels)
        bench._sync(loss)
        dt = (time.perf_counter() - t0) / steps
        return {
            "allreduce_dtype": str(allreduce_dtype or "fp32"),
            "samples_per_sec": round(b / dt, 2),
            "step_ms": round(dt * 1e3, 2),
            "final_loss": round(float(loss), 5),
            "loss_finite": bool(finite),
        }

    exact = run(None)
    int8 = run("int8")
    _emit({
        "metric": "bert_o1_ddp_int8_allreduce_samples_per_sec",
        "value": int8["samples_per_sec"],
        "unit": "samples/sec (CPU-mesh proxy)",
        "replicas": n_dev, "global_batch": b, "seq": s,
        "num_layers": layers, "num_params": int(n_params),
        "rows": {"fp32_allreduce": exact, "int8_allreduce": int8},
        "sps_vs_fp32_allreduce": round(
            int8["samples_per_sec"]
            / max(exact["samples_per_sec"], 1e-9), 3),
        "final_loss_delta": round(
            abs(int8["final_loss"] - exact["final_loss"]), 5),
        "ddp_bytes_on_wire": _ddp_bytes_on_wire(n_params, n_dev),
        "note": ("measured ROADMAP-2b row: wire bytes drop 4x (model "
                 "above; genuine int8 all_to_all/all_gather traffic), "
                 "loss trajectory agreement is gated by "
                 "test_loss_trajectory's exact-vs-int8 band test; the "
                 "CPU wall ratio prices quantize arithmetic, not ICI "
                 "— the on-chip win follows the bytes model"),
    })


# (lifted to apex_tpu/plan/costs.py — imported back above as _zero_bytes_on_wire)


def bench_bert_o1_zero():
    """Measured ISSUE-11 row: the BERT recipe under 8-way DP at O2,
    A/B'ing replicated optimizer state against ZeRO-2
    (``parallel.distributed_optim``: reduce-scatter grads →
    shard-local FusedAdam on fp32 master shards → bf16 param
    all-gather).  Three rows:

    - ``dp`` — the baseline: fp32 masters + both moments replicated,
      fp32 grad all-reduce.
    - ``zero2`` — same global batch: the hbm_peak / state-bytes drop
      at unchanged math (final-loss agreement emitted; the band gate
      is ``test_loss_trajectory``'s DP-vs-ZeRO-2 leg).
    - ``zero2_grown`` — the reclaimed-capacity-becomes-throughput
      play: the per-chip batch grown until the ZeRO step's modeled
      HBM fills the DP baseline's budget, samples/sec at the larger
      batch.  (CPU-mesh proxy: the HBM numbers are XLA
      memory-analysis bytes of the compiled step — exact and
      deterministic; the wall ratio prices CPU compute, not HBM
      bandwidth — on chip the larger batch's win follows the
      roofline as usual.)

    Env: BENCH_BERT_ZERO_LAYERS (2), BENCH_BATCH (16 global),
    BENCH_SEQ (128), BENCH_ZERO_STEPS (8), BENCH_ZERO_GROWN_BATCH
    (0 = derive from the reclaimed bytes)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu import parallel as apx_parallel
    from apex_tpu.models import BertConfig, BertModel, bert_mlm_loss_fn
    from apex_tpu.optim import fused_adam
    from apex_tpu.parallel import ZeroConfig, zero_state_specs

    n_dev = jax.device_count()
    if n_dev < 2:
        _emit({"metric": "bert_o1_zero", "value": None,
               "skipped": f"needs >= 2 devices, have {n_dev}"})
        return
    layers = int(os.environ.get("BENCH_BERT_ZERO_LAYERS", "2"))
    b = int(os.environ.get("BENCH_BATCH", "16"))
    b -= b % n_dev
    b = max(b, n_dev)
    cfg = BertConfig.bert_large(remat=True, dtype=None,
                                scan_layers=False, num_layers=layers)
    model = BertModel(cfg)
    s = int(os.environ.get("BENCH_SEQ", str(min(cfg.max_seq_len, 128))))
    p = min(max(8, int(0.15 * s / 8 + 0.5) * 8), s)
    steps = int(os.environ.get("BENCH_ZERO_STEPS", "8"))

    def batch_of(nb):
        rng = jax.random.PRNGKey(0)
        ids = jax.random.randint(rng, (nb, s), 0, cfg.vocab_size)
        positions = jnp.argsort(jax.random.uniform(rng, (nb, s)),
                                axis=-1)[:, :p]
        return ids, positions, jnp.take_along_axis(ids, positions,
                                                   axis=1)

    init = model.init(jax.random.PRNGKey(0), batch_of(2)[0])
    n_params = sum(x.size for x in jax.tree.leaves(init))
    tx = fused_adam(1e-4)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n_dev]),
                             ("data",))

    def loss_grads(state, ids, positions, mlm_labels):
        def loss_fn(pr):
            cp = state.policy.cast_to_compute(pr)
            logits, _ = state.apply_fn(
                cp, ids, mlm_positions=positions, deterministic=True)
            loss = bert_mlm_loss_fn(logits.astype(jnp.float32),
                                    mlm_labels)
            return state.scale_loss(loss), loss

        return jax.grad(loss_fn, has_aux=True)(state.params)

    def measure(step, state, batch, nb, extra):
        compiled = bench._aot_compile(step, state, *batch)
        state, loss, finite = compiled(state, *batch)
        bench._sync(loss)                  # compile + warm
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss, finite = compiled(state, *batch)
        bench._sync(loss)
        dt = (time.perf_counter() - t0) / steps
        ana = compiled.memory_analysis()
        mem = {"argument": ana.argument_size_in_bytes,
               "output": ana.output_size_in_bytes,
               "temp": ana.temp_size_in_bytes}
        row = {
            "global_batch": nb,
            "samples_per_sec": round(nb / dt, 2),
            "step_ms": round(dt * 1e3, 2),
            "final_loss": round(float(loss), 5),
            "loss_finite": bool(finite),
            "hbm_analysis_bytes": mem,
            "hbm_peak_bytes": bench._analysis_estimate(mem),
        }
        row.update(extra)
        return row

    def run_dp(nb):
        state = amp.initialize(model.apply,
                               jax.tree.map(jnp.copy, init), tx,
                               opt_level="O2",
                               half_dtype=jnp.bfloat16)

        def dp_step(state, ids, positions, mlm_labels):
            grads, loss = loss_grads(state, ids, positions, mlm_labels)
            grads = apx_parallel.all_reduce_mean_grads(grads, "data")
            new_state, finite = state.apply_gradients(grads=grads)
            return new_state, jax.lax.pmean(loss, "data"), finite

        step = jax.jit(jax.shard_map(
            dp_step, mesh=mesh,
            in_specs=(P(), P("data"), P("data"), P("data")),
            out_specs=(P(), P(), P()), check_vma=False),
            donate_argnums=(0,))
        # replicated resident state: fp32 masters + both moments on
        # every chip
        state_bytes = sum(l.size * l.dtype.itemsize
                          for l in jax.tree.leaves(state.opt_state)) \
            + sum(l.size * l.dtype.itemsize
                  for l in jax.tree.leaves(state.params))
        return measure(step, state, batch_of(nb), nb,
                       {"layout": "replicated",
                        "state_bytes_per_chip": int(state_bytes)})

    def run_zero(nb):
        state = amp.initialize(model.apply,
                               jax.tree.map(jnp.copy, init), tx,
                               opt_level="O2", half_dtype=jnp.bfloat16,
                               zero=ZeroConfig(axis="data", stage=2,
                                               axis_size=n_dev))
        specs = zero_state_specs(state)

        def z_step(state, ids, positions, mlm_labels):
            grads, loss = loss_grads(state, ids, positions, mlm_labels)
            new_state, finite = state.apply_gradients(grads=grads)
            return new_state, jax.lax.pmean(loss, "data"), finite

        step = jax.jit(jax.shard_map(
            z_step, mesh=mesh,
            in_specs=(specs, P("data"), P("data"), P("data")),
            out_specs=(specs, P(), P()), check_vma=False),
            donate_argnums=(0,))
        # sharded resident state: 1/n of masters+moments + the bf16
        # param replica
        state_bytes = sum(
            -(-l.size // n_dev) * l.dtype.itemsize
            for l in jax.tree.leaves(state.opt_state)) \
            + sum(l.size * l.dtype.itemsize
                  for l in jax.tree.leaves(state.params))
        return measure(step, state, batch_of(nb), nb,
                       {"layout": "zero2_sharded",
                        "state_bytes_per_chip": int(state_bytes)})

    dp = run_dp(b)
    zero = run_zero(b)

    # grow the per-chip batch into the reclaimed HBM: activation bytes
    # scale ~linearly with batch (temp dominates), so the headroom in
    # samples is reclaimed / (temp / batch)
    grown = int(os.environ.get("BENCH_ZERO_GROWN_BATCH", "0"))
    reclaimed = (dp["hbm_peak_bytes"] or 0) - (zero["hbm_peak_bytes"]
                                               or 0)
    if not grown:
        temp = (zero["hbm_analysis_bytes"] or {}).get("temp") or 0
        per_sample = max(temp // max(b, 1), 1)
        grown = b + max(int(reclaimed // per_sample), 0)
        grown = min(grown, 4 * b)
        grown -= grown % n_dev
        grown = max(grown, b)
    zero_grown = run_zero(grown)
    fits = (zero_grown["hbm_peak_bytes"] or 0) <= \
        (dp["hbm_peak_bytes"] or 0)

    _emit({
        "metric": "bert_o2_zero2_samples_per_sec",
        "value": zero_grown["samples_per_sec"],
        "unit": "samples/sec (CPU-mesh proxy)",
        "replicas": n_dev, "seq": s, "num_layers": layers,
        "num_params": int(n_params),
        "rows": {"dp": dp, "zero2": zero, "zero2_grown": zero_grown},
        "hbm_peak_drop_bytes": int(reclaimed),
        "hbm_peak_drop_frac": round(
            reclaimed / dp["hbm_peak_bytes"], 3)
        if dp["hbm_peak_bytes"] else None,
        "state_bytes_saved_per_chip": (
            dp["state_bytes_per_chip"] - zero["state_bytes_per_chip"]),
        "grown_batch": grown,
        "grown_batch_fits_dp_hbm_budget": bool(fits),
        "sps_grown_vs_dp": round(
            zero_grown["samples_per_sec"]
            / max(dp["samples_per_sec"], 1e-9), 3),
        "final_loss_delta_equal_batch": round(
            abs(zero["final_loss"] - dp["final_loss"]), 5),
        "zero_bytes_on_wire": _zero_bytes_on_wire(n_params, n_dev),
        "note": ("ISSUE-11 row: optimizer bytes MOVE (sharded "
                 "residency, exact placed-array accounting above) and "
                 "the hbm numbers are XLA memory-analysis bytes of "
                 "the compiled steps; trajectory agreement is gated "
                 "by test_loss_trajectory's DP-vs-ZeRO-2 band leg; "
                 "the CPU wall ratio prices compute, not HBM — "
                 "on-chip the grown batch converts the reclaimed "
                 "capacity per the roofline"),
    })


# ----------------------------------------------------------------- llama 1B

def _llama_1b_cfg(variant):
    """1.03B-param Llama recipe (d=128 heads — full MXU lanes):
    hidden 2048 × 20 layers, GQA 16q/4kv, SwiGLU ffn 5632, RoPE,
    RMSNorm, untied head, no linear biases.

    Variants isolate the recipe's two levers (round-4 verdict item 1):
    ``mha``  — kv heads = q heads (16), everything else equal: what
               GQA buys (in training: qkv-proj params/flops + kv
               bandwidth; the cache win shows in the decode bench).
    ``gelu`` — ungated GELU MLP at ffn 8448 = iso-PARAM with the
               gated 3-matrix SwiGLU (2·2048·8448 = 3·2048·5632):
               what the SwiGLU structure costs at equal capacity.
    """
    import jax.numpy as jnp

    from apex_tpu.models import LlamaConfig

    kw = dict(
        # full 20 layers by default; override for smoke tests
        num_layers=int(os.environ.get("BENCH_LLAMA_LAYERS", "20")),
        max_seq_len=int(os.environ.get("BENCH_SEQ", "1024")),
        dtype=jnp.bfloat16, remat=True, scan_layers=False)
    if variant == "mha":
        kw["num_kv_heads"] = 16
    elif variant == "gelu":
        kw.update(gated_mlp=False, activation="gelu",
                  ffn_hidden_size=8448)
    return LlamaConfig.llama_1b(**kw)


def _llama_1b_single():
    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.models import LlamaModel, gpt_loss_fn
    from apex_tpu.optim import fused_adam

    var = os.environ["BENCH_LLAMA_VARIANT"]
    cfg = _llama_1b_cfg(var)
    model = LlamaModel(cfg)
    # b=8 OOMs this chip with the probe set live (1.03B O2 state +
    # fwd/bwd probe residents); b=4 fits with margin
    b = int(os.environ.get("BENCH_BATCH", "4"))
    s = cfg.max_seq_len

    ids = jax.random.randint(
        jax.random.PRNGKey(0), (b, s + 1), 0, cfg.vocab_size, jnp.int32)
    inputs, labels = ids[:, :-1], ids[:, 1:]
    params = model.init(jax.random.PRNGKey(0), inputs[:2])
    n_params = sum(x.size for x in jax.tree.leaves(params))
    state = amp.initialize(
        model.apply, params,
        fused_adam(1e-4, moment_dtype=jnp.bfloat16),
        opt_level="O2", half_dtype=jnp.bfloat16)

    def loss_of(state, p, inputs, labels):
        cp = state.policy.cast_to_compute(p)
        logits = state.apply_fn(cp, inputs)
        # bf16 logits straight into the fused CE (upcasts per-element)
        loss = gpt_loss_fn(logits, labels)
        return state.scale_loss(loss), loss

    # BENCH_ACCUM > 1: gradient accumulation over microbatches of
    # b/accum (set BENCH_BATCH to the GLOBAL batch — e.g. the measured
    # negative in BASELINE.md is BENCH_BATCH=8 BENCH_ACCUM=2) — the
    # amortization lever the round-5 overlap experiment points at
    # (optimizer/master streaming can't overlap more, but it CAN run
    # once per accum fwd+bwds; the single-shot b is HBM-capped at 4)
    accum = int(os.environ.get("BENCH_ACCUM", "1"))
    if b % accum:
        raise ValueError(
            f"BENCH_BATCH ({b}) must be divisible by BENCH_ACCUM "
            f"({accum})")
    if accum > 1:
        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(state, inputs, labels):
            mbs = jax.tree.map(
                lambda x: x.reshape(accum, x.shape[0] // accum,
                                    *x.shape[1:]), (inputs, labels))

            def body(acc, mb):
                g, l = jax.grad(
                    lambda p: loss_of(state, p, *mb),
                    has_aux=True)(state.params)
                acc_g, acc_l = acc
                return (jax.tree.map(
                    lambda a, gg: a + gg.astype(a.dtype), acc_g, g),
                    acc_l + l), None

            # bf16 accumulator: the fp32 one costs an extra 2 GB that
            # OOMs this chip; grads feed bf16 moments downstream anyway
            zero = (jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.bfloat16),
                state.params), jnp.zeros((), jnp.float32))
            (gsum, lsum), _ = jax.lax.scan(body, zero, mbs)
            grads = jax.tree.map(lambda g: g / accum, gsum)
            new_state, finite = state.apply_gradients(grads=grads)
            return new_state, lsum / accum, finite
    else:
        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(state, inputs, labels):
            grads, loss = jax.grad(
                lambda p: loss_of(state, p, inputs, labels),
                has_aux=True)(state.params)
            new_state, finite = state.apply_gradients(grads=grads)
            return new_state, loss, finite

    @jax.jit
    def fwd_only(state, inputs, labels):
        return loss_of(state, state.params, inputs, labels)[1]

    @jax.jit
    def fwd_bwd(state, inputs, labels):
        grads, loss = jax.grad(
            lambda p: loss_of(state, p, inputs, labels),
            has_aux=True)(state.params)
        return bench._probe_reduce(grads, loss)

    n_steps = int(os.environ.get("BENCH_STEPS", "20"))
    k_windows = max(1, int(os.environ.get("BENCH_WINDOWS", "3")))
    n_probe = max(n_steps // 2, 5)
    extra = {"batch": b, "seq": s, "variant": var, "accum": accum,
             "num_params": int(n_params)}
    if accum == 1:
        # probes run the whole global batch in one fwd/bwd — only
        # meaningful (and HBM-feasible) without accumulation
        t_fwd = bench._measure_fn(fwd_only, state, (inputs, labels),
                                  n_probe, k_windows)
        t_fb = bench._measure_fn(fwd_bwd, state, (inputs, labels),
                                 n_probe, k_windows)
        extra["fwd_ms"] = round(t_fwd * 1e3, 2)
        extra["bwd_ms"] = round(max(t_fb - t_fwd, 0.0) * 1e3, 2)
    out = _measure(state, step, (inputs, labels), b, extra)
    if accum == 1:
        out["opt_ms"] = round(
            max(out["step_ms"] / 1e3 - t_fb, 0.0) * 1e3, 2)
    out["tokens_per_sec"] = round(out["value"] * s, 1)
    out["metric"] = f"llama_1b_{var}_O2_fusedadam_samples_per_sec_per_chip"
    _emit(out)


def bench_llama_1b():
    """The Llama recipe on the scoreboard (round-4 verdict item 1):
    1.03B GQA+SwiGLU+RMSNorm+RoPE, O2+FusedAdam, measured on-chip with
    fwd/bwd/opt split and roofline self-check, plus the two A/B rows
    (GQA vs MHA; SwiGLU vs iso-param GELU).  One fresh process per
    variant (HBM not reclaimed promptly across builds)."""
    if os.environ.get("BENCH_LLAMA_VARIANT"):
        _llama_1b_single()
        return
    rows = {}
    for var in ("gqa", "mha", "gelu"):
        rows[var] = _run_child(
            "llama_1b", {"BENCH_LLAMA_VARIANT": var}, timeout=2400)
    main = dict(rows.get("gqa") or {})
    ab = {}
    if rows.get("mha", {}).get("value") and main.get("value"):
        ab["gqa_vs_mha_speedup"] = round(
            main["value"] / rows["mha"]["value"], 3)
    if rows.get("gelu", {}).get("value") and main.get("value"):
        ab["swiglu_vs_gelu_iso_param_speedup"] = round(
            main["value"] / rows["gelu"]["value"], 3)
    _emit({
        "metric": "llama_1b_pretrain_O2_fusedadam_samples_per_sec_per_chip",
        "value": main.get("value"),
        "unit": "samples/sec/chip",
        "rows": rows,
        "ab": ab,
    })


# ----------------------------------------------------------------- long ctx

def bench_long_context():
    """Long-context leg (beyond-reference: the reference's fmha caps at
    seqlen 512 buckets and apex has no context parallelism): full
    O2+FusedAdam train steps MEASURED at 8k, 16k and 32k tokens through
    the O(S) flash kernel — 16k/32k are past the point where the O(S²)
    composition stops compiling on this chip (the 8k row also records
    XLA's 32k attention temp-memory comparison as the capability
    proof).  Each sequence length runs in a fresh process (HBM is not
    reclaimed promptly across builds)."""
    if not os.environ.get("BENCH_LC_SINGLE"):
        # orchestrate: one fresh process per sequence length; do NOT
        # touch jax here — the child must be the only process holding
        # the chip
        rows = {}
        # the (32768, 4096) row is Mistral-style sliding-window: the
        # banded kernel grid pays only window/seq of full attention
        for s, w, m in ((8192, 0, "gpt"), (16384, 0, "gpt"),
                        (32768, 0, "gpt"), (32768, 4096, "gpt"),
                        # full-composition row (round-4 verdict weak
                        # #5): GQA×SWA×RoPE×RMSNorm×SwiGLU in ONE
                        # full train step at 32k
                        (32768, 4096, "llama")):
            key = (f"{s}w{w}" if w else str(s)) + (
                "_llama" if m == "llama" else "")
            rows[key] = _run_child(
                "long_context",
                {"BENCH_LC_SINGLE": "1", "BENCH_SEQ": str(s),
                 "BENCH_WINDOW": str(w), "BENCH_LC_MODEL": m},
                timeout=1500)
        out8 = dict(rows.get("8192") or {})
        out8.pop("metric", None)
        _emit({
            "metric": "gpt_long_context_O2_tokens_per_sec_per_chip",
            "value": out8.get("tokens_per_sec"),
            "unit": "tokens/sec/chip",
            "rows": rows,
        })
        return
    _long_context_single()


def _long_context_single():
    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.models import GPTConfig, GPTModel, gpt_loss_fn
    from apex_tpu.optim import fused_adam
    from apex_tpu.ops.attention import fused_attention, attention_reference

    b = int(os.environ.get("BENCH_BATCH", "1"))
    s = int(os.environ.get("BENCH_SEQ", "8192"))
    w = int(os.environ.get("BENCH_WINDOW", "0")) or None
    lc_model = os.environ.get("BENCH_LC_MODEL", "gpt")
    # shared bench settings; qkv_grouped off: no TP on a single chip
    # to profit from the grouped layout, and its strided-slice temps
    # (2x-padded at d=64) cost real HBM at 16k-32k tokens
    common = dict(max_seq_len=s, sliding_window=w, dtype=jnp.bfloat16,
                  remat=True, scan_layers=False, qkv_grouped=False)
    if lc_model == "llama":
        # the full-composition row: GQA (16q/4kv) × sliding window ×
        # RoPE × RMSNorm × SwiGLU at d=128, one real train step at
        # 32k — the llama_1b recipe geometry at 6 layers (12 OOMs:
        # the 32000-vocab CE at 32k tokens costs ~6 GB by itself;
        # composition, not depth, is what this row certifies)
        from apex_tpu.models import LlamaConfig

        cfg = LlamaConfig.llama_1b(num_layers=6, **common)
    else:
        cfg = GPTConfig(
            vocab_size=32768, hidden_size=1024, num_layers=12,
            num_heads=16, **common)
    model = GPTModel(cfg)
    ids = jax.random.randint(
        jax.random.PRNGKey(0), (b, s + 1), 0, cfg.vocab_size, jnp.int32)
    inputs, labels = ids[:, :-1], ids[:, 1:]
    params = model.init(jax.random.PRNGKey(0), inputs[:1])
    state = amp.initialize(
        model.apply, params, fused_adam(1e-4, moment_dtype=jnp.bfloat16),
        opt_level="O2", half_dtype=jnp.bfloat16)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, inputs, labels):
        def loss_fn(p):
            cp = state.policy.cast_to_compute(p)
            logits = state.apply_fn(cp, inputs)
            # bf16 logits straight into the fused CE (it upcasts
            # per-element internally): materializing f32 logits first
            # costs an extra 2·b·s·V·2-byte pass and doubles the
            # xentropy residual at 32k vocab
            loss = gpt_loss_fn(logits, labels)
            return state.scale_loss(loss), loss

        grads, loss = jax.grad(loss_fn, has_aux=True)(state.params)
        new_state, finite = state.apply_gradients(grads=grads)
        return new_state, loss, finite

    # Uniform phase-sum bound for the whole ladder (round-4 verdict
    # weak #2 — and a round-5 correction: XLA's cost model reports
    # flops=None for Pallas custom calls, so the round-4 "kernel-own
    # bound" 16k/32k rows were accidentally scoring the bound on the
    # NON-attention remainder only).  The flash kernels' work is
    # accounted analytically — tools/attn_bench.py's useful-flop
    # units: one tile-matmul = 2·b·h·visible_pairs·d; per step the
    # kernels run 9 units (fwd 2 + dq 3 + dkv 4) — at the family's
    # MEASURED achievable rate (93 TFLOP/s full-causal, 70 windowed;
    # the d=64 contraction padding caps it below chip peak).  NOT 11:
    # although remat=True nominally re-runs the forward in the
    # backward, the layers remat with prevent_cse=False and the
    # measured step times REFUTE an executed re-run — counting 11
    # units puts the 16k/32k bounds at 1.00-1.06 of the measured
    # clock, i.e. attention alone would need longer than the whole
    # step minus its XLA work; the only consistent reading is that
    # XLA CSEs the recomputed fwd kernel against the original.
    ww = min(w or s, s)
    pairs = (ww - 1) * ww / 2 + (s - ww + 1) * ww
    unit = 2 * b * cfg.num_heads * pairs * cfg.head_dim
    attn_flops = 9 * unit * cfg.num_layers
    if cfg.head_dim == 128:
        # d=128 GQA rates measured at this exact geometry
        # (tools/attn_bench.py h=16 hk=4 d=128: windowed 162.4,
        # full-causal (h32/kv8) 152.5 fwd+bwd useful TFLOP/s)
        attn_rate = (162.0 if w else 152.0) * 1e12
    else:
        attn_rate = (70.0 if w else 93.0) * 1e12
    # kernel I/O visible to XLA (deducted from its bytes-accessed so
    # the phase-sum bound never counts this traffic twice), per layer
    # per step, GQA-aware: q-head-sized bf16 passes — q reads ×3
    # calls, o write, do reads ×2, dq write = 7; kv-head-sized — k,v
    # reads ×3 calls = 6; dk/dv — direct bf16 kv-head writes under
    # MHA, but with rep>1 the dkv kernel writes PER-Q-HEAD fp32
    # partials that XLA then group-sums (write+read f32 ×2 tensors)
    # before the kv-head-sized bf16 result
    io_h = b * s * cfg.num_heads * cfg.head_dim * 2
    io_hk = b * s * cfg.kv_heads * cfg.head_dim * 2
    io_h_f32 = 2 * io_h
    dkv_io = (2 * io_hk if cfg.kv_heads == cfg.num_heads
              else 2 * 2 * io_h_f32 + 2 * io_hk)
    lse_io = b * s * cfg.num_heads * 4
    attn_xla_bytes = cfg.num_layers * (
        7 * io_h + 6 * io_hk + dkv_io + 5 * lse_io)
    out = _measure(
        state, step, (inputs, labels), b,
        {"batch": b, "seq": s, "window": w},
        phase_bounds=[{"name": "flash_attention_fwd_bwd",
                       "seconds": attn_flops / attn_rate,
                       "flops": attn_flops,
                       "xla_bytes": attn_xla_bytes}])
    out["tokens_per_sec"] = round(out["value"] * s, 1)

    if s == 8192:
        # 32k capability proof: compile one attention fwd+bwd both ways
        # and compare XLA's per-device temp memory (no execution)
        s32, h, d = 32768, 8, 64
        q = jax.ShapeDtypeStruct((1, s32, h, d), jnp.bfloat16)

        def attn_loss(impl):
            def f(qq, kk, vv):
                o = (fused_attention(qq, kk, vv, causal=True,
                                     implementation="pallas")
                     if impl == "pallas" else
                     attention_reference(qq, kk, vv, causal=True))
                return jnp.sum(o.astype(jnp.float32) ** 2)
            return jax.jit(jax.grad(f, argnums=(0, 1, 2)))

        mems = {}
        for impl in ("pallas", "xla"):
            try:
                stats = attn_loss(impl).lower(q, q, q).compile(
                ).memory_analysis()
                mems[impl] = int(stats.temp_size_in_bytes)
            except Exception as e:                 # composition may not
                mems[impl] = f"uncompilable: {type(e).__name__}"  # fit
        out["attn_32k_temp_bytes"] = mems
    tag = (f"{s//1024}k" + (f"_swa{w//1024}k" if w else "")
           + ("_llama_gqa" if lc_model == "llama" else ""))
    out["metric"] = f"gpt_long_context_{tag}_O2_samples_per_sec_per_chip"
    _emit(out)


# ---------------------------------------------------------------- serving

# (lifted to apex_tpu/plan/costs.py — imported back above as _serving_traffic_model)


def bench_prefix_spec_serving():
    """Prefix-sharing + speculative-decoding scoreboard (ISSUE 7).

    Two rows on the paged datapath, tiny-GPT proxy (CPU smoke — the
    protocol and the RATIOS are the artifact, like ``fleet_serving``):

    - **shared-system-prompt A/B at EQUAL HBM**: every request carries
      the same system prompt + a small unique tail; the same pool is
      served with ``share_prefixes`` off vs on.  Off, each tenant
      charges the pool its full prompt, the token-budget gate admits
      only a couple at a time, and the rest queue; on, the prefix's
      pages are mapped refcounted so the SAME pool admits the whole
      wave — reclaimed capacity converts into admitted occupancy and
      therefore tokens/s (reported with TTFT p50/p99, which also
      collapses: shared admissions skip the prefix prefill compute).
      ``pool capacity in tokens`` is reported shared vs unshared from
      the analytic traffic model + the measured ``blocks_saved`` peak.
    - **speculative decoding on a prompt-lookup-friendly workload**:
      repetitive prompts, drafted with the n-gram prompt-lookup
      drafter at K = ``BENCH_PSS_SPEC_K``.  The honest accelerator
      metric is **decode tokens per STEP** (= 1 + accepted drafts per
      verify step): a TPU decode step is HBM-bound on the param/KV
      stream, so at K ≪ seq the verify step costs ≈ one decode step
      and tokens/s scales with tokens/step; the CPU proxy's wall
      tokens/s is also reported but is compute-bound (verify width
      costs linearly) and NOT the acceptance number.

    Env: BENCH_PSS_SYS (192), BENCH_PSS_USER (12), BENCH_PSS_TOKENS
    (32), BENCH_PSS_SLOTS (6), BENCH_PSS_SPEC_K (4),
    BENCH_PSS_BLOCK (16)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serving import (
        InferenceServer,
        PagedEngine,
        Request,
        Scheduler,
    )

    SYS = int(os.environ.get("BENCH_PSS_SYS", "192"))
    U = int(os.environ.get("BENCH_PSS_USER", "12"))
    N = int(os.environ.get("BENCH_PSS_TOKENS", "32"))
    slots = int(os.environ.get("BENCH_PSS_SLOTS", "6"))
    K = int(os.environ.get("BENCH_PSS_SPEC_K", "4"))
    block = int(os.environ.get("BENCH_PSS_BLOCK", "16"))

    cfg = GPTConfig.tiny(position_embedding="learned",
                         scan_layers=True)
    if SYS + U + N + 2 > cfg.max_seq_len:
        raise ValueError("BENCH_PSS_SYS+USER+TOKENS exceeds the "
                         f"proxy's max_seq_len ({cfg.max_seq_len})")
    model = GPTModel(cfg)
    params = {"params": model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 4), jnp.int32))["params"]}
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(0, cfg.vocab_size,
                              size=(SYS,)).astype(np.int32)
    prompts = [np.concatenate([sys_prompt, rng.integers(
        0, cfg.vocab_size, size=(U,)).astype(np.int32)])
        for _ in range(slots)]

    # -------- A: shared-system-prompt wave at EQUAL HBM --------------
    # the pool holds ONE copy of the system prefix + every tenant's
    # private tail (+decode headroom) — unshared, the same pool fits
    # only ~pool/(SYS+U+N) tenants and the rest queue behind the
    # token-budget admission gate
    pool_tokens = SYS + slots * (U + N + 2 * block) + 2 * block

    def run_wave(share):
        server = InferenceServer(
            model, params, max_slots=slots,
            block_size=block, pool_tokens=pool_tokens,
            prefill_chunk=32, share_prefixes=share)
        peak_saved = 0
        with server:
            t0 = time.perf_counter()
            handles = [server.submit(p, max_new_tokens=N, seed=i)
                       for i, p in enumerate(prompts)]
            while not all(h.done for h in handles):
                peak_saved = max(peak_saved,
                                 server.engine.blocks_saved)
                time.sleep(0.005)
            tokens = sum(len(h.result(timeout=600)) for h in handles)
            wall = time.perf_counter() - t0
            lat = server.latency_summary()
            assert server.engine.blocks_in_use == 0
        return {
            "share_prefixes": share,
            "tokens_per_sec": round(tokens / wall, 1),
            "wall_s": round(wall, 3),
            "ttft_p50_ms": round(lat.get("ttft_p50_s", 0.0) * 1e3, 1),
            "ttft_p99_ms": round(lat.get("ttft_p99_s", 0.0) * 1e3, 1),
            "peak_blocks_saved": int(peak_saved),
            "cow_forks": int(server.engine.cow_forks),
        }

    unshared = run_wave(False)
    shared = run_wave(True)
    tm = _serving_traffic_model(
        num_layers=cfg.num_layers, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, max_seq_len=cfg.max_seq_len,
        live_tokens=SYS + U + N, slots=slots, block_size=block,
        dtype_bytes=4, shared_prefix_tokens=SYS)
    _emit({
        "metric": "prefix_spec_serving_shared_tokens_per_sec",
        "value": shared["tokens_per_sec"],
        "unit": "tokens/sec (CPU-proxy smoke)",
        "system_prompt": SYS, "user_tail": U, "budget": N,
        "slots": slots, "block_size": block,
        "pool_tokens": pool_tokens,
        "hbm_budget": "equal pool both rows",
        "rows": {"unshared": unshared, "shared": shared},
        "tps_vs_unshared": round(
            shared["tokens_per_sec"]
            / max(unshared["tokens_per_sec"], 1e-9), 2),
        "pool_capacity_tokens_unshared":
            tm["paged_live_pool_tokens_unshared"],
        "pool_capacity_tokens_shared":
            tm["paged_live_pool_tokens_shared"],
        "analytic_kv_traffic": tm,
        "note": ("equal-HBM A/B: sharing admits the whole wave where "
                 "the unshared pool serializes it behind the token "
                 "gate — tokens/s tracks admitted occupancy; TTFT "
                 "also collapses because shared admissions skip the "
                 "prefix prefill"),
    })

    # -------- B: speculative decoding, lookup-friendly workload ------
    # prompt lookup pays when generation CONTINUES spans of the
    # context (summarization, code edits, few-shot) — an ability a
    # RANDOM init does not have.  Briefly train the proxy on cyclic
    # sequences so it (like any real LM) continues repetitions, then
    # serve prompts of 1.5 periods: the drafter finds the continuation
    # one period back and the trained model actually emits it.
    from apex_tpu.models import gpt_loss_fn

    train_steps = int(os.environ.get("BENCH_PSS_TRAIN_STEPS", "200"))
    period = 24
    cyc = rng.permutation(min(cfg.vocab_size, 256))[:period] \
        .astype(np.int32)
    tparams = model.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 4), jnp.int32))["params"]

    def cyc_batch(bs, L):
        phases = rng.integers(0, period, size=bs)
        idx = (phases[:, None] + np.arange(L + 1)) % period
        return jnp.asarray(cyc[idx])

    @jax.jit
    def sgd_step(p, ids, lr):
        def loss_fn(p):
            logits = model.apply({"params": p}, ids[:, :-1],
                                 deterministic=True)
            return gpt_loss_fn(logits, ids[:, 1:])
        loss, grads = jax.value_and_grad(loss_fn)(p)
        return jax.tree.map(lambda a, g: a - lr * g, p, grads), loss

    loss = None
    for i in range(train_steps):
        tparams, loss = sgd_step(
            tparams, cyc_batch(8, 48),
            jnp.float32(0.5 if i < train_steps // 2 else 0.2))
    trained = {"params": tparams}
    spec_prompts = [np.asarray(
        cyc[(ph + np.arange(period + period // 2)) % period],
        np.int32) for ph in range(slots)]

    def run_spec(k):
        engine = PagedEngine(model, trained, max_slots=slots,
                             block_size=block, prefill_chunk=32,
                             spec_tokens=k, spec_ngram=2)
        engine.warmup()
        sched = Scheduler(engine)
        reqs = [sched.submit(Request(prompt=p, max_new_tokens=N,
                                     seed=i))
                for i, p in enumerate(spec_prompts)]
        while any(t is not None and t.fed < t.prompt.size
                  for t in engine._tenants):
            sched.run_step()          # prefill outside the window
        t0 = time.perf_counter()
        steps, row_steps, tokens = 0, 0, 0
        while sched.has_work():
            events = sched.run_step()
            steps += 1
            # one row-step per DISTINCT emitting row: an undrafted
            # run scores exactly 1.0 token per row-step, a drafted
            # one 1 + accepted-per-verify — batch-size-independent
            row_steps += len({id(ev.request) for ev in events})
            tokens += len(events)
        wall = time.perf_counter() - t0
        assert tokens == sum(len(r.tokens) for r in reqs)
        assert engine.blocks_in_use == 0
        return {
            "spec_tokens": k,
            "decode_tokens_per_sec": round(tokens / wall, 1),
            "decode_steps": steps,
            "tokens_per_row_step": round(tokens / max(row_steps, 1),
                                         3),
            "accept_rate": round(engine.spec_accept_rate, 3),
            "proposed": int(engine.spec_proposed),
            "accepted": int(engine.spec_accepted),
        }

    base = run_spec(0)
    spec = run_spec(K)
    _emit({
        "metric": f"prefix_spec_serving_spec_k{K}_tokens_per_row_step",
        "value": spec["tokens_per_row_step"],
        "unit": "decode tokens/row-step (HBM-bound tokens/s proxy)",
        "slots": slots, "budget": N, "spec_ngram": 2,
        "proxy_train_steps": train_steps,
        "proxy_train_loss": round(float(loss), 4),
        "rows": {"undrafted": base, "drafted": spec},
        "tokens_per_row_step_vs_undrafted": round(
            spec["tokens_per_row_step"]
            / max(base["tokens_per_row_step"], 1e-9), 2),
        "wall_tps_vs_undrafted_cpu": round(
            spec["decode_tokens_per_sec"]
            / max(base["decode_tokens_per_sec"], 1e-9), 2),
        "note": ("tokens/row-step is the accelerator metric: a TPU "
                 "decode step is HBM-bound on the param/KV stream, so "
                 "a K-token verify costs ≈ one width-1 step and "
                 "tokens/s scales with tokens/row-step at the "
                 "measured accept rate; the CPU proxy's wall ratio is "
                 "compute-bound (verify width is linear cost there) "
                 "and reported only for honesty"),
    })


def bench_quantized_kv_serving():
    """Quantized KV pages scoreboard (ISSUE 8): equal-HBM A/B of the
    unquantized paged pool vs an ``kv_dtype="int8"`` pool holding 2×
    the slots in the SAME byte budget, tiny-GPT proxy (CPU smoke — the
    protocol and the RATIOS are the artifact, like
    ``prefix_spec_serving``).

    Protocol: a wave of ``2 × quantized slots`` independent requests
    hits both servers.  The unquantized pool fits only
    ``pool_bytes / (fp32 K+V bytes/token)`` tokens, the token-budget
    admission gate serializes the wave behind it; the int8 pool's same
    bytes hold ~3.9× the tokens (scales included — fp32 compute proxy;
    2× from bf16), so 2× the slots admit concurrently and tokens/s
    tracks admitted occupancy exactly as the ISSUE-5 occupancy sweep
    measured (2× slots → 2.25× tokens/s at equal HBM on-chip; the CPU
    wall ratio reported here is compute-bound and understates it).
    The smoke ASSERTS the capacity side — ≥1.9× pool tokens at equal
    HBM from the extended traffic model AND from the engines' actual
    pool sizes — and reports tokens/s + TTFT p50/p99 for both rows.

    Env: BENCH_QKV_SLOTS (3), BENCH_QKV_PROMPT (24), BENCH_QKV_TOKENS
    (16), BENCH_QKV_BLOCK (8)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.ops.paged_attention import kv_store_bytes_per_token
    from apex_tpu.serving import InferenceServer

    slots = int(os.environ.get("BENCH_QKV_SLOTS", "3"))
    P = int(os.environ.get("BENCH_QKV_PROMPT", "24"))
    N = int(os.environ.get("BENCH_QKV_TOKENS", "16"))
    block = int(os.environ.get("BENCH_QKV_BLOCK", "8"))

    cfg = GPTConfig.tiny(position_embedding="learned",
                         scan_layers=True)
    if P + N + 2 > cfg.max_seq_len:
        raise ValueError("BENCH_QKV_PROMPT+TOKENS exceeds the proxy's "
                         f"max_seq_len ({cfg.max_seq_len})")
    model = GPTModel(cfg)
    params = {"params": model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 4), jnp.int32))["params"]}
    rng = np.random.default_rng(0)

    # the shared byte budget: an unquantized pool that fits the base
    # slot count's working set (prompt + budget + page slack)
    per_tenant = P + N + 2 * block
    pool_base = slots * per_tenant
    # K+V bytes per token per (kv_head, layer) — the common factor
    # cancels in the ratio; the shared formula is the one
    # PagedEngine's equal-HBM default admits with
    unq_tok = kv_store_bytes_per_token(cfg.head_dim, block,
                                       dtype=cfg.dtype)
    q_tok = kv_store_bytes_per_token(cfg.head_dim, block, "int8")
    pool_quant = int(pool_base * unq_tok / q_tok)
    q_slots = 2 * slots
    wave = 2 * q_slots
    prompts = [rng.integers(0, cfg.vocab_size, size=(P,))
               .astype(np.int32) for _ in range(wave)]

    def run_wave(kv_dtype, max_slots, pool_tokens):
        server = InferenceServer(
            model, params, max_slots=max_slots,
            block_size=block, pool_tokens=pool_tokens,
            prefill_chunk=8, kv_dtype=kv_dtype)
        with server:
            t0 = time.perf_counter()
            handles = [server.submit(p, max_new_tokens=N, seed=i)
                       for i, p in enumerate(prompts)]
            tokens = sum(len(h.result(timeout=600)) for h in handles)
            wall = time.perf_counter() - t0
            lat = server.latency_summary()
            assert server.engine.blocks_in_use == 0
            pool = server.engine.pool_tokens
        return {
            "kv_dtype": kv_dtype or "none",
            "slots": max_slots,
            "pool_tokens": pool,
            "tokens_per_sec": round(tokens / wall, 1),
            "wall_s": round(wall, 3),
            "ttft_p50_ms": round(lat.get("ttft_p50_s", 0.0) * 1e3, 1),
            "ttft_p99_ms": round(lat.get("ttft_p99_s", 0.0) * 1e3, 1),
        }

    base = run_wave(None, slots, pool_base)
    quant = run_wave("int8", q_slots, pool_quant)
    capacity_mult = quant["pool_tokens"] / base["pool_tokens"]
    assert capacity_mult >= 1.9, (
        f"equal-HBM int8 pool holds only {capacity_mult:.2f}x the "
        "tokens (acceptance: >= 1.9x, scales included)")
    tm = _serving_traffic_model(
        num_layers=cfg.num_layers, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, max_seq_len=cfg.max_seq_len,
        live_tokens=P + N, slots=q_slots, block_size=block,
        dtype_bytes=comp_bytes, kv_dtype="int8")
    assert tm["quantized_capacity_multiplier"] >= 1.9
    _emit({
        "metric": "quantized_kv_serving_int8_tokens_per_sec",
        "value": quant["tokens_per_sec"],
        "unit": "tokens/sec (CPU-proxy smoke)",
        "prompt": P, "budget": N, "block_size": block,
        "hbm_budget": f"= unquantized pool at {slots} slots "
                      f"({base['pool_tokens']} tokens)",
        "rows": {"unquantized": base, "int8_2x_slots": quant},
        "pool_capacity_multiplier_at_equal_hbm":
            round(capacity_mult, 2),
        "tps_vs_unquantized": round(
            quant["tokens_per_sec"]
            / max(base["tokens_per_sec"], 1e-9), 2),
        "analytic_kv_traffic": tm,
        "note": ("equal-HBM A/B: the int8 pool admits 2x the slots in "
                 "the same bytes; what that buys in sustained tokens/s "
                 "is a chip measurement — the "
                 "CPU wall ratio here is compute-bound (dequant is "
                 "arithmetic, not bandwidth, on CPU) and reported for "
                 "honesty; the asserted artifact is the capacity side, "
                 "scales included"),
    })


# ----------------------------------------------------------------- decode

def _decode_single():
    """One (batch, max_seq_len, attn-impl) decode measurement: prefill
    tokens/s + steady-state per-token decode latency on the llama_1b
    GQA model, with a bytes/token roofline (decode is the canonical
    HBM-bound workload: every token reads all params + the KV cache)."""
    import dataclasses
    import time

    import jax
    import jax.numpy as jnp

    from apex_tpu.models import LlamaModel, init_cache

    b = int(os.environ["BENCH_DECODE_BATCH"])
    S = int(os.environ["BENCH_DECODE_MAXLEN"])
    P = int(os.environ.get("BENCH_DECODE_PROMPT", "1024"))
    N = int(os.environ.get("BENCH_DECODE_TOKENS", "64"))
    # host-side read, plumbed through config (part of the compile
    # signature) — the model no longer reads this env var at trace time
    attn = os.environ.get("APEX_TPU_DECODE_ATTN", "auto")
    cfg = dataclasses.replace(_llama_1b_cfg("gqa"), max_seq_len=S,
                              decode_attn=attn)
    model = LlamaModel(cfg)

    ids = jax.random.randint(
        jax.random.PRNGKey(0), (b, P), 0, cfg.vocab_size, jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids[:1, :8])
    # inference: bf16 params (the O2 compute copy; no masters needed)
    params = {"params": jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params["params"])}
    n_params = sum(x.size for x in jax.tree.leaves(params))
    cache = init_cache(model, b)

    def apply(params, cache, ids):
        logits, upd = model.apply(
            {**params, "cache": cache}, ids, deterministic=True,
            decode=True, mutable=["cache"])
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32),
                         axis=-1).astype(jnp.int32)
        return nxt, upd["cache"]

    prefill = jax.jit(apply)

    @jax.jit
    def decode_n(params, cache, tok):
        def step(carry, _):
            cache, tok = carry
            nxt, cache = apply(params, cache, tok[:, None])
            return (cache, nxt), None

        (cache, tok), _ = jax.lax.scan(step, (cache, tok), None,
                                       length=N)
        return tok

    tok, filled = prefill(params, cache, ids)          # warm + fill
    bench._sync(tok)
    dec = bench._aot_compile(decode_n, params, filled, tok)
    bench._sync(dec(params, filled, tok))
    ovh = bench._call_overhead()
    k_windows = max(1, int(os.environ.get("BENCH_WINDOWS", "3")))

    reps = 5

    def prefill_window():
        t0 = time.perf_counter()
        for _ in range(reps):
            nxt, _f = prefill(params, cache, ids)
        bench._sync(nxt)
        return (time.perf_counter() - t0 - ovh) / reps

    t_pre, pre_w = bench._time_windows(prefill_window, k_windows)

    def decode_window():
        t0 = time.perf_counter()
        for _ in range(2):
            out = dec(params, filled, tok)
        bench._sync(out)
        return (time.perf_counter() - t0 - ovh) / 2

    t_dec, dec_w = bench._time_windows(decode_window, k_windows)
    t_tok = t_dec / N

    # bytes/token roofline: params once + KV (k and v) per layer, bf16.
    # 'full' = the whole (b, S, hk, d) cache (what the one-shot einsum
    # reads); 'live' = the filled prefix P..P+N only (what the blocked
    # skip bounds reads to).
    kvb = cfg.num_layers * b * cfg.kv_heads * cfg.head_dim * 2 * 2
    bytes_full = 2 * n_params + kvb * S
    bytes_live = 2 * n_params + kvb * (P + N // 2)
    out = {
        "batch": b, "max_seq_len": S, "prompt": P,
        "decode_attn": cfg.decode_attn,
        "num_params": int(n_params),
        "prefill_tokens_per_sec": round(b * P / t_pre, 1),
        "prefill_ms": round(t_pre * 1e3, 2),
        "prefill_window_ms": [round(d * 1e3, 2) for d in pre_w],
        "decode_tokens_per_sec": round(b / t_tok, 1),
        "decode_ms_per_token": round(t_tok * 1e3, 3),
        "decode_window_ms": [round(d * 1e3, 2) for d in dec_w],
        "bytes_per_token_model": {
            "params": 2 * n_params, "kv_full_cache": kvb * S,
            "kv_live": kvb * (P + N // 2)},
        "achieved_gbs_vs_full_read": round(
            bytes_full / t_tok / 1e9, 1),
        "achieved_gbs_vs_live_read": round(
            bytes_live / t_tok / 1e9, 1),
        "frac_of_peak_hbm_live": bench.frac_of_hbm_peak(
            bytes_live / t_tok / 1e9),
    }
    byts = float((dec.cost_analysis() or {}).get("bytes accessed", 0.0))
    if byts:
        out["cost_bytes_per_token"] = round(byts / N, 1)
    out["metric"] = f"llama1b_decode_b{b}_S{S}"
    _emit(out)


def bench_decode():
    """Generation scoreboard (round-4 verdict item 2a): prefill +
    steady-state decode throughput of the llama_1b recipe at
    b ∈ {1, 8, 32}, plus the full-vs-live cache-read A/B (the dense
    einsum reads all max_seq_len slots every token; the blocked form
    skips dead blocks) at 2k and 8k cache sizes."""
    if os.environ.get("BENCH_DECODE_BATCH"):
        _decode_single()
        return
    runs = [
        ("b1_S2048", {"BENCH_DECODE_BATCH": "1",
                      "BENCH_DECODE_MAXLEN": "2048"}),
        ("b8_S2048", {"BENCH_DECODE_BATCH": "8",
                      "BENCH_DECODE_MAXLEN": "2048"}),
        ("b32_S2048", {"BENCH_DECODE_BATCH": "32",
                       "BENCH_DECODE_MAXLEN": "2048"}),
        ("b8_S2048_einsum", {"BENCH_DECODE_BATCH": "8",
                             "BENCH_DECODE_MAXLEN": "2048",
                             "APEX_TPU_DECODE_ATTN": "einsum"}),
        ("b8_S8192", {"BENCH_DECODE_BATCH": "8",
                      "BENCH_DECODE_MAXLEN": "8192"}),
        ("b8_S8192_einsum", {"BENCH_DECODE_BATCH": "8",
                             "BENCH_DECODE_MAXLEN": "8192",
                             "APEX_TPU_DECODE_ATTN": "einsum"}),
    ]
    rows = {}
    for key, env_kw in runs:
        rows[key] = _run_child("decode", env_kw, timeout=1500)
    head = rows.get("b8_S2048") or {}
    _emit({
        "metric": "llama1b_decode_tokens_per_sec",
        "value": head.get("decode_tokens_per_sec"),
        "unit": "tokens/sec (b=8, S=2048)",
        "rows": rows,
    })


# ------------------------------------------------------- decode epilogue

def bench_decode_epilogue():
    """Fused decode-step epilogue A/B (ISSUE 14): the decode
    executable with the HISTORICAL sampling tail — full-vocab sort,
    softmax, cumsum, masking passes and the categorical draw as
    separate XLA ops over ``(slots, vocab)`` — against the fused
    one-pass epilogue (``ops.fused_sampling``), reporting XLA
    cost-analysis bytes and wall tokens/s.

    Bytes protocol: every arm that XLA can compile on this backend is
    MEASURED via ``Compiled.cost_analysis()`` (the
    ``test_paged_attention`` protocol).  On TPU that includes the
    fused step, whose pallas call declares its true one-pass traffic
    through ``pl.CostEstimate`` —
    ``fused_sampling.sampling_cost_bytes``, the logits read once.  On
    the CPU smoke the Mosaic kernel cannot compile, so the fused
    step's bytes are COMPOSED from measured parts: (measured unfused
    step − measured unfused tail) + the kernel's declared cost — i.e.
    exactly the rollup a TPU cost analysis performs — and the
    interpret-mode kernel's measured bytes ride alongside as a
    cross-check (they OVERSTATE the kernel: interpret materializes
    every VMEM pass as a buffer).  ``fused_bytes_source`` names which
    path produced the headline number.  The ≥10% acceptance drop on
    the decode executable is asserted here, on the CPU smoke.

    Wall rows are host wall (noisy on CPU — the kernel itself isn't
    in play off-chip; documented, not asserted), EXCEPT the
    sort-short-circuit row: the satellite fix gates the reference's
    sort + cumsum tail behind a runtime ``lax.cond`` on any row
    enabling top-k/top-p, so an ALL-GREEDY step measurably skips the
    sort even on CPU — ``greedy_shortcircuit_speedup`` is that
    measured ratio (the pre-PR tail paid the sort anyway).

    Env: BENCH_EPILOGUE_SLOTS (16), BENCH_EPILOGUE_VOCAB (16384),
    BENCH_EPILOGUE_WIDTH (4 — the spec-step ``1+K`` row),
    BENCH_EPILOGUE_LAYERS (2)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.models.generate import apply_decode, init_cache
    from apex_tpu.ops.fused_sampling import (
        fused_sample,
        fused_sample_reference,
        sampling_cost_bytes,
    )

    slots = int(os.environ.get("BENCH_EPILOGUE_SLOTS", "16"))
    V = int(os.environ.get("BENCH_EPILOGUE_VOCAB", "16384"))
    W = int(os.environ.get("BENCH_EPILOGUE_WIDTH", "4"))
    L = int(os.environ.get("BENCH_EPILOGUE_LAYERS", "2"))
    k_windows = max(1, int(os.environ.get("BENCH_WINDOWS", "3")))
    on_tpu = jax.default_backend() == "tpu"

    cfg = GPTConfig.tiny(vocab_size=V, num_layers=L,
                         position_embedding="learned",
                         scan_layers=True)
    model = GPTModel(cfg)
    rng = np.random.default_rng(0)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    variables = {"params": params["params"]}
    cache = init_cache(model, slots)
    tok = jnp.asarray(rng.integers(1, V, (slots,)), jnp.int32)
    keys = jax.vmap(jax.random.PRNGKey)(
        jnp.arange(slots, dtype=jnp.uint32))
    mixed = dict(
        temperature=jnp.asarray(
            rng.choice([0.0, 0.7, 1.0], slots), jnp.float32),
        top_k=jnp.asarray(rng.choice([0, 8, 40], slots), jnp.int32),
        top_p=jnp.asarray(rng.choice([0.0, 0.9], slots), jnp.float32))
    greedy = dict(temperature=jnp.zeros((slots,), jnp.float32),
                  top_k=jnp.zeros((slots,), jnp.int32),
                  top_p=jnp.zeros((slots,), jnp.float32))

    def legacy_tail(logits, keys, temperature, top_k, top_p):
        # the pre-fusion sample_dynamic body — the executable tail
        # every decode step used to pay, sort and all, regardless of
        # which filters the admitted rows enabled
        logits = logits.astype(jnp.float32)
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        k = jnp.where(top_k > 0, top_k, V)
        ordered = jnp.sort(scaled, axis=-1)
        kth = jnp.take_along_axis(ordered, (V - k)[:, None], axis=-1)
        scaled = jnp.where(scaled < kth, -1e30, scaled)
        p_on = (top_p > 0.0) & (top_p < 1.0)
        desc = jnp.where(ordered[:, ::-1] < kth, -1e30,
                         ordered[:, ::-1])
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < jnp.where(p_on, top_p, 1.0)[:, None]
        thresh = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                         keepdims=True)
        scaled = jnp.where(p_on[:, None] & (scaled < thresh), -1e30,
                           scaled)
        s = jax.vmap(jax.random.categorical)(keys, scaled)
        return jnp.where(temperature > 0.0, s.astype(jnp.int32), g)

    fused_impl = "pallas" if on_tpu else "pallas_interpret"

    def fused_tail(logits, keys, temperature, top_k, top_p):
        return fused_sample(logits, keys, temperature, top_k, top_p,
                            implementation=fused_impl)

    def interp_tail(logits, keys, temperature, top_k, top_p):
        # the interpret-mode cross-check row is ALWAYS interpret —
        # on TPU fused_tail compiles the Mosaic kernel, which would
        # otherwise masquerade as the interpret overstatement
        return fused_sample(logits, keys, temperature, top_k, top_p,
                            implementation="pallas_interpret")

    def ref_tail(logits, keys, temperature, top_k, top_p):
        return fused_sample_reference(logits, keys, temperature,
                                      top_k, top_p, V)

    def step_with(tail):
        def step(variables, cache, tok, keys, temperature, top_k,
                 top_p):
            logits, cache = apply_decode(model, variables, cache,
                                         tok[:, None])
            nxt = tail(logits[:, -1], keys, temperature, top_k, top_p)
            return cache, nxt
        return step

    def bytes_of(fn, *args, **kw):
        ca = jax.jit(fn).lower(*args, **kw).compile().cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        return float((ca or {}).get("bytes accessed", 0.0))

    logits0 = jnp.asarray(rng.normal(size=(slots, V)) * 2, jnp.float32)
    t_un = bytes_of(legacy_tail, logits0, keys, **mixed)
    t_ref = bytes_of(ref_tail, logits0, keys, **mixed)
    t_model = float(sampling_cost_bytes(slots, V, jnp.float32))
    t_interp = bytes_of(interp_tail, logits0, keys, **mixed)
    s_un = bytes_of(step_with(legacy_tail), variables, cache, tok,
                    keys, **mixed)
    if on_tpu:
        s_fused = bytes_of(step_with(fused_tail), variables, cache,
                           tok, keys, **mixed)
        src = "measured"
    else:
        # the TPU rollup, composed from measured parts + the kernel's
        # declared CostEstimate (see docstring)
        s_fused = (s_un - t_un) + t_model
        src = "declared-model"
    drop = 1.0 - s_fused / s_un

    # spec-step row: W positions per row — the old executable looped W
    # sorted tails, the fused op takes the width axis in ONE call
    logits_w = jnp.asarray(rng.normal(size=(slots, W, V)),
                           jnp.float32)
    keys_w = jnp.stack([keys] * W, axis=1)

    def legacy_spec_tail(logits, keys, temperature, top_k, top_p):
        return jnp.stack(
            [legacy_tail(logits[:, j], keys[:, j], temperature,
                         top_k, top_p) for j in range(W)], axis=1)

    ts_un = bytes_of(legacy_spec_tail, logits_w, keys_w, **mixed)
    ts_model = float(sampling_cost_bytes(slots * W, V, jnp.float32))

    # wall: steady decode steps, each arm (fused arm on CPU == the
    # reference tail the engine actually dispatches to off-chip)
    ovh = bench._call_overhead()

    def wall(tail, sampling):
        fn = jax.jit(step_with(tail))
        c = jax.tree.map(jnp.copy, cache)
        c, out = fn(variables, c, tok, keys, **sampling)   # compile
        bench._sync(out)

        def window():
            nonlocal c
            t0 = time.perf_counter()
            for _ in range(8):
                c, out = fn(variables, c, tok, keys, **sampling)
            bench._sync(out)
            return (time.perf_counter() - t0 - ovh) / 8

        t, _w = bench._time_windows(window, k_windows)
        return t

    wall_tail = fused_tail if on_tpu else ref_tail
    t_leg_mix = wall(legacy_tail, mixed)
    t_new_mix = wall(wall_tail, mixed)
    t_leg_gre = wall(legacy_tail, greedy)
    t_new_gre = wall(wall_tail, greedy)

    out = {
        "metric": "decode_epilogue_bytes_drop",
        "value": round(drop, 4),
        "unit": f"fraction of decode-executable cost-analysis bytes "
                f"(slots={slots}, V={V})",
        "fused_bytes_source": src,
        "epilogue_bytes": {
            "unfused_sort_tail": t_un,
            "reference_cond_tail": t_ref,
            "fused_kernel_declared": t_model,
            "fused_kernel_interpret_measured": t_interp,
            "spec_width_unfused": ts_un,
            "spec_width_fused_declared": ts_model,
            "spec_width": W,
        },
        "step_bytes": {"unfused": s_un, "fused": s_fused},
        "wall_ms_per_step": {
            "legacy_mixed": round(t_leg_mix * 1e3, 3),
            "fused_arm_mixed": round(t_new_mix * 1e3, 3),
            "legacy_all_greedy": round(t_leg_gre * 1e3, 3),
            "fused_arm_all_greedy": round(t_new_gre * 1e3, 3),
        },
        "greedy_shortcircuit_speedup": round(t_leg_gre / t_new_gre,
                                             3),
        "tokens_per_sec_mixed": round(slots / t_new_mix, 1),
        "wall_note": ("CPU wall is noisy and the Mosaic kernel is "
                      "not in play off-chip; the short-circuit row "
                      "is the one wall claim the CPU smoke makes"),
    }
    # the acceptance bar: >= 10% cost-analysis bytes off the decode
    # executable from the fused epilogue
    assert drop >= 0.10, (
        f"fused epilogue bytes drop {drop:.3f} < 0.10 on the decode "
        f"executable (unfused {s_un}, fused {s_fused}, {src})")
    # and the tail itself must shrink however it is measured: even the
    # interpret-mode OVERSTATEMENT of the kernel must beat the sort
    # tail it replaces
    assert t_interp < t_un, (t_interp, t_un)
    _emit(out)


# ----------------------------------------------------------------- ViT-Huge

def bench_vit_huge_lamb():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.models import ViTConfig, ViTModel
    from apex_tpu.optim import fused_lamb

    b = int(os.environ.get("BENCH_BATCH", "32"))
    cfg = ViTConfig.vit_huge(dtype=jnp.bfloat16, remat=True,
                             scan_layers=False)
    model = ViTModel(cfg)

    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.normal(size=(b, 224, 224, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, cfg.num_classes, size=(b,)))
    params = model.init(jax.random.PRNGKey(0), images[:2])
    state = amp.initialize(
        model.apply, params, fused_lamb(1e-3),
        opt_level="O2", half_dtype=jnp.bfloat16)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, x, y):
        def loss_fn(p):
            cp = state.policy.cast_to_compute(p)
            logits = state.apply_fn(cp, x)
            onehot = jax.nn.one_hot(y, cfg.num_classes)
            loss = -jnp.mean(jnp.sum(
                jax.nn.log_softmax(logits.astype(jnp.float32)) * onehot,
                axis=-1))
            return state.scale_loss(loss), loss

        grads, loss = jax.grad(loss_fn, has_aux=True)(state.params)
        new_state, finite = state.apply_gradients(grads=grads)
        return new_state, loss, finite

    out = _measure(state, step, (images, labels), b, {"batch": b})
    out["metric"] = "vit_huge_O2_fusedlamb_samples_per_sec_per_chip"
    _emit(out)


# ----------------------------------------------------------------- groupnorm

def bench_group_norm():
    """GroupNorm+SiLU scoreboard (round-2 verdict weak #6): fwd+bwd
    GN(32 groups)+SiLU over a diffusion-typical activation, achieved
    HBM GB/s vs the chip's peak, measured with the DEFAULT
    implementation — the round-3 Pallas kernels on TPU (the round-2
    XLA composition measured 70 GB/s ≈ 9% of peak here, which refuted
    the original no-kernel rationale; the kernel A/B lives in
    BASELINE.md).  Set APEX_TPU_OPS_IMPL=xla to re-measure the
    composition."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops.group_norm import group_norm

    b, hw, c, groups = 8, 64, 512, 32
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(b, hw, hw, c)),
        jnp.bfloat16)
    w = jnp.ones((c,), jnp.float32)
    bias = jnp.zeros((c,), jnp.float32)

    # ≥1000 in-jit iterations: a FIXED per-call dispatch+fetch cost
    # poisoned every round-3 GN number at the old 50 steps (÷50 → +2
    # ms/step on a ~0.3 ms op — the scoreboard's 2.5 ms/step was ~80%
    # overhead); the measured trivial-call overhead is also subtracted
    # per window now
    n_steps = int(os.environ.get("BENCH_STEPS", "0")) or 1000

    # the timed body is EXACTLY the counted passes (round-3 verdict
    # weak #1 — the old harness added ~4 uncounted passes): fwd (read
    # x, write y) + vjp (read dy, read x, write dx), with y and dx
    # both live in the carry and dy independent of x so XLA can
    # neither dead-code the forward nor alias dy into the x read
    dy0 = jnp.asarray(
        np.random.default_rng(1).normal(size=(b, hw, hw, c)),
        jnp.bfloat16)

    @jax.jit
    def many(x, dy, w, bias):
        def body(carry, _):
            xx, dd = carry
            y, pull = jax.vjp(
                lambda q: group_norm(q, groups, w, bias, act="silu"),
                xx)
            (dx,) = pull(dd)
            return (dx.astype(xx.dtype), y.astype(dd.dtype)), None

        carry, _ = jax.lax.scan(body, (x, dy), None, length=n_steps)
        return carry

    out = many(x, dy0, w, bias)
    bench._sync(out)
    assert bool(jnp.isfinite(out[0][0, 0, 0]).all()), "diverged"
    ovh = bench._call_overhead()

    def window():
        t0 = time.perf_counter()
        out = many(x, dy0, w, bias)
        bench._sync(out)
        return (time.perf_counter() - t0 - ovh) / n_steps

    dt, dts = bench._time_windows(
        window, max(1, int(os.environ.get("BENCH_WINDOWS", "3"))))
    # HBM traffic of what is timed: read x, write y (fwd); read dy,
    # read x, write dx (bwd) — 5 × numel × 2 bytes in bf16 (stat
    # reductions are negligible).  NB the KERNEL's own traffic is
    # higher (two-phase sweeps re-read x/dy once each: 8 passes); this
    # metric stays the end-to-end lower-bound form for comparability.
    numel = b * hw * hw * c
    min_bytes = 5 * numel * 2
    gbs = min_bytes / dt / 1e9
    _emit({
        "metric": "group_norm_silu_fwd_bwd_achieved_gbs",
        "value": round(gbs, 1),
        "unit": "GB/s (lower-bound traffic / time)",
        "shape": [b, hw, hw, c], "groups": groups,
        "step_us": round(dt * 1e6, 1),
        "window_us": [round(d * 1e6, 1) for d in dts],
        "frac_of_peak_hbm": bench.frac_of_hbm_peak(gbs),
        "impl_note": (
            "default impl = XLA composition (measured 2.3x faster "
            "than the Pallas kernels once the fixed call overhead is "
            "subtracted — BASELINE.md round-4 GN section); "
            "APEX_TPU_OPS_IMPL=pallas re-measures the kernels"),
    })


# ------------------------------------------------------------- resilience

def bench_resilience_overhead():
    """Steady-state cost of the resilience wrapper (ISSUE 4): the SAME
    jitted train step driven by the bare python loop vs
    ``ResilientLoop`` with async rolling hash-manifest checkpoints
    every ``BENCH_RESIL_CKPT_EVERY`` steps.  Target: <2% step-time
    overhead at checkpoint-every-100 — per step the wrapper adds two
    no-plan fault-injection checks, a ``time.monotonic`` pair and a
    preemption-flag read; the checkpoint's device_get+hash+write rides
    a background thread and amortizes across the interval.  The step
    count is sized so the run ends ON a checkpoint boundary (the final
    blocking save is skipped as already-saved, keeping the measurement
    steady-state).

    Env: BENCH_RESIL_STEPS (300), BENCH_RESIL_CKPT_EVERY (100)."""
    import shutil
    import tempfile
    import time

    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.models import gpt_loss_fn
    from apex_tpu.optim import fused_adam
    from apex_tpu.resilience import ResilientCheckpointer, ResilientLoop
    from apex_tpu.transformer.testing import standalone_gpt

    steps = int(os.environ.get("BENCH_RESIL_STEPS", "300"))
    every = int(os.environ.get("BENCH_RESIL_CKPT_EVERY", "100"))
    steps = max(every, steps - steps % every)   # end ON a ckpt boundary
    b, s = 8, 32
    model, init_params = standalone_gpt(seed=0, max_seq_len=s)
    vocab = model.cfg.vocab_size
    ids = jax.random.randint(jax.random.PRNGKey(7), (4, b, s + 1), 0,
                             vocab, jnp.int32)

    def make_state():
        # fresh buffers per run: the donated step would otherwise
        # delete the shared init_params out from under the next run
        fresh = jax.tree.map(jnp.array, init_params)
        return amp.initialize(
            model.apply, {"params": fresh}, fused_adam(3e-4),
            opt_level="O2", half_dtype=jnp.bfloat16)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, chunk):
        inputs, labels = chunk[:, :-1], chunk[:, 1:]

        def loss_fn(p):
            cp = state.policy.cast_to_compute(p)
            logits = state.apply_fn(cp, inputs)
            loss = gpt_loss_fn(logits.astype(jnp.float32), labels)
            return state.scale_loss(loss), loss

        grads, loss = jax.grad(loss_fn, has_aux=True)(state.params)
        new_state, _finite = state.apply_gradients(grads=grads)
        return new_state, loss

    def data_fn(i):
        return ids[i % 4]

    # shared warmup: one compile serves both loops (same jit object)
    warm, _ = step(make_state(), ids[0])
    jax.block_until_ready(warm.params)
    del warm

    def bare():
        state = make_state()
        t0 = time.perf_counter()
        for i in range(steps):
            state, loss = step(state, data_fn(i))
        jax.block_until_ready(loss)
        return (time.perf_counter() - t0) / steps

    def loop_step(st, batch):
        st, loss = step(st, batch)
        return st, {"loss": loss}

    def resilient():
        ckpt_dir = tempfile.mkdtemp(prefix="apex_tpu_resil_bench_")
        loop = ResilientLoop(
            loop_step,
            checkpointer=ResilientCheckpointer(ckpt_dir, keep=2),
            checkpoint_every=every, async_checkpoints=True)
        try:
            t0 = time.perf_counter()
            carry, report = loop.run(make_state(), data_fn, steps)
            jax.block_until_ready(carry.params)
            dt = (time.perf_counter() - t0) / steps
        finally:
            shutil.rmtree(ckpt_dir, ignore_errors=True)
        return dt, report

    k_windows = max(1, int(os.environ.get("BENCH_WINDOWS", "3")))
    bare_dt = min(bare() for _ in range(k_windows))
    pairs = [resilient() for _ in range(k_windows)]
    resil_dt = min(dt for dt, _ in pairs)
    report = pairs[0][1]
    overhead = resil_dt / bare_dt - 1.0
    n_ckpts = max(1, report.checkpoints_saved)
    _emit({
        "metric": f"resilience_overhead_ckpt{every}_pct",
        "value": round(100.0 * overhead, 2),
        "unit": "percent step-time overhead (ResilientLoop + async "
                "rolling checkpoints vs bare loop)",
        "bare_step_ms": round(bare_dt * 1e3, 3),
        "resilient_step_ms": round(resil_dt * 1e3, 3),
        "ms_per_checkpoint": round(
            (resil_dt - bare_dt) * steps * 1e3 / n_ckpts, 1),
        "steps": steps,
        "checkpoint_every": every,
        "checkpoints_written": report.checkpoints_saved,
        "target_pct": 2.0,
        "meets_target": bool(overhead < 0.02),
        "note": ("same jitted step both rows, shared compile, best of "
                 f"{k_windows} runs each; run ends on a checkpoint "
                 "boundary so the final blocking save is amortized "
                 "out (steady state, not save latency).  On the CPU "
                 "backend this is an UPPER bound: the async snapshot "
                 "copy and the background hash/serialize thread share "
                 "the step's own cores, whereas on TPU the step runs "
                 "on-device and only the (μs-scale) on-device copy "
                 "lands in the step's critical path — "
                 "ms_per_checkpoint / (checkpoint_every × step_ms) "
                 "models other intervals"),
    })


def bench_fleet_serving():
    """Multi-replica serving fleet scoreboard (ISSUE 6): tokens/s and
    TTFT p50/p99 *per chip* at a fixed SLO, 1 vs 3 replicas, plus a
    kill-at-midpoint resilience row — reporting protocol per the
    Gemma-on-TPU serving paper (PAPERS.md, arxiv 2605.25645):
    throughput numbers are only comparable at a fixed latency SLO, so
    every row carries the SLO and whether it held.  One replica = one
    chip's worth of serving in this model, so per-chip tokens/s should
    be ~flat 1 → 3 replicas (the router adds routing, not compute),
    and the kill row quantifies what a replica death costs: migrated
    tenants resume on survivors with zero lost requests while
    fleet-wide throughput degrades to the surviving capacity.

    Env: BENCH_FLEET_REPLICAS (3), BENCH_FLEET_REQUESTS (18),
    BENCH_FLEET_PROMPT (8), BENCH_FLEET_TOKENS (16),
    BENCH_FLEET_SLOTS (2), BENCH_FLEET_TTFT_SLO_MS (5000).
    CPU smoke uses the tiny-GPT proxy; the protocol (not the absolute
    numbers) is the artifact."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serving import FleetRouter, InferenceServer

    replicas = int(os.environ.get("BENCH_FLEET_REPLICAS", "3"))
    requests = int(os.environ.get("BENCH_FLEET_REQUESTS", "18"))
    P = int(os.environ.get("BENCH_FLEET_PROMPT", "8"))
    N = int(os.environ.get("BENCH_FLEET_TOKENS", "16"))
    slots = int(os.environ.get("BENCH_FLEET_SLOTS", "2"))
    slo_ms = float(os.environ.get("BENCH_FLEET_TTFT_SLO_MS", "5000"))

    cfg = GPTConfig.tiny(position_embedding="learned",
                         scan_layers=True)
    model = GPTModel(cfg)
    params = {"params": model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 4), jnp.int32))["params"]}
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=(P,)).astype(
        np.int32) for _ in range(requests)]

    def factory():
        return InferenceServer(
            model, params, max_slots=slots,
            block_size=8, prefill_chunk=4,
            pool_tokens=slots * cfg.max_seq_len)

    def run_fleet(n_replicas, *, kill_mid=False):
        router = FleetRouter(factory, replicas=n_replicas,
                             probe_interval=0.05)
        with router:
            t0 = time.perf_counter()
            handles = [router.submit(p, max_new_tokens=N, seed=i)
                       for i, p in enumerate(prompts)]
            if kill_mid:
                # midpoint: half the total token work done, then a
                # SIGKILL-equivalent death of the busiest replica
                target = requests * N // 2
                while router.stats()["tokens_total"] < target:
                    time.sleep(0.005)
                live = [r for r in router._replicas
                        if r is not None and not r.dead]
                victim = max(live,
                             key=lambda r: len(r.active)).index
                router.kill_replica(victim)
            tokens = sum(len(h.result(timeout=600)) for h in handles)
            wall = time.perf_counter() - t0
            lat = router.latency_summary()
            stats = router.stats()
        ttft_p99_ms = lat.get("ttft_p99_s", 0.0) * 1e3
        return {
            "replicas": n_replicas,
            "tokens_per_sec": round(tokens / wall, 1),
            "tokens_per_sec_per_chip": round(
                tokens / wall / n_replicas, 1),
            "ttft_p50_ms": round(lat.get("ttft_p50_s", 0.0) * 1e3, 1),
            "ttft_p99_ms": round(ttft_p99_ms, 1),
            "ttft_slo_ms": slo_ms,
            "slo_met": bool(ttft_p99_ms <= slo_ms),
            "completed": stats["completed"],
            "failed": stats["failed"],
            "migrated": stats["migrated"],
            "wall_s": round(wall, 3),
        }

    rows = {
        "x1": run_fleet(1),
        f"x{replicas}": run_fleet(replicas),
        f"x{replicas}_kill_midpoint": run_fleet(replicas,
                                                kill_mid=True),
    }
    kill_row = rows[f"x{replicas}_kill_midpoint"]
    _emit({
        "metric": f"fleet_serving_x{replicas}_tokens_per_sec_per_chip",
        "value": rows[f"x{replicas}"]["tokens_per_sec_per_chip"],
        "unit": "tokens/sec/chip at fixed TTFT SLO",
        "requests": requests, "prompt": P, "budget": N,
        "slots_per_replica": slots,
        "rows": rows,
        "zero_loss_under_kill": bool(
            kill_row["completed"] + kill_row["failed"] == requests
            and kill_row["failed"] == 0),
        "note": ("Gemma-paper protocol: tokens/s and TTFT p50/p99 per "
                 "chip reported AT the SLO; the kill row shows "
                 "migrated in-flight tenants resuming on survivors "
                 "with zero lost requests (CPU smoke on the tiny-GPT "
                 "proxy — protocol, not absolute throughput, is the "
                 "artifact)"),
    })


def bench_tp_serving():
    """Tensor-parallel paged serving A/B (ISSUE 13): at EQUAL chip
    count C, (a) C replicas × 1 chip behind a FleetRouter vs (b) ONE
    replica × C chips (``InferenceServer(tp=C)`` — pool sharded on
    kv_heads, matmuls over the GSPMD TP layers), reporting tokens/s
    and TTFT p50/p99 *per chip* per the Gemma-paper protocol, with
    the per-step ICI collective column of ``_serving_traffic_model``
    populated for the TP row.  The M×1 fleet wins pure throughput
    (zero ICI, C independent steps in flight) — the TP row's value is
    CAPACITY: it serves a model C× too big for one chip, and the
    A/B + traffic model quantify exactly what that costs per chip.

    Env: BENCH_TP_CHIPS (2), BENCH_TP_REQUESTS (10),
    BENCH_TP_PROMPT (8), BENCH_TP_TOKENS (16), BENCH_TP_SLOTS (2).
    CPU smoke uses the tiny-GPT proxy over the virtual-device mesh;
    the protocol (not the absolute numbers) is the artifact."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serving import FleetRouter, InferenceServer

    chips = int(os.environ.get("BENCH_TP_CHIPS", "2"))
    if len(jax.devices()) < chips:
        raise RuntimeError(
            f"tp_serving needs {chips} devices, found "
            f"{len(jax.devices())} — on CPU run with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            f"(the _run_all driver sets it)")
    requests = int(os.environ.get("BENCH_TP_REQUESTS", "10"))
    P = int(os.environ.get("BENCH_TP_PROMPT", "8"))
    N = int(os.environ.get("BENCH_TP_TOKENS", "16"))
    slots = int(os.environ.get("BENCH_TP_SLOTS", "2"))

    cfg = GPTConfig.tiny(position_embedding="learned",
                         scan_layers=True)
    model = GPTModel(cfg)
    params = {"params": model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 4), jnp.int32))["params"]}
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=(P,)).astype(
        np.int32) for _ in range(requests)]

    def summarize(tokens, wall, lat, n_chips, extra):
        ttft_p99 = lat.get("ttft_p99_s", 0.0) * 1e3
        return {
            "chips": n_chips,
            "tokens_per_sec": round(tokens / wall, 1),
            "tokens_per_sec_per_chip": round(
                tokens / wall / n_chips, 1),
            "ttft_p50_ms": round(lat.get("ttft_p50_s", 0.0) * 1e3, 1),
            "ttft_p99_ms": round(ttft_p99, 1),
            "wall_s": round(wall, 3),
            **extra,
        }

    def run_fleet():
        # C replicas × 1 chip: the pre-ISSUE-13 scaling axis.  Each
        # replica's weights are COMMITTED to its own device so the
        # jitted steps actually run there (uncommitted params would
        # pile every replica onto device 0 and the per-chip division
        # below would be fiction)
        import itertools

        devices = jax.devices()
        idx = itertools.count()

        def factory():
            dev = devices[next(idx) % len(devices)]
            return InferenceServer(
                model, jax.device_put(params, dev), max_slots=slots,
                block_size=8, prefill_chunk=4)

        router = FleetRouter(factory, replicas=chips,
                             probe_interval=0.05)
        with router:
            t0 = time.perf_counter()
            handles = [router.submit(p, max_new_tokens=N, seed=i)
                       for i, p in enumerate(prompts)]
            tokens = sum(len(h.result(timeout=600)) for h in handles)
            wall = time.perf_counter() - t0
            lat = router.latency_summary()
            merged = router.health()
        return summarize(tokens, wall, lat, chips, {
            "layout": f"{chips}x1 (replicas x chips)",
            "chips_total": merged["chips_total"],
        })

    def run_tp():
        # 1 replica × C chips: one engine spans the mesh
        server = InferenceServer(
            model, params, max_slots=slots,
            block_size=8, prefill_chunk=4, tp=chips)
        with server:
            t0 = time.perf_counter()
            handles = [server.submit(p, max_new_tokens=N, seed=i)
                       for i, p in enumerate(prompts)]
            tokens = sum(len(h.result(timeout=600)) for h in handles)
            wall = time.perf_counter() - t0
            lat = server.latency_summary()
            health = server.health()
        return summarize(tokens, wall, lat, chips, {
            "layout": f"1x{chips} (replicas x chips)",
            "chips_per_replica": health["chips_per_replica"],
            "mesh_shape": str(health.get("mesh_shape")),
        })

    tm = _serving_traffic_model(
        num_layers=cfg.num_layers, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, max_seq_len=cfg.max_seq_len,
        live_tokens=P + N, slots=slots, block_size=8,
        dtype_bytes=jnp.dtype(cfg.dtype).itemsize,
        tp=chips, hidden_size=cfg.hidden_size)
    rows = {
        f"{chips}x1_fleet": run_fleet(),
        f"1x{chips}_tp": run_tp(),
    }
    _emit({
        "metric": f"tp_serving_1x{chips}_tokens_per_sec_per_chip",
        "value": rows[f"1x{chips}_tp"]["tokens_per_sec_per_chip"],
        "unit": "tokens/sec/chip at equal chip count",
        "requests": requests, "prompt": P, "budget": N,
        "slots_per_replica": slots,
        "rows": rows,
        "traffic_model": tm,
        "note": ("ISSUE-13 A/B at equal chip count: the M×1 fleet is "
                 "the throughput ceiling (zero ICI), the 1×M TP row "
                 "buys model CAPACITY (one replica spans the mesh; "
                 "kv-head-sharded pool reads "
                 f"{tm['paged_kv_read_bytes_per_step_per_chip']} "
                 "B/step/chip vs "
                 f"{tm['paged_kv_read_bytes_per_step']} single-chip) "
                 "at the modeled ICI cost of "
                 f"{tm['ici_bytes_per_step_per_chip']} B/step/chip "
                 "(CPU smoke on the tiny-GPT proxy — protocol, not "
                 "absolute throughput, is the artifact)"),
    })


# ----------------------------------------------------------------- driver

# ----------------------------------------------------- pipeline 1F1B


def bench_pipeline_train():
    """Measured ISSUE-20 row: dp baseline vs dp × pipe at EQUAL chips.

    Two arms over the same global batch and the same stacked
    residual-MLP layer stack (the pipeline test suite's workload):

    - ``dp`` — pure data parallelism over all chips, replicated
      params/optimizer: the layout the planner falls back FROM when
      per-chip residency busts the HBM budget.
    - ``dp_pipe`` — ``parallel.pipeline`` end-to-end: ``stage_split``
      over ``pipe=BENCH_PIPE_PP``, stage-local ZeRO-2 over the
      remaining ``data`` axis, 1F1B via ``wrap_pipeline_step``.

    Emits samples/sec/chip for both arms, the XLA memory-analysis
    per-chip (= per-stage × dp-shard) HBM plus exact placed-array
    state bytes, and measured-vs-modeled bubble: the pipe arm runs at
    two microbatch counts (m, 2m) so the per-microbatch time
    ``τ = (t(2m) − t(m)) / m`` factors out the fixed overhead;
    ``measured_bubble = (t(m) − m·τ) / (m·τ)`` is pinned against the
    schedule's ``(p−1)/m`` and the ``plan.costs.pipeline_costs``
    block the planner scores with.  On the CPU mesh τ prices compute,
    not the overlapped ppermute wire, so the comparison is
    report-only unless ``BENCH_PIPE_BUBBLE_BAND`` is set (> 0:
    ``|measured − modeled|`` must land inside the band).

    Env: BENCH_PIPE_PP (2), BENCH_PIPE_LAYERS (8), BENCH_PIPE_HIDDEN
    (64), BENCH_PIPE_MB (8), BENCH_PIPE_MICROBATCHES (8),
    BENCH_PIPE_STEPS (8), BENCH_PIPE_BUBBLE_BAND (0 = report-only).
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.optim import fused_adam
    from apex_tpu.parallel import ZeroConfig
    from apex_tpu.parallel import pipeline as pl
    from apex_tpu.plan.costs import pipeline_costs

    n_dev = jax.device_count()
    pp = int(os.environ.get("BENCH_PIPE_PP", "2"))
    if n_dev < 2 or pp < 2 or n_dev % pp:
        _emit({"metric": "pipeline_train", "value": None,
               "skipped": (f"needs device_count % pp == 0 with "
                           f"pp >= 2, have {n_dev} devices, pp={pp}")})
        return
    dp = n_dev // pp
    layers = int(os.environ.get("BENCH_PIPE_LAYERS", "8"))
    layers = max(pp, layers - layers % pp)      # stage-balance gate
    hid = int(os.environ.get("BENCH_PIPE_HIDDEN", "64"))
    mb = int(os.environ.get("BENCH_PIPE_MB", "8"))
    m = int(os.environ.get("BENCH_PIPE_MICROBATCHES", "8"))
    m = max(pp, m - m % pp)                     # m >= p, DP-divisible
    steps = int(os.environ.get("BENCH_PIPE_STEPS", "8"))
    lr = 1e-2

    r = np.random.default_rng(0)
    params = {"stages": (
        jnp.asarray(r.normal(size=(layers, hid, hid)) * 0.3,
                    jnp.float32),
        jnp.asarray(r.normal(size=(layers, hid)) * 0.1, jnp.float32),
        jnp.asarray(r.normal(size=(layers, hid, hid)) * 0.3,
                    jnp.float32),
    )}
    n_params = sum(x.size for x in jax.tree.leaves(params))

    def layer(x, args):
        w1, b1, w2 = args
        h = jnp.tanh(x @ w1 + b1)
        return x + h @ w2, None

    def stage_fn(stage_params, x):
        x, _ = jax.lax.scan(layer, x, stage_params)
        return x

    def batch_of(mm):
        rb = np.random.default_rng(1)
        x = jnp.asarray(rb.normal(size=(dp * mm, mb, hid)),
                        jnp.float32)
        y = jnp.asarray(rb.normal(size=(dp * mm, mb, hid)),
                        jnp.float32)
        return x, y

    def placed_bytes_per_chip(tree):
        total = 0
        for leaf in jax.tree.leaves(tree):
            try:
                shp = leaf.sharding.shard_shape(leaf.shape)
            except Exception:
                shp = leaf.shape
            total += int(np.prod(shp, dtype=np.int64)) \
                * leaf.dtype.itemsize
        return int(total)

    def timed_loop(step, state, batch):
        state, loss = step(state, *batch)       # compile + warm
        bench._sync(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss = step(state, *batch)
        bench._sync(loss)
        return (time.perf_counter() - t0) / steps, float(loss)

    samples = dp * m * mb                       # global samples/step

    def run_dp():
        mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))
        state = amp.initialize(None, jax.tree.map(jnp.copy, params),
                               fused_adam(lr), opt_level="O0")

        def dp_step(state, x, y):
            def loss_fn(p):
                out, _ = jax.lax.scan(layer, x, p["stages"])
                return jnp.mean((out - y) ** 2)

            loss, grads = jax.value_and_grad(loss_fn)(state.params)
            grads = jax.tree.map(
                lambda g: jax.lax.pmean(g, "data"), grads)
            new_state, _ = state.apply_gradients(grads=grads)
            return new_state, jax.lax.pmean(loss, "data")

        step = jax.jit(jax.shard_map(
            dp_step, mesh=mesh,
            in_specs=(P(), P("data"), P("data")),
            out_specs=(P(), P()), check_vma=False),
            donate_argnums=(0,))
        x, y = batch_of(m)                      # same global samples
        flat = (x.reshape(-1, hid), y.reshape(-1, hid))
        compiled = bench._aot_compile(step, state, *flat)
        state_bytes = placed_bytes_per_chip(
            (state.params, state.opt_state))
        dt, loss = timed_loop(step, state, flat)
        row = {"layout": f"dp={n_dev}",
               "samples_per_sec_per_chip": round(
                   samples / dt / n_dev, 2),
               "step_ms": round(dt * 1e3, 2),
               "final_loss": round(loss, 5),
               "state_bytes_per_chip": state_bytes}
        row.update(bench._memory_fields(compiled))
        return row

    def run_pipe(mm, want_mem):
        mesh = Mesh(np.array(jax.devices()[:n_dev]).reshape(dp, pp),
                    ("data", "pipe"))
        staged = {"stages": pl.stage_split(params["stages"], pp)}
        state = amp.initialize(
            None, jax.tree.map(jnp.copy, staged), fused_adam(lr),
            opt_level="O0",
            zero=ZeroConfig(axis="data", axis_size=dp, stage=2))
        state = pl.stage_local_zero(state, num_stages=pp)
        state = jax.device_put(
            state, pl.pipeline_state_shardings(state, mesh=mesh))

        def body(state, mbs, labels):
            def loss_fn(out, i):
                yl = jax.lax.dynamic_index_in_dim(labels, i, 0,
                                                  keepdims=False)
                return jnp.mean((out - yl) ** 2)

            loss, grads = pl.run_1f1b(stage_fn, loss_fn,
                                      state.params["stages"], mbs)
            grads = pl.sync_grad_overflow({"stages": grads})
            new_state, _ = state.apply_gradients(grads=grads)
            return new_state, jax.lax.pmean(loss, "data")

        step = pl.wrap_pipeline_step(
            body, state=state, mesh=mesh,
            batch_specs=(P("data"), P("data")))
        batch = batch_of(mm)
        row = {}
        if want_mem:
            compiled = bench._aot_compile(step, state, *batch)
            row.update(bench._memory_fields(compiled))
            row["state_bytes_per_chip"] = placed_bytes_per_chip(
                (state.params, state.opt_state))
        dt, loss = timed_loop(step, state, batch)
        row.update({"layout": f"dp={dp} x pipe={pp} zero2",
                    "microbatches": mm,
                    "samples_per_sec_per_chip": round(
                        dp * mm * mb / dt / n_dev, 2),
                    "step_ms": round(dt * 1e3, 2),
                    "final_loss": round(loss, 5)})
        return row

    dp_row = run_dp()
    pipe_row = run_pipe(m, want_mem=True)
    pipe_2m = run_pipe(2 * m, want_mem=False)

    # two-m extraction: t(m) = m·τ + overhead, so τ falls out of the
    # difference and the bubble is the overhead in units of work time
    t1 = pipe_row["step_ms"]
    t2 = pipe_2m["step_ms"]
    tau = (t2 - t1) / m
    measured_bubble = (round((t1 - m * tau) / (m * tau), 4)
                       if tau > 0 else None)
    modeled = pipeline_costs(pp, m, microbatch_tokens=mb,
                             hidden_size=hid, dtype_bytes=4)
    band = float(os.environ.get("BENCH_PIPE_BUBBLE_BAND", "0"))
    within = (abs(measured_bubble - modeled["bubble_fraction"]) <= band
              if band > 0 and measured_bubble is not None else None)

    _emit({
        "metric": "pipeline_train_samples_per_sec_per_chip",
        "value": pipe_row["samples_per_sec_per_chip"],
        "unit": "samples/sec/chip (CPU-mesh proxy)",
        "devices": n_dev, "dp": dp, "pipe": pp,
        "num_layers": layers, "hidden": hid,
        "num_params": int(n_params),
        "global_samples_per_step": samples,
        "rows": {"dp": dp_row, "dp_pipe": pipe_row,
                 "dp_pipe_2m": pipe_2m},
        "measured_bubble_fraction": measured_bubble,
        "modeled": modeled,
        "bubble_band": band or None,
        "bubble_within_band": within,
        "sps_pipe_vs_dp": round(
            pipe_row["samples_per_sec_per_chip"]
            / max(dp_row["samples_per_sec_per_chip"], 1e-9), 3),
        "state_bytes_pipe_vs_dp": round(
            pipe_row["state_bytes_per_chip"]
            / max(dp_row["state_bytes_per_chip"], 1), 3),
        "note": ("ISSUE-20 row: equal chips, equal global batch; the "
                 "pipe arm's per-chip state is the stage-local "
                 "ZeRO-2 residency (exact placed-array accounting) "
                 "and its hbm fields are XLA memory-analysis bytes "
                 "of the compiled 1F1B step; trajectory agreement is "
                 "gated by test_loss_trajectory's dp-vs-dp×pipe band "
                 "leg; on CPU the wall ratio prices compute, not the "
                 "overlapped ppermute wire — on chip the bubble "
                 "comparison is the contract (set "
                 "BENCH_PIPE_BUBBLE_BAND to gate it)"),
    })


LEGS = {
    "resnet50_o1": bench_resnet50_o1,
    "resnet50_syncbn": bench_resnet50_syncbn,
    "bert_o1": bench_bert_o1,
    "bert_o1_ddp": bench_bert_o1_ddp,
    "bert_o1_zero": bench_bert_o1_zero,
    "gpt2_1p3b": bench_gpt2_1p3b,
    "gpt2_tp8_full_step": bench_gpt2_tp8_full_step,
    "gpt2_3d_full_step": bench_gpt2_3d_full_step,
    "mistral7b_tp8_full_step": bench_mistral7b_tp8_full_step,
    "moe_mixtral": bench_moe_mixtral,
    "llama_1b": bench_llama_1b,
    "decode": bench_decode,
    "decode_epilogue": bench_decode_epilogue,
    "prefix_spec_serving": bench_prefix_spec_serving,
    "quantized_kv_serving": bench_quantized_kv_serving,
    "resilience_overhead": bench_resilience_overhead,
    "fleet_serving": bench_fleet_serving,
    "tp_serving": bench_tp_serving,
    "pipeline_train": bench_pipeline_train,
    "vit_huge_lamb": bench_vit_huge_lamb,
    "long_context": bench_long_context,
    "group_norm": bench_group_norm,
}

# legs that must run on the virtual CPU mesh, not the real chip
_CPU_LEGS = {"gpt2_tp8_full_step", "gpt2_3d_full_step",
             "mistral7b_tp8_full_step", "pipeline_train"}


# per-leg timeouts: orchestrator legs must outlast the sum of their
# own children's budgets (a parent timeout would discard every
# already-measured child row)
_LEG_TIMEOUT = {"decode": 10000, "llama_1b": 8000,
                "long_context": 6600,
                # A/B orchestrators: 4 (o1) / 2 (syncbn) child rows
                "resnet50_o1": 11000, "resnet50_syncbn": 5600}


def _run_all():
    results = {}
    for name in LEGS:
        env = {}
        if name in _CPU_LEGS:
            env = {"JAX_PLATFORMS": "cpu",
                   "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                                 + " --xla_force_host_platform_device"
                                   "_count=8").strip()}
        elif name == "tp_serving":
            # needs a multi-chip mesh: the host-platform device-count
            # flag makes the CPU smoke multi-device and is inert on a
            # real TPU child (which brings its own chips)
            env = {"XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                                 + " --xla_force_host_platform_device"
                                   "_count=8").strip()}
        print(f"== {name}", file=sys.stderr)
        results[name] = _run_child(
            name, env, timeout=_LEG_TIMEOUT.get(name, 5400))
        if "error" in results[name]:
            print(f"  FAILED: {results[name]['error'][-300:]}",
                  file=sys.stderr)
        else:
            print(f"  {json.dumps(results[name])[:400]}",
                  file=sys.stderr)
    with open("BENCH_CONFIGS.json", "w") as f:
        json.dump(results, f, indent=2)
    print(json.dumps({"legs": {k: v.get("value") for k, v in
                               results.items()}}))
    failed = _errors_in(results)
    if failed:
        sys.exit("bench_configs: legs errored: "
                 + ", ".join(path for path, _ in failed))


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which == "all":
        _run_all()
    else:
        from apex_tpu.utils import enable_compile_cache

        enable_compile_cache()
        LEGS[which]()


if __name__ == "__main__":
    main()
