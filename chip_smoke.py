"""The standing proof that the system starts on the chip.

Drives the two main paths once each, through the entry points a user
would call, at the full width of a model the repo supports, on ONE
process and ONE TPU chip:

* **train** — BERT-Large (24 x 1024, 16 heads, vocab 30528) at b=16,
  s=512, 80 MLM positions: amp O2 (bf16 compute, fp32 masters) +
  FusedAdam + full remat, the donated jitted step ``bench.py`` times.
* **serve** — an ``InferenceServer`` over Mistral-7B
  widths (hidden 4096, 32/8 heads x d128, ffn 14336, vocab 32000) with
  the depth cut to what leaves a KV pool beside the weights on 16 GB;
  mixed-length greedy and sampled requests through ``submit()`` /
  ``stream()``.

``--chips 4`` runs, instead, the path that exists only across chips —
ZeRO-2 sharded training of the same BERT-Large over a 4-device
``data`` mesh — and the one-device run it is compared with.

Every check is the repo's own means: finite losses near ln(V) that
fall, kernels against their ``*_reference`` at the phase's real shapes,
greedy streams against ``generate()``, the sampling chain-identity
contract, a drained pool, the retrace budget, and the kernels' names in
the compiled programs.  No ``implementation=`` override anywhere: the
point is what ``"auto"`` does on the chip.  One JSON object per phase,
then as the LAST line ``{"ok": true, "device": {...}}``.  Any failed
check or exception exits non-zero with no such line.

Off a TPU the script fails before doing any work.  ``--rehearse``
shrinks the models and lets the platform check pass on the CPU, for a
control-flow rehearsal; the last line then reports the platform it
really ran on (``"cpu"``), so a rehearsal is never read as a chip run.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import time

import numpy as np

SEED = 0


# ------------------------------------------------------------ reporting
class Phase:
    """One phase's checks and printed fields."""

    def __init__(self, name):
        self.name = name
        self.fields = {}
        self.failed = []
        self.t0 = time.perf_counter()

    def check(self, name, ok, **detail):
        self.fields[name] = dict(detail, ok=bool(ok))
        if not ok:
            self.failed.append(name)

    def finish(self):
        self.fields["wall_s"] = round(time.perf_counter() - self.t0, 1)
        print(json.dumps({"phase": self.name, "ok": not self.failed,
                          **self.fields}), flush=True)
        if self.failed:
            sys.exit(f"chip_smoke: phase {self.name!r} failed its "
                     f"checks: {', '.join(self.failed)}")


def rel_err(got, want):
    """Largest absolute error, normalized by the reference's largest
    magnitude (floored at 1) — one number per tensor for the bf16
    tolerance tiers of tests/test_layer_norm.py / test_attention.py."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / max(1.0, float(np.max(np.abs(want)))))


def pallas_calls(text, scope=None):
    """``tpu_custom_call`` sites in a compiled program's text — all of
    them, or those under a kernel's ``jax.named_scope``."""
    lines = [l for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    if scope is not None:
        lines = [l for l in lines if f"/{scope}/pallas_call" in l]
    return len(lines)


def device_bytes(device, key="peak_bytes_in_use"):
    stats = device.memory_stats()     # None where the backend has none
    return None if not stats else stats.get(key)


def cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# ---------------------------------------------------------------- train
def bert_config(rehearse):
    import jax.numpy as jnp

    from apex_tpu.models import BertConfig

    make = BertConfig.tiny if rehearse else BertConfig.bert_large
    # unrolled layers, full remat: the configuration bench.py measures
    return make(remat=True, dtype=jnp.bfloat16, scan_layers=False)


def train_shape(rehearse):
    return (4, 64) if rehearse else (16, 512)


def run_steps(phase, step, state, batch, n_warm, n_steps, vocab):
    """Warm-up + timed steps on the repeated batch; the loss checks
    every train phase shares.  Returns (state, losses, step ms)."""
    import jax

    losses, finites, ms = [], [], []
    for _ in range(n_warm + n_steps):
        t0 = time.perf_counter()
        state, loss, finite = step(state, *batch)
        jax.block_until_ready((state, loss))
        ms.append((time.perf_counter() - t0) * 1e3)
        losses.append(float(loss))
        finites.append(bool(finite))
    ln_v = math.log(vocab)
    phase.check("loss_finite_every_step",
                all(math.isfinite(l) for l in losses), losses=losses)
    phase.check("step0_loss_near_ln_vocab",
                abs(losses[0] - ln_v) <= 0.15 * ln_v,
                loss=losses[0], ln_vocab=round(ln_v, 4))
    phase.check("loss_falls_on_repeated_batch", losses[-1] < losses[0],
                first=losses[0], last=losses[-1])
    phase.check("grads_finite", all(finites))
    return state, losses, ms[n_warm:]


def kernel_parity(phase, b, s, heads, hidden, on_chip):
    """fused_layer_norm / fused_attention, forward and gradients, as
    "auto" resolves them, against their references at the train
    phase's real shapes on seeded inputs."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops import (attention_reference, fused_attention,
                              fused_layer_norm, layer_norm_reference)

    ks = jax.random.split(jax.random.PRNGKey(SEED + 1), 8)
    d = hidden // heads
    x = jax.random.normal(ks[0], (b * s, hidden), jnp.bfloat16)
    w = 1.0 + 0.1 * jax.random.normal(ks[1], (hidden,), jnp.float32)
    bias = 0.1 * jax.random.normal(ks[2], (hidden,), jnp.float32)
    ct = jax.random.normal(ks[3], (b * s, hidden), jnp.float32)
    q, k, v = (jax.random.normal(kk, (b, s, heads, d), jnp.bfloat16)
               for kk in ks[4:7])
    cta = jax.random.normal(ks[7], (b, s, heads, d), jnp.float32)

    def fwd_and_grads(fn, args, cot):
        def loss(*a):
            out = fn(*a)
            return jnp.sum(out.astype(jnp.float32) * cot), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(len(args))), has_aux=True))(*args)
        return (out,) + tuple(grads)

    cases = {
        # tolerance tiers: tests/test_layer_norm.py (bf16: 2e-2) and
        # tests/test_attention.py::test_bf16 (5e-2)
        "layer_norm": (fused_layer_norm, layer_norm_reference,
                       (x, w, bias), ct, 2e-2),
        "attention": (fused_attention, attention_reference,
                      (q, k, v), cta, 5e-2),
    }
    for name, (fused, ref, args, cot, tol) in cases.items():
        got = fwd_and_grads(fused, args, cot)
        want = fwd_and_grads(ref, args, cot)
        errs = [rel_err(g, r) for g, r in zip(got, want)]
        phase.check(f"{name}_matches_reference_fwd_and_grads",
                    max(errs) <= tol, rel_err=errs, tol=tol)
        text = jax.jit(fused).lower(*args).compile().as_text()
        n = pallas_calls(text)
        phase.check(f"{name}_auto_is_the_kernel", n > 0 or not on_chip,
                    tpu_custom_calls=n)


def phase_train(device, rehearse):
    import jax
    import jax.numpy as jnp

    import bench
    from apex_tpu.optim import fused_adam

    phase = Phase("train")
    on_chip = device.platform == "tpu"
    cfg = bert_config(rehearse)
    b, s = train_shape(rehearse)
    state, step, _, batch, _ = bench.build_train_step(
        cfg, fused_adam(1e-4), "O2", jnp.bfloat16, b, s)
    t0 = time.perf_counter()
    compiled = step.lower(state, *batch).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    n_calls = pallas_calls(compiled.as_text())
    phase.check("step_contains_pallas_calls", n_calls > 0 or not on_chip,
                tpu_custom_calls=n_calls)
    state, losses, ms = run_steps(phase, compiled, state, batch,
                                  n_warm=2, n_steps=6,
                                  vocab=cfg.vocab_size)
    phase.fields.update({
        "model": f"bert {cfg.num_layers}x{cfg.hidden_size} "
                 f"heads={cfg.num_heads} vocab={cfg.vocab_size}",
        "batch": b, "seq": s, "mlm_positions": int(batch[1].shape[1]),
        "compile_s": round(compile_s, 1),
        "step_ms": [round(m, 2) for m in ms],
        "step_ms_median": float(np.median(ms)),
        "program_bytes": {"arguments": mem.argument_size_in_bytes,
                          "temporaries": mem.temp_size_in_bytes},
        "peak_bytes_in_use": device_bytes(device),
    })
    del state, compiled, step
    kernel_parity(phase, b, s, cfg.num_heads, cfg.hidden_size, on_chip)
    phase.finish()


# ---------------------------------------------------------------- serve
def llama_config(rehearse):
    import jax.numpy as jnp

    from apex_tpu.models import LlamaConfig

    if rehearse:
        return LlamaConfig.tiny(dtype=jnp.bfloat16,
                                param_dtype=jnp.bfloat16), {}
    full = LlamaConfig.mistral_7b()
    # depth: 8 of 32 layers is ~4 GB of bf16 weights, which leaves a KV
    # pool (and the reference's programs) beside them on 16 GB.
    # context: as the benchmark's Mistral configuration, which dates
    # from when PagedEngine refused windowed configs (it serves them
    # since PR 37; ROADMAP M1): at max_seq_len <= the 4096-token window
    # the window never binds, so the served function is the published
    # one.
    cfg = LlamaConfig.mistral_7b(
        num_layers=8, max_seq_len=4096, sliding_window=None,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    cut = {"num_layers": [full.num_layers, cfg.num_layers],
           "max_seq_len": [full.max_seq_len, cfg.max_seq_len],
           "sliding_window": [full.sliding_window, None]}
    return cfg, cut


def requests_for(cfg, rehearse):
    """~8 requests, mixed prompt lengths, half greedy / half sampled
    with mixed temperature / top-k / top-p; all from SEED."""
    rng = np.random.default_rng(SEED)
    lengths = ([5, 9, 17, 12, 30, 7, 24, 3] if rehearse
               else [16, 48, 129, 300, 517, 777, 1024, 64])
    sampling = [{}, {"temperature": 0.8, "top_k": 40},
                {}, {"temperature": 1.0, "top_p": 0.9},
                {}, {"temperature": 0.7, "top_k": 50, "top_p": 0.95},
                {}, {"temperature": 1.2}]
    return [dict(prompt=rng.integers(0, cfg.vocab_size, size=(n,),
                                     dtype=np.int32),
                 max_new_tokens=6 if rehearse else 16, seed=100 + i,
                 **sampling[i])
            for i, n in enumerate(lengths)]


def serve_all(server, reqs):
    handles = [server.submit(**r) for r in reqs]
    return [list(h.stream(timeout=900)) for h in handles]


def greedy_gap(model, params, prompt, tokens):
    """Teacher-force ``prompt + tokens`` through the plain forward and
    return, over the generated positions, the largest amount by which
    the reference's best logit beats the streamed token's, normalized
    by the logits' largest magnitude: 0 where the stream IS the
    reference's argmax, a bf16 rounding where two logits nearly tie."""
    import jax
    import jax.numpy as jnp

    ids = np.concatenate([prompt, np.asarray(tokens, np.int32)])[None]
    logits = jax.jit(model.apply)(params, jnp.asarray(ids))[0]
    rows = np.asarray(logits[len(prompt) - 1:-1], np.float32)
    chosen = rows[np.arange(len(tokens)), tokens]
    return float(np.max(rows.max(axis=-1) - chosen)
                 / max(1.0, float(np.abs(rows).max())))


def paged_op_parity(phase, cfg, engine, on_chip):
    """paged_attention (s=1, s=chunk) and paged_decode_fused against
    their references at the served shapes, on a seeded pool."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.paged_attention import (
        paged_attention, paged_attention_reference, paged_decode_fused,
        paged_decode_fused_reference)
    from apex_tpu.ops.rope import rope_cos_sin

    b, h, hk, d = engine.max_slots, cfg.num_heads, cfg.kv_heads, \
        cfg.head_dim
    bs, nb = engine.block_size, engine.blocks_total + 1   # + null page
    mb = -(-cfg.max_seq_len // bs)
    chunk = 32 if on_chip else 8
    rng = np.random.default_rng(SEED + 2)
    ks = jax.random.split(jax.random.PRNGKey(SEED + 2), 6)
    kp, vp = (jax.random.normal(k, (hk, nb, bs, d), cfg.dtype)
              for k in ks[:2])
    # every row owns distinct pool blocks (block 0 is the null page;
    # the engine's default pool holds max_seq_len for every slot)
    tables = jnp.asarray(
        1 + rng.permutation(nb - 1)[:b * mb].reshape(b, mb), jnp.int32)
    lengths = jnp.asarray(
        rng.integers(1, cfg.max_seq_len - chunk, size=(b,)), jnp.int32)
    tol = 5e-2                # the bf16 tier of tests/test_attention.py
    for s in (1, chunk):
        q = jax.random.normal(ks[2], (b, s, h, d), cfg.dtype)
        got = jax.jit(paged_attention)(q, kp, vp, tables, lengths)
        want = jax.jit(paged_attention_reference)(q, kp, vp, tables,
                                                  lengths)
        err = rel_err(got, want)
        phase.check(f"paged_attention_s{s}_matches_reference",
                    err <= tol, rel_err=err, tol=tol)
    q = jax.random.normal(ks[3], (b, 1, h, d), cfg.dtype)
    nk, nv = (jax.random.normal(k, (b, 1, hk, d), cfg.dtype)
              for k in ks[4:6])
    cos, sin = rope_cos_sin(cfg.max_seq_len, d, base=cfg.rope_base)
    kw = dict(max_seq_len=cfg.max_seq_len,
              cos_b=cos[lengths][:, None, None, :],
              sin_b=sin[lengths][:, None, None, :])

    def fused(fn):
        return jax.jit(lambda *a: fn(*a, **kw))(
            q, nk, nv, kp, vp, tables, lengths)

    got = fused(paged_decode_fused)
    want = fused(paged_decode_fused_reference)
    errs = [rel_err(g, r) for g, r in zip(got, want)]
    phase.check("paged_decode_fused_matches_reference_out_and_pages",
                max(errs) <= tol, rel_err=errs, tol=tol)


def phase_serve(device, rehearse):
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import LlamaModel, generate
    from apex_tpu.ops.fused_sampling import (fused_sample,
                                             fused_sample_reference)
    from apex_tpu.serving import InferenceServer

    phase = Phase("serve")
    on_chip = device.platform == "tpu"
    cfg, cut = llama_config(rehearse)
    model = LlamaModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED),
                                 jnp.zeros((1, 4), jnp.int32))
    params = {"params": params["params"]}
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(params))
    reqs = requests_for(cfg, rehearse)

    server = InferenceServer(model, params, max_slots=8)
    engine = server.engine
    t0 = time.perf_counter()
    server.start()                    # warm-up traces every executable
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    streams = serve_all(server, reqs)
    wall_s = time.perf_counter() - t0
    sampled = [i for i, r in enumerate(reqs) if "temperature" in r]
    again = serve_all(server, [reqs[i] for i in sampled])
    health = server.health()
    trace_counts = dict(engine.trace_counts)
    decode_text = engine.compiled_step_text()
    prefill_text = engine.compiled_step_text(prefill=True)
    server.shutdown()

    phase.check("every_request_completes",
                all(len(s) == r["max_new_tokens"]
                    for s, r in zip(streams, reqs)),
                tokens=[len(s) for s in streams])
    phase.check("sampled_streams_repeat_for_the_same_seed",
                all(streams[i] == a for i, a in zip(sampled, again)))
    phase.check("pool_drained", health["blocks_in_use"] == 0,
                blocks_in_use=health["blocks_in_use"],
                blocks_total=health["blocks_total"])
    # the documented retrace budget: one trace per executable
    phase.check("trace_counts_within_budget",
                all(n <= 1 for n in trace_counts.values()),
                trace_counts=trace_counts)
    kernels = {
        "decode:paged_decode_fused": pallas_calls(
            decode_text, "paged_decode_fused"),
        "decode:fused_sample": pallas_calls(decode_text,
                                            "fused_sample"),
        "prefill:paged_attention": pallas_calls(prefill_text,
                                                "paged_attention"),
    }
    phase.check("step_programs_contain_their_kernels",
                all(kernels.values()) or not on_chip, **kernels)

    # greedy streams == generate() — token for token, or, where bf16
    # near-ties on seeded random weights split the two paths, every
    # streamed token within a bf16 rounding of the reference's argmax
    # on the teacher-forced stream (the comparison is never dropped)
    tol = 2.0 ** -7
    greedy = {}
    for i, r in enumerate(reqs):
        if i in sampled:
            continue
        ref = generate(model, params, r["prompt"][None],
                       max_new_tokens=r["max_new_tokens"])
        ref = [int(t) for t in np.asarray(ref)[0, len(r["prompt"]):]]
        greedy[i] = (0.0 if ref == streams[i] else
                     greedy_gap(model, params, r["prompt"], streams[i]))
    phase.check("greedy_streams_match_generate",
                all(g <= tol for g in greedy.values()),
                token_identical=[i for i, g in greedy.items()
                                 if g == 0.0],
                logit_gap={i: g for i, g in greedy.items() if g},
                tol=tol)

    # chain identity at the op: one real batch of the served model's
    # logits, engine-style keys (split products of per-request seeds)
    rows = engine.max_slots
    ids = jnp.asarray(np.stack([r["prompt"][:3] for r in reqs][:rows]))
    logits = jax.jit(model.apply)(params, ids)[:, -1]
    keys = jax.vmap(lambda s: jax.random.split(jax.random.PRNGKey(s))[0])(
        jnp.arange(rows, dtype=jnp.uint32) + 100)
    temp = jnp.asarray([0.0, 0.8, 1.0, 0.7, 1.2, 0.0, 0.9, 1.0][:rows],
                       jnp.float32)
    top_k = jnp.asarray([0, 40, 0, 50, 0, 0, 5, 0][:rows], jnp.int32)
    top_p = jnp.asarray([0.0, 0.0, 0.9, 0.95, 0.0, 0.0, 0.0, 0.5][:rows],
                        jnp.float32)
    args = (logits, keys, temp, top_k, top_p)
    got = jax.jit(fused_sample)(*args)
    want = jax.jit(lambda *a: fused_sample_reference(
        *a, cfg.vocab_size))(*args)
    n_kernel = pallas_calls(
        jax.jit(fused_sample).lower(*args).compile().as_text())
    phase.check("fused_sample_equals_reference_ids",
                bool(jnp.array_equal(got, want)) and
                (n_kernel > 0 or not on_chip),
                ids=np.asarray(got).tolist(),
                reference=np.asarray(want).tolist(),
                tpu_custom_calls=n_kernel)

    paged_op_parity(phase, cfg, engine, on_chip)
    phase.fields.update({
        "model": f"llama {cfg.num_layers}x{cfg.hidden_size} "
                 f"heads={cfg.num_heads}/{cfg.kv_heads} "
                 f"ffn={cfg.ffn_size} vocab={cfg.vocab_size}",
        "cut_from_mistral_7b": cut,
        "weight_bytes": int(weight_bytes),
        "slots": engine.max_slots, "block_size": engine.block_size,
        "pool_tokens": engine.pool_tokens,
        "prompt_lengths": [len(r["prompt"]) for r in reqs],
        "tokens": sum(len(s) for s in streams),
        "wall_s_first_wave": round(wall_s, 2),
        "compile_s": round(compile_s, 1),
        # the peak is the process's high-water mark (the train phase
        # set it); bytes_in_use is what the serve phase holds now
        "bytes_in_use": device_bytes(device, "bytes_in_use"),
        "peak_bytes_in_use": device_bytes(device),
    })
    phase.finish()


# -------------------------------------------------------- four chips
def phase_zero4(devices, rehearse):
    """ZeRO-2 over four devices against the same steps on one."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import bench
    from apex_tpu import amp, initialize_mesh
    from apex_tpu.models import BertModel
    from apex_tpu.optim import fused_adam
    from apex_tpu.parallel import (ZeroConfig, zero_shardings,
                                   zero_state_specs)

    phase = Phase("zero2_x4")
    on_chip = devices[0].platform == "tpu"
    n = 4
    cfg = bert_config(rehearse)
    b, s = train_shape(rehearse)
    n_warm, n_steps = 1, 5

    # --- the comparison: same seed, batch and steps on ONE device
    state, step, _, batch, _ = bench.build_train_step(
        cfg, fused_adam(1e-4), "O2", jnp.bfloat16, b, s)
    one = Phase("one_device")
    state, ref_losses, ref_ms = run_steps(
        one, step, state, batch, n_warm, n_steps, cfg.vocab_size)
    phase.failed += one.failed
    phase.fields["one_device"] = dict(one.fields, losses=ref_losses,
                                      step_ms=ref_ms)
    del state, step

    # --- the path: data mesh -> amp O2 + ZeRO-2 -> shard_map step
    # (weights first: under the mesh the model constrains its batch
    # axis to 'data', which the two-row init batch cannot divide)
    model = BertModel(cfg)
    params = model.init(jax.random.PRNGKey(0), batch[0][:2])
    mesh = initialize_mesh(data_parallel_size=-1, devices=devices[:n])
    zero = ZeroConfig(axis="data", stage=2, axis_size=n)
    state = amp.initialize(model.apply, params, fused_adam(1e-4),
                           opt_level="O2", half_dtype=jnp.bfloat16,
                           zero=zero)
    del params
    state = jax.device_put(state, zero_shardings(state, mesh=mesh))
    specs = zero_state_specs(state)
    batch = jax.device_put(batch, NamedSharding(mesh, P("data")))

    def zero_step(state, ids, positions, labels):
        grads, loss = jax.grad(
            lambda p: bench.mlm_loss_of(state, p, ids, positions,
                                        labels),
            has_aux=True)(state.params)
        new_state, finite = state.apply_gradients(grads=grads)
        return new_state, jax.lax.pmean(loss, "data"), finite

    step = jax.jit(jax.shard_map(
        zero_step, mesh=mesh,
        in_specs=(specs, P("data"), P("data"), P("data")),
        out_specs=(specs, P(), P()), check_vma=False),
        donate_argnums=(0,))
    t0 = time.perf_counter()
    compiled = step.lower(state, *batch).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    state, losses, ms = run_steps(phase, compiled, state, batch,
                                  n_warm, n_steps, cfg.vocab_size)

    tol = 2e-2                        # bf16 forward, fp32 masters
    gaps = [abs(a - r) / abs(r) for a, r in zip(losses, ref_losses)]
    phase.check("per_step_loss_agrees_with_one_device",
                max(gaps) <= tol, losses=losses, rel_gap=gaps, tol=tol)
    shard_bytes = {}
    for leaf in jax.tree.leaves(state.opt_state):
        for sh in leaf.addressable_shards:
            shard_bytes[sh.device.id] = (shard_bytes.get(sh.device.id, 0)
                                         + sh.data.nbytes)
    total = sum(shard_bytes.values())
    phase.check("optimizer_state_on_four_devices_a_quarter_each",
                len(shard_bytes) == n and all(
                    abs(v / total - 1 / n) < 0.05
                    for v in shard_bytes.values()),
                bytes_per_device=shard_bytes)
    in_use = {d.id: device_bytes(d, "bytes_in_use")
              for d in devices[:n]}
    phase.check("all_four_devices_hold_bytes",
                all(in_use.values()) or not on_chip,
                bytes_in_use=in_use)
    collectives = {op: len(re.findall(rf"\b{op}(-start)?\(", text))
                   for op in ("reduce-scatter", "all-to-all",
                              "all-gather", "all-reduce")}
    # ZeRO-2: grads leave as a reduce-scatter (lowered here as an
    # all-to-all + local sum), updated params return by all-gather
    phase.check("step_contains_reduce_scatter_and_all_gather",
                (collectives["reduce-scatter"]
                 + collectives["all-to-all"]) > 0
                and collectives["all-gather"] > 0, **collectives)
    phase.fields.update({
        "model": f"bert {cfg.num_layers}x{cfg.hidden_size}",
        "global_batch": b, "seq": s, "mesh": dict(mesh.shape),
        "compile_s": round(compile_s, 1),
        "step_ms": [round(m, 2) for m in ms],
        "step_ms_median": float(np.median(ms)),
        "tpu_custom_calls": pallas_calls(text),
        "peak_bytes_in_use": {d.id: device_bytes(d)
                              for d in devices[:n]},
    })
    phase.finish()


# ----------------------------------------------------------------- main
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run ONLY the sharded-training path and "
                         "its one-device comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny models, any platform: a control-flow "
                         "rehearsal, reported as the platform it ran on")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu" and not args.rehearse:
        sys.exit(f"chip_smoke: jax found no TPU (platform "
                 f"{device.platform!r}, {len(devices)} device(s)) — "
                 f"this is the on-chip smoke; nothing was run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs "
                 f"{args.chips} devices, jax found {len(devices)}")

    from apex_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    before = cache_entries(cache_dir)
    print(json.dumps({"phase": "setup", "jax": jax.__version__,
                      "compile_cache": cache_dir,
                      "cache_entries_before": before}), flush=True)
    if args.chips == 4:
        phase_zero4(devices, args.rehearse)
    else:
        phase_train(device, args.rehearse)
        phase_serve(device, args.rehearse)
    print(json.dumps({"phase": "teardown", "compile_cache": cache_dir,
                      "cache_entries_before": before,
                      "cache_entries_after": cache_entries(cache_dir),
                      "memory_stats": device.memory_stats()}))
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
