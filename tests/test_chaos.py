"""Chaos tier (``pytest -m chaos``): end-to-end fault trajectories.

Two acceptance soaks for the resilience layer (docs/resilience.md):

- **kill-and-resume**: a training run killed by an injected preemption
  auto-resumes from the latest valid checkpoint and reproduces the
  uninterrupted loss trajectory (the ``test_loss_trajectory.py``
  claim, extended across a process "death"); a corrupted latest
  checkpoint is detected by its manifest hashes and the run falls back
  to the previous one — trajectory still intact.
- **serving soak**: with transient step faults firing throughout and
  per-request deadlines in the mix, every accepted request either
  completes or fails with an explicit terminal error — none lost, none
  hung — the server keeps serving, and the engine's compile/retrace
  budgets are exactly the warmup budgets (recovery replays compiled
  programs, it never traces new ones).
- **fleet soak** (ISSUE 6): SIGKILL-equivalent replica death — and a
  graceful drain — under mixed greedy/top-p/deadline traffic on a
  3-replica ``FleetRouter``: zero lost/hung requests, migrated greedy
  streams token-identical to an uninterrupted ``generate()``,
  survivors' paged pools back to ``blocks_in_use == 0``, and every
  replica's trace budget still exactly 4 executables × 1 trace.
- **quantized paged soak** (ISSUE 8): the sharing+spec paged soak
  with ``kv_dtype="int8"`` — zero lost/hung, ``blocks_in_use == 0``
  (per-page scales freed with their pages), budgets exactly 5 × 1.
- **sharded-replica kill soak** (ISSUE 13): the fleet soak with a
  TENSOR-PARALLEL replica in the pool (one replica spanning 2 chips,
  KV pool sharded on kv_heads) — the TP replica is the one killed
  under mixed traffic: zero lost/hung, its tenants migrate onto
  single-chip survivors token-identically (migration re-prefills from
  the streamed prefix, so replicas of DIFFERENT mesh shapes
  interoperate), survivors' pools drain to ``blocks_in_use == 0``.
- **ZeRO-sharded kill-and-resume** (ISSUE 11): the training soak with
  optimizer state ZeRO-2-sharded over the 8-device mesh — checkpoint
  mid-run, kill, restore onto the ``zero_shardings`` placement,
  spliced trajectory allclose to uninterrupted; plus the
  ``bert_o1_zero`` bench leg's CPU-tiny smoke (measured hbm drop,
  grown-batch row, loss agreement).

The serving and fleet soaks also run under the **strict runtime lock
sanitizer** (``apex_tpu.utils.lockcheck``, ISSUE 9): every lock in the
stack is wrapped with an acquisition-order recorder and every
``# graftlint: guarded-by`` field access is verified to hold its
declared lock — the soak asserts zero reports at the end.  The
chaos-smoke CI job exports ``APEX_TPU_LOCKCHECK=strict`` to document
the mode; the soaks force ``strict=True`` regardless.

The training soaks additionally run under the **strict runtime
numerics sanitizer** (``apex_tpu.utils.numcheck``, ISSUE 10 — the
precision pass's dynamic twin, same mold): the amp cast boundaries,
loss-scale path and optimizer step are hooked, grad underflow /
non-finite stats recorded, and the soak asserts zero numerics
violations at the end.  ``TestMixedPrecisionBenchSmoke`` is the bench
leg's chaos twin: the BERT-bench O2 recipe at toy size, with a planted
overflow step proving skip/backoff fires (and is *counted*) without a
violation.  The chaos-smoke CI job exports ``APEX_TPU_NUMCHECK=strict``
to document the mode; the soaks force ``strict=True`` regardless.

CI runs these in the dedicated ``chaos-smoke`` job (small configs,
CPU).  They carry ``slow`` too: the tier-1 ``-m 'not slow'`` gate
already rides its wall-clock budget, and these three dots cost ~a
minute of mini-training — the chaos job (``-m chaos``) is their gate;
the fast unit tier in ``tests/test_resilience.py`` stays in tier-1.
"""

import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu import amp
from apex_tpu.models import GPTConfig, GPTModel, generate, gpt_loss_fn
from apex_tpu.optim import fused_adam
from apex_tpu.resilience import (
    FaultPlan,
    FaultSpec,
    ResilientCheckpointer,
    ResilientLoop,
    active,
)
from apex_tpu.serving import (
    FleetRouter,
    InferenceServer,
    RequestFailed,
    tp_mesh,
)
from apex_tpu.transformer.testing import standalone_gpt
from apex_tpu.utils import (MetricsWriter, lockcheck, numcheck,
                            shardcheck, tracecheck)

pytestmark = [pytest.mark.chaos, pytest.mark.slow]


class TestKillAndResumeTrajectory:
    STEPS = 40
    B, S = 4, 16
    CKPT_EVERY = 8

    @pytest.fixture(autouse=True)
    def _numcheck_strict(self):
        # ISSUE-10: the GPT soak runs under the strict runtime
        # numerics sanitizer — installed before the first jit trace so
        # the hooks ride the compiled step; torn down even on failure
        # so the process-wide wrappers never leak into other tests
        numcheck.reset()
        numcheck.instrument(strict=True)
        yield
        numcheck.uninstrument()
        numcheck.reset()

    def _make(self):
        model, init_params = standalone_gpt(seed=0, max_seq_len=self.S)
        vocab = model.cfg.vocab_size
        # the trajectory-test recipe: a fixed pool of batches, cycled,
        # so the signal is memorization speed and data is a pure
        # function of the step index (what makes resume exact)
        ids = jax.random.randint(
            jax.random.PRNGKey(1234), (4, self.B, self.S + 1), 0,
            vocab, jnp.int32)

        def make_state():
            return amp.initialize(
                model.apply, {"params": init_params},
                fused_adam(3e-4), opt_level="O0")

        @jax.jit
        def step(state, chunk):
            inputs, labels = chunk[:, :-1], chunk[:, 1:]

            def loss_fn(p):
                logits = state.apply_fn(p, inputs)
                return gpt_loss_fn(logits.astype(jnp.float32), labels)

            loss, grads = jax.value_and_grad(loss_fn)(state.params)
            new_state, _finite = state.apply_gradients(grads=grads)
            return new_state, loss

        def loop_step(state, batch):
            state, loss = step(state, batch)
            return state, {"loss": loss}

        def data_fn(i):
            return ids[i % 4]

        return make_state, step, loop_step, data_fn

    def _rows(self, writer):
        return {s: r["loss"] for s, r in writer.history}

    def test_preempt_resume_and_corrupt_skip(self, tmp_path):
        make_state, step, loop_step, data_fn = self._make()

        # ------------------------- the uninterrupted reference run
        state = make_state()
        ref = []
        for i in range(self.STEPS):
            state, loss = step(state, data_fn(i))
            ref.append(float(loss))
        assert np.all(np.isfinite(ref))
        assert ref[-1] < ref[0]             # it actually trains

        # ------------------------- run 1: killed by injected preemption
        ckpt_dir = str(tmp_path / "ckpts")
        kill_at = 17
        writer1 = MetricsWriter(sink=lambda s, m: None)
        loop1 = ResilientLoop(
            loop_step,
            checkpointer=ResilientCheckpointer(ckpt_dir, keep=3),
            checkpoint_every=self.CKPT_EVERY,
            scalars_of=lambda aux: {"loss": aux["loss"]},
            metrics=writer1)
        plan = FaultPlan([FaultSpec(site="train.step", kind="preempt",
                                    step=kill_at, times=1)])
        with active(plan):
            _carry, report1 = loop1.run(make_state(), data_fn,
                                        self.STEPS)
        assert report1.preempted
        assert report1.final_step == kill_at

        # corrupt the preemption checkpoint: flip bytes in one payload
        # file of the newest step dir — restore must detect it via the
        # manifest hashes and fall back to the previous checkpoint
        ck = ResilientCheckpointer(ckpt_dir, keep=3)
        assert ck.latest_step() == kill_at
        newest = os.path.join(ckpt_dir, f"step_{kill_at:08d}")
        victims = []
        for base, _dirs, names in os.walk(newest):
            victims.extend(
                os.path.join(base, n) for n in names
                if "manifest" not in n
                and os.path.getsize(os.path.join(base, n)) > 0)
        with open(sorted(victims)[0], "r+b") as f:
            blob = f.read(16)
            f.seek(0)
            f.write(bytes(b ^ 0xFF for b in blob))

        # ------------------------- run 2: auto-resume, finish the run
        writer2 = MetricsWriter(sink=lambda s, m: None)
        loop2 = ResilientLoop(
            loop_step,
            checkpointer=ResilientCheckpointer(ckpt_dir, keep=3),
            checkpoint_every=self.CKPT_EVERY,
            scalars_of=lambda aux: {"loss": aux["loss"]},
            metrics=writer2)
        carry2, report2 = loop2.run(make_state(), data_fn, self.STEPS)
        # the corrupt step-17 checkpoint was skipped for step 16
        assert report2.resumed_from == 16
        assert report2.final_step == self.STEPS
        assert not report2.preempted

        # ------------------------- the spliced trajectory matches
        rows1, rows2 = self._rows(writer1), self._rows(writer2)
        # metrics are emitted at step = cursor+1 (1-based)
        spliced = [rows1[i] if i <= report2.resumed_from else rows2[i]
                   for i in range(1, self.STEPS + 1)]
        np.testing.assert_allclose(
            spliced, ref, rtol=0, atol=1e-5,
            err_msg="resumed trajectory diverged from uninterrupted")
        # and the replayed overlap (steps 17 after rewind vs run 1's
        # own pre-kill steps) is bit-identical too: same data, same
        # restored state, same program
        overlap = [i for i in rows2 if i in rows1]
        for i in overlap:
            np.testing.assert_allclose(rows2[i], rows1[i], rtol=0,
                                       atol=1e-5)

        # ------------------- zero numerics violations across the soak
        # (kill, corrupt-checkpoint fallback and resume included) —
        # and the sanitizer demonstrably observed the optimizer steps
        jax.effects_barrier()
        numcheck.assert_clean()
        assert numcheck.summary()["grad_stat_steps"] > 0


class TestZeroKillAndResumeTrajectory:
    """ISSUE-11 chaos arm: the kill-and-resume soak with the optimizer
    state ZeRO-2-SHARDED over an 8-device mesh.  Checkpoint mid-run,
    kill via an injected preemption, restore with the
    ``zero_shardings`` placement (the checkpoint target is the placed
    state, so orbax lands the master/moment shards back on their mesh
    rows), and the spliced trajectory must match the uninterrupted run
    — sharding the state must not change WHAT is persisted, only
    where it lives.  Runs under the strict numerics sanitizer: fp32
    master shards verified at runtime across kill and resume.
    """

    STEPS = 40
    B, S = 8, 16            # batch divisible by the 8-way mesh
    CKPT_EVERY = 8

    @pytest.fixture(autouse=True)
    def _sanitizers_strict(self):
        # ISSUE-16: the placement sanitizer rides alongside the
        # numerics one — the declared ZeRO layout is re-checked
        # against every compiled step's actual output shardings
        numcheck.reset()
        numcheck.instrument(strict=True)
        shardcheck.reset()
        yield
        shardcheck.uninstrument()
        shardcheck.reset()
        numcheck.uninstrument()
        numcheck.reset()

    def _make(self):
        from jax.sharding import PartitionSpec as P

        from apex_tpu.parallel import (ZeroConfig, zero_shardings,
                                       zero_state_specs)

        model, init_params = standalone_gpt(seed=0, max_seq_len=self.S)
        vocab = model.cfg.vocab_size
        ids = jax.random.randint(
            jax.random.PRNGKey(1234), (4, self.B, self.S + 1), 0,
            vocab, jnp.int32)
        # raw mesh, fully-manual step (test_loss_trajectory precedent)
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:8]),
                                 ("data",))
        tx = fused_adam(3e-4)   # ONE transform: shared static treedef

        def make_state():
            state = amp.initialize(
                model.apply, {"params": init_params}, tx,
                opt_level="O0",
                zero=ZeroConfig(axis="data", stage=2, axis_size=8))
            # committed sharded placement — doubles as the
            # checkpoint-restore target
            return jax.device_put(state,
                                  zero_shardings(state, mesh=mesh))

        specs = zero_state_specs(make_state())

        def z_step(state, chunk):
            inputs, labels = chunk[:, :-1], chunk[:, 1:]

            def loss_fn(p):
                logits = state.apply_fn(p, inputs)
                return gpt_loss_fn(logits.astype(jnp.float32), labels)

            loss, grads = jax.value_and_grad(loss_fn)(state.params)
            new_state, _finite = state.apply_gradients(grads=grads)
            return new_state, jax.lax.pmean(loss, "data")

        step = jax.jit(jax.shard_map(
            z_step, mesh=mesh,
            in_specs=(specs, P("data")), out_specs=(specs, P()),
            check_vma=False))

        # runtime placement oracle (ISSUE-16): the step's declared
        # ZeRO layout — master/moment shards on their mesh rows,
        # params replicated, pmean'd loss replicated — verified
        # against the compiled executable's actual outputs every call
        declared = (jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P)),
            jax.sharding.NamedSharding(mesh, P()))
        step = shardcheck.wrap_step(step, declared=declared,
                                    mesh=mesh, name="zero.train_step",
                                    strict=True)

        def loop_step(state, batch):
            state, loss = step(state, batch)
            return state, {"loss": loss}

        def data_fn(i):
            return ids[i % 4]

        return make_state, step, loop_step, data_fn

    def _rows(self, writer):
        return {s: r["loss"] for s, r in writer.history}

    def test_sharded_preempt_resume_matches_uninterrupted(
            self, tmp_path):
        from jax.sharding import PartitionSpec as P

        make_state, step, loop_step, data_fn = self._make()

        # ------------------------- the uninterrupted reference run
        state = make_state()
        ref = []
        for i in range(self.STEPS):
            state, loss = step(state, data_fn(i))
            ref.append(float(loss))
        assert np.all(np.isfinite(ref))
        assert ref[-1] < ref[0]

        # ------------------- run 1: killed by injected preemption
        ckpt_dir = str(tmp_path / "ckpts")
        kill_at = 17
        writer1 = MetricsWriter(sink=lambda s, m: None)
        loop1 = ResilientLoop(
            loop_step,
            checkpointer=ResilientCheckpointer(ckpt_dir, keep=3),
            checkpoint_every=self.CKPT_EVERY,
            scalars_of=lambda aux: {"loss": aux["loss"]},
            metrics=writer1)
        plan = FaultPlan([FaultSpec(site="train.step", kind="preempt",
                                    step=kill_at, times=1)])
        with active(plan):
            _carry, report1 = loop1.run(make_state(), data_fn,
                                        self.STEPS)
        assert report1.preempted
        assert report1.final_step == kill_at

        # ------------------- run 2: auto-resume onto the SHARDED
        # placement (the target is the zero_shardings-placed state)
        writer2 = MetricsWriter(sink=lambda s, m: None)
        loop2 = ResilientLoop(
            loop_step,
            checkpointer=ResilientCheckpointer(ckpt_dir, keep=3),
            checkpoint_every=self.CKPT_EVERY,
            scalars_of=lambda aux: {"loss": aux["loss"]},
            metrics=writer2)
        carry2, report2 = loop2.run(make_state(), data_fn, self.STEPS)
        assert report2.resumed_from == kill_at
        assert report2.final_step == self.STEPS
        assert not report2.preempted

        # master shards came back ON their mesh rows: 1/8-sized
        # addressable shards with the zero spec
        for leaf in jax.tree.leaves(carry2.opt_state.master):
            # (trailing-None spec normalization differs across paths)
            assert tuple(leaf.sharding.spec)[:1] == ("data",)
            assert leaf.sharding.shard_shape(leaf.shape)[0] * 8 \
                == leaf.shape[0]
            assert leaf.dtype == jnp.float32

        # ------------------------- the spliced trajectory matches
        rows1, rows2 = self._rows(writer1), self._rows(writer2)
        spliced = [rows1[i] if i <= report2.resumed_from else rows2[i]
                   for i in range(1, self.STEPS + 1)]
        np.testing.assert_allclose(
            spliced, ref, rtol=0, atol=1e-5,
            err_msg="ZeRO-sharded resume diverged from uninterrupted")

        # ------------------- strict numerics oracle: clean, and the
        # shard-local updates consumed only fp32 masters
        jax.effects_barrier()
        numcheck.assert_clean()
        hist = numcheck.site_histograms()
        assert set(hist["apply_gradients.master_shards"]) \
            == {"float32"}
        # ... and the placement oracle: every step of all three runs
        # actually landed the shards where the ZeRO spec declares
        shardcheck.assert_clean()
        zsite = shardcheck.site_shardings()["zero.train_step"]
        assert zsite["checked"] > 0
        assert zsite["mismatched"] == 0


class TestZeroBenchSmoke:
    """ISSUE-11 CI bench smoke: the ``bert_o1_zero`` leg at a CPU-tiny
    preset — the emission must carry a measured hbm_peak/state-bytes
    drop for ZeRO-2 vs the replicated-DP baseline, a grown-batch row
    that fits the DP HBM budget, and final-loss agreement at equal
    batch.  (The full-size leg rides ``bench_configs.py bert_o1``
    on-chip; this pins the protocol and the emission schema.)"""

    def test_zero_leg_emits_hbm_drop(self):
        import json
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device"
                              "_count=8").strip()
        env.update({"BENCH_BERT_ZERO_LAYERS": "1", "BENCH_BATCH": "8",
                    "BENCH_SEQ": "32", "BENCH_ZERO_STEPS": "2"})
        r = subprocess.run(
            [sys.executable,
             os.path.join(repo, "bench_configs.py"), "bert_o1_zero"],
            env=env, capture_output=True, text=True, timeout=900)
        assert r.returncode == 0, r.stderr[-2000:]
        rows = [json.loads(l) for l in r.stdout.splitlines()
                if l.startswith("{")]
        assert rows, r.stdout[-2000:]
        out = rows[-1]
        assert out["metric"] == "bert_o2_zero2_samples_per_sec"
        # the tentpole acceptance: measured hbm drop, sharded-state
        # residency drop, grown batch inside the DP budget, loss
        # agreement at equal batch
        assert out["hbm_peak_drop_bytes"] > 0, out
        assert out["state_bytes_saved_per_chip"] > 0, out
        assert out["rows"]["zero2"]["state_bytes_per_chip"] \
            < out["rows"]["dp"]["state_bytes_per_chip"]
        assert out["grown_batch"] >= out["rows"]["dp"]["global_batch"]
        assert out["grown_batch_fits_dp_hbm_budget"], out
        assert out["final_loss_delta_equal_batch"] < 0.05, out
        model = out["zero_bytes_on_wire"]
        assert model["state_bytes_saved_per_chip"] > 0
        assert model["wire_reduction_vs_dp"] > 1.0


class TestMixedPrecisionBenchSmoke:
    """ISSUE-10 bench-smoke twin: the bench BERT leg's mixed-precision
    recipe (O2 + FusedAdam + ``scale_loss`` + ``apply_gradients``) at
    toy size, under the strict runtime numerics sanitizer — with a
    deliberately planted fp16 overflow step proving that the dynamic
    loss scaler's skip/backoff path fires, is *counted* on the shared
    ``amp.loss_scale.*`` counters (the bench emission's source), and is
    NOT a numerics violation; the trajectory keeps training through it.
    """

    STEPS = 18
    B, S = 4, 16

    def test_o2_fp16_smoke_strict_numcheck_clean(self):
        from apex_tpu.core.loss_scale import DynamicLossScale
        from apex_tpu.transformer.testing import standalone_gpt
        from apex_tpu.utils.metrics import counters

        numcheck.reset()
        numcheck.instrument(strict=True)
        try:
            model, init_params = standalone_gpt(seed=0, max_seq_len=self.S)
            vocab = model.cfg.vocab_size
            ids = jax.random.randint(
                jax.random.PRNGKey(7), (4, self.B, self.S + 1), 0,
                vocab, jnp.int32)

            state = amp.initialize(
                model.apply, {"params": init_params}, fused_adam(3e-4),
                opt_level="O2", half_dtype=jnp.float16)
            # short growth interval so the soak exercises growth too
            ls = DynamicLossScale(growth_interval=4)
            state = state.replace(loss_scaler=ls,
                                  loss_scale_state=ls.init())

            @jax.jit
            def step(state, chunk, boost):
                inputs, labels = chunk[:, :-1], chunk[:, 1:]

                def loss_fn(p):
                    logits = state.apply_fn(p, inputs)
                    loss = gpt_loss_fn(logits.astype(jnp.float32),
                                       labels)
                    # `boost` plants a deterministic overflow: at the
                    # poisoned step the scaled loss (and so the fp16
                    # grads) goes inf, driving the skip/backoff path
                    return state.scale_loss(loss * boost), loss

                grads, loss = jax.grad(loss_fn, has_aux=True)(
                    state.compute_params())
                new_state, finite = state.apply_gradients(grads=grads)
                return new_state, loss, finite

            g0 = counters.get("amp.loss_scale.growth")
            b0 = counters.get("amp.loss_scale.backoff")
            overflow_at = 9
            losses, finites = [], []
            for i in range(self.STEPS):
                boost = jnp.asarray(
                    1e30 if i == overflow_at else 1.0, jnp.float32)
                state, loss, finite = step(state, ids[i % 4], boost)
                losses.append(float(loss))
                finites.append(bool(finite))
            jax.effects_barrier()

            # the planted overflow skipped exactly its own step...
            assert not finites[overflow_at]
            assert all(f for i, f in enumerate(finites)
                       if i != overflow_at)
            # ...was counted as a backoff (and clean runs as growth)
            assert counters.get("amp.loss_scale.backoff") == b0 + 1
            assert counters.get("amp.loss_scale.growth") > g0
            # the un-boosted losses stayed finite and it still trains
            assert np.all(np.isfinite(losses))
            assert losses[-1] < losses[0]

            # strict sanitizer: the overflow is diet, not a violation;
            # masters stayed fp32 through every step
            numcheck.assert_clean()
            s = numcheck.summary()
            assert s["grad_stat_steps"] == self.STEPS
            assert s["nonfinite_grad_steps"] == 1
            assert s["sites"]["apply_gradients.params"] \
                == {"float32": s["sites"]["apply_gradients.params"]
                    .get("float32", 0)}   # fp32 masters only
            assert "float16" in s["sites"]["apply_gradients.grads"]
        finally:
            numcheck.uninstrument()
            numcheck.reset()


class TestServingChaosSoak:
    @pytest.fixture(autouse=True)
    def _shardcheck(self):
        # ISSUE-16: the soak runs under the strict placement
        # sanitizer; torn down even on failure so the process-wide
        # step wrappers and monitoring listener never leak
        shardcheck.reset()
        yield
        shardcheck.uninstrument()
        shardcheck.reset()

    def _tiny(self):
        cfg = GPTConfig.tiny(position_embedding="learned",
                             scan_layers=True)
        model = GPTModel(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
        return model, {"params": params["params"]}

    def test_soak_no_lost_requests_no_retraces(self):
        model, params = self._tiny()
        server = InferenceServer(model, params, max_slots=3,
                                 block_size=8, prefill_chunk=4)
        # runtime lock sanitizer, strict: order-inversion recording on
        # every lock in the stack plus guarded-by field verification
        # (docs/graftlint.md) — instrumented before the worker starts
        lockcheck.reset()
        lockcheck.instrument(server, strict=True)
        # ... and the strict placement sanitizer on the same server:
        # single-chip, so no declared layout to verify, but every step
        # window must stay free of unexpected device-to-host traffic
        shardcheck.instrument(server, strict=True)
        # transient faults throughout the soak (attempt counter: every
        # 5th decode attempt), plus one admission-path fault
        plan = FaultPlan([
            FaultSpec(site="serving.step", kind="transient", every=5,
                      times=4),
            FaultSpec(site="serving.admit", kind="transient", step=3,
                      times=1),
        ])
        rng = np.random.default_rng(23)
        # short requests: a requeued continuation (prompt ++ emitted
        # tokens) is a few chunks at most
        cases = [
            (3, 4, 0.0, None, None), (7, 3, 0.8, 20, None),
            (5, 5, 1.2, 5, 0.9), (2, 6, 0.0, None, None),
            (8, 2, 0.5, None, 0.5), (4, 4, 0.0, None, None),
            (6, 3, 1.0, 50, 0.95), (4, 5, 0.0, None, None),
            (9, 4, 0.7, 10, None), (1, 2, 0.0, None, None),
            (10, 3, 1.5, 2, 1.0), (6, 6, 0.0, None, None),
        ]
        with active(plan):
            with server:
                before = tracecheck.trace_event_count()
                handles = []
                for i, (L, n, t, k, p) in enumerate(cases):
                    handles.append(server.submit(
                        rng.integers(0, model.cfg.vocab_size,
                                     size=(L,)).astype(np.int32),
                        max_new_tokens=n, temperature=t, top_k=k,
                        top_p=p, seed=i))
                # two deadline-doomed requests: accepted, then expired
                doomed = [server.submit(
                    np.zeros(3, np.int32), max_new_tokens=5,
                    deadline=1e-4) for _ in range(2)]

                completed, failed, hung = 0, 0, 0
                for h in handles + doomed:
                    try:
                        toks = h.result(timeout=300)
                        completed += 1
                        assert 1 <= len(toks)
                    except RequestFailed:
                        failed += 1
                    except TimeoutError:
                        hung += 1
                health = server.health()
                after = tracecheck.trace_event_count()

        # zero lost/hung: every accepted request reached a terminal
        # outcome, explicitly
        total = len(handles) + len(doomed)
        assert hung == 0
        assert completed + failed == total
        assert completed >= len(handles) - 2    # faults mostly healed
        assert failed >= 1                      # the doomed deadlines
        # the server survived the whole soak
        assert health["status"] == "serving", health
        assert server.error is None
        assert health["requeues"] >= 1          # recovery actually ran
        # compile/retrace budgets unchanged: recovery replays compiled
        # programs — warmup budgets exactly, zero traces during soak
        assert after == before, "chaos soak retraced after warmup"
        assert server.engine.trace_counts == {
            "decode_step": 1, "prefill_step": 1, "admit": 1,
            "release": 1}
        # every path out of a slot (finish, requeue, deadline) freed
        # its pages
        assert health["blocks_in_use"] == 0
        # the strict lock sanitizer observed the whole storm: zero
        # order inversions, zero guarded-field touches without locks
        lockcheck.assert_clean()
        # ... and the placement sanitizer: the engine's per-step host
        # sync happens OUTSIDE the compiled-step windows it watched
        shardcheck.assert_clean()
        assert shardcheck.site_shardings()[
            "PagedEngine._decode"]["calls"] >= 1

    def test_worker_survives_and_serves_after_faults(self):
        """After the fault plan is exhausted the same server keeps
        taking new traffic — self-healing, not merely not-crashing."""
        model, params = self._tiny()
        server = InferenceServer(model, params, max_slots=2,
                                 block_size=8, prefill_chunk=4)
        plan = FaultPlan([FaultSpec(site="serving.step",
                                    kind="transient", steps=(1, 2))])
        with active(plan):
            with server:
                h1 = server.submit(np.zeros(3, np.int32),
                                   max_new_tokens=4)
                try:
                    h1.result(timeout=300)
                except RequestFailed:
                    pass
                h2 = server.submit(np.ones(5, np.int32),
                                   max_new_tokens=3)
                assert len(h2.result(timeout=300)) == 3
                assert server.health()["ready"]


class TestPagedServingChaosSoak:
    """ISSUE-5 chaos satellite: the paged engine under injected
    transient faults + deadline expiries must release every pool page
    — requeue, terminal failure and mid-decode eviction all route
    through the same block-freeing release, so a fault storm cannot
    leak the KV pool (the paged analogue of "no lost requests")."""

    def _tiny(self):
        cfg = GPTConfig.tiny(position_embedding="learned",
                             scan_layers=True)
        model = GPTModel(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
        return model, {"params": params["params"]}

    def test_soak_releases_all_blocks_no_retraces(self):
        model, params = self._tiny()
        server = InferenceServer(model, params, max_slots=3,
                                 block_size=8,
                                 pool_tokens=256, prefill_chunk=4)
        plan = FaultPlan([
            FaultSpec(site="serving.step", kind="transient", every=5,
                      times=4),
            FaultSpec(site="serving.admit", kind="transient", step=3,
                      times=1),
        ])
        rng = np.random.default_rng(29)
        cases = [
            (3, 4, 0.0, None, None), (7, 3, 0.8, 20, None),
            (12, 5, 1.2, 5, 0.9), (2, 6, 0.0, None, None),
            (8, 2, 0.5, None, 0.5), (17, 4, 0.0, None, None),
            (6, 3, 1.0, 50, 0.95), (4, 5, 0.0, None, None),
            (9, 4, 0.7, 10, None), (1, 2, 0.0, None, None),
            (10, 3, 1.5, 2, 1.0), (6, 6, 0.0, None, None),
        ]
        with active(plan):
            with server:
                before = tracecheck.trace_event_count()
                handles = []
                for i, (L, n, t, k, p) in enumerate(cases):
                    handles.append(server.submit(
                        rng.integers(0, model.cfg.vocab_size,
                                     size=(L,)).astype(np.int32),
                        max_new_tokens=n, temperature=t, top_k=k,
                        top_p=p, seed=i))
                doomed = [server.submit(
                    np.zeros(3, np.int32), max_new_tokens=5,
                    deadline=1e-4) for _ in range(2)]
                completed, failed, hung = 0, 0, 0
                for h in handles + doomed:
                    try:
                        toks = h.result(timeout=300)
                        completed += 1
                        assert 1 <= len(toks)
                    except RequestFailed:
                        failed += 1
                    except TimeoutError:
                        hung += 1
                health = server.health()
                after = tracecheck.trace_event_count()

        total = len(handles) + len(doomed)
        assert hung == 0
        assert completed + failed == total
        assert completed >= len(handles) - 2
        assert failed >= 1
        assert health["status"] == "serving", health
        assert server.error is None
        assert health["requeues"] >= 1
        # the tentpole invariant: every page came home — no leak
        # across faults, deadlines, requeues and normal completion
        assert health["blocks_in_use"] == 0
        assert server.engine.blocks_in_use == 0
        # recovery replays compiled programs at the exact paged budget
        assert after == before, "paged chaos soak retraced"
        assert server.engine.trace_counts == {
            "decode_step": 1, "prefill_step": 1, "admit": 1,
            "release": 1}

    def test_soak_sharing_and_spec_no_leaks_token_identical(self):
        """ISSUE-7 chaos satellite: the paged soak with prefix sharing
        AND speculative decoding on.  Transient step/admit faults,
        deadline expiries, and pool-pressure preempt-requeues all ride
        refcounted shared pages and drafted steps — at the end not one
        page is leaked (``blocks_in_use == 0`` exactly: a refcount
        miscount would strand or double-free pages), every surviving
        greedy chain is token-identical to ``generate()``, and the
        trace budget is exactly the warmed 5 × 1."""
        model, params = self._tiny()
        server = InferenceServer(model, params, max_slots=3,
                                 block_size=8,
                                 pool_tokens=160, prefill_chunk=4,
                                 admit_headroom=0, share_prefixes=True,
                                 spec_tokens=3)
        plan = FaultPlan([
            FaultSpec(site="serving.step", kind="transient", every=6,
                      times=3),
            FaultSpec(site="serving.admit", kind="transient", step=4,
                      times=1),
        ])
        rng = np.random.default_rng(71)
        pref = rng.integers(0, model.cfg.vocab_size,
                            size=(16,)).astype(np.int32)
        cases = []                   # (prompt, n, temperature, seed)
        for i in range(12):
            if i % 2 == 0:           # hot shared prompt, lookup-friendly
                prompt = np.concatenate([pref, rng.integers(
                    0, model.cfg.vocab_size,
                    size=(1 + i // 2,)).astype(np.int32)])
            else:                    # cold random traffic
                prompt = rng.integers(0, model.cfg.vocab_size,
                                      size=(3 + i,)).astype(np.int32)
            cases.append((prompt, 4 + i % 8, 0.0 if i % 3 else 0.0, i))
        with active(plan):
            with server:
                before = tracecheck.trace_event_count()
                handles = [
                    server.submit(p, max_new_tokens=n, temperature=t,
                                  seed=s)
                    for p, n, t, s in cases]
                doomed = [server.submit(
                    np.concatenate([pref, np.zeros(2, np.int32)]),
                    max_new_tokens=5, deadline=1e-4)
                    for _ in range(2)]
                completed, failed, hung = 0, 0, 0
                survivors = []
                for (p, n, _t, _s), h in zip(cases, handles):
                    try:
                        toks = h.result(timeout=300)
                        completed += 1
                        survivors.append((p, n, toks))
                    except RequestFailed:
                        failed += 1
                    except TimeoutError:
                        hung += 1
                for h in doomed:
                    try:
                        h.result(timeout=300)
                        completed += 1
                    except RequestFailed:
                        failed += 1
                    except TimeoutError:
                        hung += 1
                health = server.health()
                after = tracecheck.trace_event_count()

        assert hung == 0
        assert completed + failed == len(cases) + len(doomed)
        assert completed >= len(cases) - 2
        assert health["status"] == "serving", health
        assert server.error is None
        # the tentpole invariant under SHARING: every page came home —
        # refcounts balanced across faults, deadlines, preempts,
        # CoW forks and normal completion
        assert health["blocks_in_use"] == 0
        assert server.engine.blocks_in_use == 0
        assert server.engine.shared_blocks == 0
        # greedy chains that completed are token-identical (across
        # shared prefixes, drafted steps and any preempt-requeue)
        for p, n, toks in survivors:
            ref = np.asarray(generate(
                model, params, jnp.asarray(p[None]),
                max_new_tokens=n))[0, len(p):]
            np.testing.assert_array_equal(np.asarray(toks), ref)
        # drafting actually happened, and recovery replayed compiled
        # programs at the exact warmed budget — 5 executables, 1 each
        assert server.engine.spec_proposed > 0
        assert after == before, "sharing+spec chaos soak retraced"
        assert server.engine.trace_counts == {
            "decode_step": 1, "prefill_step": 1, "spec_step": 1,
            "admit": 1, "release": 1}

    def test_soak_quantized_sharing_and_spec_no_leaks(self):
        """ISSUE-8 chaos satellite: the sharing+spec soak with
        ``kv_dtype="int8"`` on — transient step/admit faults, deadline
        expiries and pool-pressure preempts over a QUANTIZED pool.
        Zero lost/hung; ``blocks_in_use == 0`` exactly at the end (a
        page's scale lives at its pool index and is reset at the next
        tenant's first write, so freeing the page IS freeing the scale
        — a refcount miscount would strand both); trace budget exactly
        the warmed 5 × 1 (scale maintenance rides inside the existing
        executables).  Chains here are quantized (within the accuracy
        band of ``generate()``, not bitwise — the parity-to-band claim
        is pinned by test_paged_serving's trained-proxy test); what
        this soak pins is accounting + trace discipline under fire."""
        model, params = self._tiny()
        server = InferenceServer(model, params, max_slots=3,
                                 block_size=8,
                                 pool_tokens=160, prefill_chunk=4,
                                 admit_headroom=0, share_prefixes=True,
                                 spec_tokens=3, kv_dtype="int8")
        plan = FaultPlan([
            FaultSpec(site="serving.step", kind="transient", every=6,
                      times=3),
            FaultSpec(site="serving.admit", kind="transient", step=4,
                      times=1),
        ])
        rng = np.random.default_rng(83)
        pref = rng.integers(0, model.cfg.vocab_size,
                            size=(16,)).astype(np.int32)
        cases = []
        for i in range(12):
            if i % 2 == 0:           # hot shared prompt, lookup-friendly
                prompt = np.concatenate([pref, rng.integers(
                    0, model.cfg.vocab_size,
                    size=(1 + i // 2,)).astype(np.int32)])
            else:                    # cold random traffic
                prompt = rng.integers(0, model.cfg.vocab_size,
                                      size=(3 + i,)).astype(np.int32)
            t, k, p = [(0.0, None, None), (0.8, 20, None),
                       (1.2, 5, 0.9)][i % 3]
            cases.append((prompt, 4 + i % 8, t, k, p, i))
        with active(plan):
            with server:
                before = tracecheck.trace_event_count()
                handles = [
                    server.submit(p, max_new_tokens=n, temperature=t,
                                  top_k=k, top_p=tp, seed=s)
                    for p, n, t, k, tp, s in cases]
                doomed = [server.submit(
                    np.concatenate([pref, np.zeros(2, np.int32)]),
                    max_new_tokens=5, deadline=1e-4)
                    for _ in range(2)]
                completed, failed, hung = 0, 0, 0
                for h in handles + doomed:
                    try:
                        toks = h.result(timeout=300)
                        completed += 1
                        assert 1 <= len(toks)
                    except RequestFailed:
                        failed += 1
                    except TimeoutError:
                        hung += 1
                health = server.health()
                after = tracecheck.trace_event_count()

        assert hung == 0
        assert completed + failed == len(cases) + len(doomed)
        assert completed >= len(cases) - 2
        assert health["status"] == "serving", health
        assert server.error is None
        assert health["kv_dtype"] == "int8"
        assert health["kv_bits"] == 8
        # every page (and with it, its scale slot) came home
        assert health["blocks_in_use"] == 0
        assert server.engine.blocks_in_use == 0
        assert server.engine.shared_blocks == 0
        assert server.engine.spec_proposed > 0
        assert after == before, "quantized chaos soak retraced"
        assert server.engine.trace_counts == {
            "decode_step": 1, "prefill_step": 1, "spec_step": 1,
            "admit": 1, "release": 1}


class TestFleetChaosSoak:
    """ISSUE-6 acceptance: a 3-replica FleetRouter under mixed
    greedy/top-p/deadline traffic survives a SIGKILL-equivalent
    replica death at midpoint — zero lost/hung requests, migrated
    greedy streams token-identical to uninterrupted ``generate()``,
    survivors leak no pages, per-replica trace budgets stay exactly 4
    executables at 1 trace each — and a graceful drain under load is
    loss-free with the drained pool back to ``blocks_in_use == 0``."""

    PAGED_BUDGET = {"decode_step": 1, "prefill_step": 1, "admit": 1,
                    "release": 1}

    @pytest.fixture(autouse=True)
    def _shardcheck(self):
        # ISSUE-16: every replica's step windows run under the strict
        # placement sanitizer for the whole storm
        shardcheck.reset()
        yield
        shardcheck.uninstrument()
        shardcheck.reset()

    def _tiny(self):
        cfg = GPTConfig.tiny(position_embedding="learned",
                             scan_layers=True)
        model = GPTModel(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
        return model, {"params": params["params"]}

    def _factory(self, model, params):
        def factory():
            # each replica is lock- AND placement-sanitized as it is
            # built — before the fleet warms/starts it, so no thread
            # can be inside a raw critical section at instrumentation
            # time (the same hook covers autoscale replacements)
            return shardcheck.instrument(lockcheck.instrument(
                InferenceServer(
                    model, params, max_slots=2,
                    block_size=8, pool_tokens=256, prefill_chunk=4),
                strict=True), strict=True)
        return factory

    def _wait_live(self, handles, min_tokens=2, timeout=180.0):
        """Block until every handle has streamed >= min_tokens (the
        kill/drain must land mid-generation, not before or after)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if all(len(h.tokens_so_far) >= min_tokens
                   for h in handles):
                return
            time.sleep(0.01)
        raise AssertionError("streams never went live")

    def _busiest(self, router):
        live = [r for r in router._replicas
                if r is not None and not r.dead and not r.draining]
        return max(live, key=lambda r: len(r.active)).index

    def test_replica_kill_zero_loss_token_identical(self):
        model, params = self._tiny()
        vocab = model.cfg.vocab_size
        router = FleetRouter(self._factory(model, params), replicas=3,
                             probe_interval=0.05)
        lockcheck.reset()
        lockcheck.instrument(router, strict=True)
        rng = np.random.default_rng(31)
        greedy_cases = [(4, 12), (7, 10), (3, 14), (6, 11), (9, 9),
                        (2, 13)]
        sampled_cases = [(5, 8, 1.0, 0.9), (8, 6, 0.8, 0.95)]
        with router:
            before = tracecheck.trace_event_count()
            greedy = []
            for i, (L, n) in enumerate(greedy_cases):
                p = rng.integers(0, vocab, size=(L,)).astype(np.int32)
                greedy.append((p, n, router.submit(
                    p, max_new_tokens=n, seed=i)))
            sampled = [router.submit(
                rng.integers(0, vocab, size=(L,)).astype(np.int32),
                max_new_tokens=n, temperature=t, top_p=tp,
                seed=100 + i)
                for i, (L, n, t, tp) in enumerate(sampled_cases)]
            doomed = [router.submit(np.zeros(3, np.int32),
                                    max_new_tokens=5, deadline=1e-4)
                      for _ in range(2)]
            # midpoint: every greedy stream live, then kill the
            # busiest replica (SIGKILL-equivalent: worker dies, engine
            # state abandoned, nothing released)
            self._wait_live([h for _, _, h in greedy])
            victim = self._busiest(router)
            assert router._replicas[victim].active, \
                "kill must land on live streams"
            router.kill_replica(victim)

            completed, failed, hung = 0, 0, 0
            for h in ([h for _, _, h in greedy] + sampled + doomed):
                try:
                    toks = h.result(timeout=300)
                    completed += 1
                    assert len(toks) >= 1
                except RequestFailed:
                    failed += 1
                except TimeoutError:
                    hung += 1
            stats = router.stats()
            health = router.health()
            after = tracecheck.trace_event_count()
            # survivors: no page leaked, budgets exactly 4 × 1
            survivors = [r for r in router._replicas
                         if r.index != victim]
            for rep in survivors:
                assert rep.server.engine.blocks_in_use == 0, rep.index
                assert rep.server.engine.trace_counts \
                    == self.PAGED_BUDGET, rep.index

        # zero lost/hung: every accepted request reached an explicit
        # terminal outcome; only the deadline-doomed pair failed
        total = len(greedy) + len(sampled) + len(doomed)
        assert hung == 0
        assert completed + failed == total
        assert completed == len(greedy) + len(sampled)
        assert failed == len(doomed)
        # the kill actually forced migrations, and they were invisible
        # to clients: greedy output token-identical to an
        # uninterrupted generate() run
        assert stats["migrated"] >= 1
        for p, n, h in greedy:
            ref = np.asarray(generate(
                model, params, jnp.asarray(p[None]),
                max_new_tokens=n))[0, len(p):]
            np.testing.assert_array_equal(
                np.asarray(h.result(timeout=1)), ref,
                err_msg=f"migrated greedy stream diverged (L={len(p)})")
        # the fleet stayed up (2 ready survivors) and the ledger
        # balances: nothing silently lost
        assert health["replicas_ready"] == 2, health
        assert stats["submitted"] == stats["completed"] \
            + stats["failed"]
        # migration replays compiled programs — no retraces anywhere
        assert after == before, "fleet kill soak retraced"
        # and the whole storm ran under the strict lock sanitizer:
        # zero order inversions, zero unguarded guarded-field touches
        lockcheck.assert_clean()
        # ... and the placement sanitizer saw every replica decode
        # (single-chip fleet: transfer-window accounting) — clean
        shardcheck.assert_clean()
        assert shardcheck.site_shardings()[
            "PagedEngine._decode"]["calls"] >= 1

    def test_drain_under_load_is_loss_free(self):
        model, params = self._tiny()
        vocab = model.cfg.vocab_size
        router = FleetRouter(self._factory(model, params), replicas=2,
                             probe_interval=0.05)
        lockcheck.reset()
        lockcheck.instrument(router, strict=True)
        rng = np.random.default_rng(37)
        cases = [(4, 10), (6, 9), (3, 12), (8, 8), (5, 11)]
        with router:
            handles = []
            for i, (L, n) in enumerate(cases):
                p = rng.integers(0, vocab, size=(L,)).astype(np.int32)
                handles.append((p, n, router.submit(
                    p, max_new_tokens=n, seed=i)))
            self._wait_live([h for _, _, h in handles])
            victim = self._busiest(router)
            drained = router.drain(victim)
            # the drained replica released everything and is detached
            assert drained.engine.blocks_in_use == 0
            assert drained.health()["status"] == "stopped"
            assert drained.health()["draining"] is True
            assert drained.engine.trace_counts == self.PAGED_BUDGET
            # every active tenant finished or migrated — loss-free —
            # and greedy output is still token-identical
            for p, n, h in handles:
                ref = np.asarray(generate(
                    model, params, jnp.asarray(p[None]),
                    max_new_tokens=n))[0, len(p):]
                np.testing.assert_array_equal(
                    np.asarray(h.result(timeout=300)), ref)
            stats = router.stats()
            assert stats["migrated"] >= 1
            assert stats["failed"] == 0
            assert stats["completed"] == len(handles)
            # scale back up through the factory and keep serving: the
            # scale hooks ride the same drain/start machinery
            assert router.scale_up() is not None
            p = rng.integers(0, vocab, size=(5,)).astype(np.int32)
            h = router.submit(p, max_new_tokens=4)
            assert len(h.result(timeout=300)) == 4
            # the surviving + fresh replicas hold the exact budget and
            # a clean pool once everything finished
            for rep in router._replicas:
                if rep.dead:
                    continue
                assert rep.server.engine.blocks_in_use == 0
                assert rep.server.engine.trace_counts \
                    == self.PAGED_BUDGET
        # drain + scale-up ran under the strict lock AND placement
        # sanitizers too (the scale-up replica enters pre-wrapped
        # through the factory)
        lockcheck.assert_clean()
        shardcheck.assert_clean()


class TestTPFleetChaosSoak:
    """ISSUE-13 acceptance: a fleet with a TENSOR-PARALLEL replica in
    the pool (replica spanning 2 chips, KV pool sharded on kv_heads)
    survives a SIGKILL-equivalent death of exactly that replica under
    mixed greedy/sampled/deadline traffic — zero lost/hung requests,
    its tenants migrate onto the single-chip survivors with greedy
    output token-identical to uninterrupted ``generate()`` (mesh
    shapes are a per-replica detail: migration re-prefills from the
    streamed prefix, so heterogeneous layouts interoperate), and the
    survivors' pools drain to ``blocks_in_use == 0`` at the exact
    4×1 budget."""

    PAGED_BUDGET = {"decode_step": 1, "prefill_step": 1, "admit": 1,
                    "release": 1}

    @pytest.fixture(autouse=True)
    def _shardcheck(self):
        # ISSUE-16: the ONE soak where the declared-placement arm of
        # the sanitizer is live — the TP replica has a committed mesh,
        # so its pool/state output shardings are verified every step
        shardcheck.reset()
        yield
        shardcheck.uninstrument()
        shardcheck.reset()

    def test_tp_replica_kill_zero_loss_token_identical(self):
        cfg = GPTConfig.tiny(position_embedding="learned",
                             scan_layers=True)
        model = GPTModel(cfg)
        params = {"params": model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 4), jnp.int32))["params"]}
        vocab = cfg.vocab_size
        import itertools

        built = itertools.count()

        def factory():
            # the FIRST replica spans 2 chips; later builds (and any
            # autoscale replacement) are single-chip — a mixed-layout
            # fleet is the realistic mid-migration state
            i = next(built)
            mesh = tp_mesh(2, jax.devices()[:2]) if i == 0 else None
            return shardcheck.instrument(lockcheck.instrument(
                InferenceServer(
                    model, params, max_slots=2,
                    block_size=8, pool_tokens=256, prefill_chunk=4,
                    mesh=mesh), strict=True), strict=True)

        router = FleetRouter(factory, replicas=3, probe_interval=0.05)
        lockcheck.reset()
        lockcheck.instrument(router, strict=True)
        rng = np.random.default_rng(41)
        # budgets long enough that NOTHING completes before the kill
        # lands — the TP replica must lose live mid-stream tenants,
        # or the migration assertion below is vacuous
        greedy_cases = [(4, 28), (7, 26), (3, 30), (6, 27), (9, 25),
                        (2, 29)]
        with router:
            # the TP replica is identifiable by its chips gauge — and
            # the fleet health must already merge it
            merged = router.health()
            assert merged["chips_per_replica"] == 2
            assert merged["chips_total"] == 4         # 2 + 1 + 1
            tp_index = next(
                r.index for r in router._replicas
                if r is not None and not r.dead
                and r.server.health()["chips_per_replica"] == 2)
            before = tracecheck.trace_event_count()
            greedy = []
            for i, (L, n) in enumerate(greedy_cases):
                p = rng.integers(0, vocab, size=(L,)).astype(np.int32)
                greedy.append((p, n, router.submit(
                    p, max_new_tokens=n, seed=i)))
            sampled = [router.submit(
                rng.integers(0, vocab, size=(6,)).astype(np.int32),
                max_new_tokens=18, temperature=0.9, top_p=0.9,
                seed=100 + i) for i in range(2)]
            doomed = [router.submit(np.zeros(3, np.int32),
                                    max_new_tokens=5, deadline=1e-4)]
            # midpoint: streams live AND the TP replica is actually
            # serving someone (the kill must cost it tenants)
            deadline = time.monotonic() + 180.0
            while time.monotonic() < deadline:
                live = all(len(h.tokens_so_far) >= 2
                           for _, _, h in greedy)
                if live and router._replicas[tp_index].active:
                    break
                time.sleep(0.01)
            assert router._replicas[tp_index].active, \
                "TP replica never took traffic — kill would be vacuous"
            router.kill_replica(tp_index)

            completed, failed, hung = 0, 0, 0
            for h in ([h for _, _, h in greedy] + sampled + doomed):
                try:
                    toks = h.result(timeout=300)
                    completed += 1
                    assert len(toks) >= 1
                except RequestFailed:
                    failed += 1
                except TimeoutError:
                    hung += 1
            stats = router.stats()
            after = tracecheck.trace_event_count()
            survivors = [r for r in router._replicas
                         if r.index != tp_index]
            for rep in survivors:
                assert rep.server.engine.blocks_in_use == 0, rep.index
                assert rep.server.engine.trace_counts \
                    == self.PAGED_BUDGET, rep.index
                assert rep.server.engine.chips_per_replica == 1

        total = len(greedy) + len(sampled) + len(doomed)
        assert hung == 0
        assert completed + failed == total
        assert completed == len(greedy) + len(sampled)
        assert failed == len(doomed)
        # the TP replica's death forced real migrations — and the
        # clients never noticed: greedy chains == generate()
        assert stats["migrated"] >= 1
        for p, n, h in greedy:
            ref = np.asarray(generate(
                model, params, jnp.asarray(p[None]),
                max_new_tokens=n))[0, len(p):]
            np.testing.assert_array_equal(
                np.asarray(h.result(timeout=1)), ref,
                err_msg=f"migrated greedy stream diverged "
                        f"(L={len(p)})")
        assert after == before, "TP fleet kill soak retraced"
        lockcheck.assert_clean()
        # placement oracle, declared arm live: the TP replica's
        # sharded pool + replicated state were compared leaf-by-leaf
        # against the committed layout on every step it served before
        # the kill — real comparisons (checked > 0), zero mismatches
        shardcheck.assert_clean()
        sites = shardcheck.site_shardings()
        tp_checked = sum(
            sites.get(f"PagedEngine.{s}", {}).get("checked", 0)
            for s in ("_decode", "_prefill", "_admit", "_release"))
        assert tp_checked > 0, \
            "TP replica served traffic but nothing was checked"


class TestPipelineKillAndResumeTrajectory:
    """ISSUE-20 chaos arm: the kill-and-resume soak on the COMPOSED
    dp × pipe 1F1B step with stage-local ZeRO-2.  Checkpoint mid-run,
    kill via an injected preemption, restore onto the
    ``pipeline_state_shardings`` placement (stage-stacked params on
    ``pipe``, masters/moments stage-local over ``data``), and the
    spliced trajectory must match the uninterrupted run.  The step is
    wrapped by the runtime placement sanitizer throughout, and the
    whole soak — reference, killed run, resumed run — holds exactly
    ONE trace of the 1F1B body (the declared retrace budget: the
    schedule is a single shape-keyed executable)."""

    STEPS = 40
    HID, DP, PP, M, MB = 16, 2, 2, 4, 2
    LAYERS = 4
    CKPT_EVERY = 8

    @pytest.fixture(autouse=True)
    def _sanitizers_strict(self):
        numcheck.reset()
        numcheck.instrument(strict=True)
        shardcheck.reset()
        yield
        shardcheck.uninstrument()
        shardcheck.reset()
        numcheck.uninstrument()
        numcheck.reset()

    def _make(self):
        from jax.sharding import Mesh, PartitionSpec as P

        from apex_tpu.parallel import ZeroConfig
        from apex_tpu.parallel import pipeline as pl

        r = np.random.default_rng(0)
        init = {"stages": (
            jnp.asarray(r.normal(size=(self.LAYERS, self.HID,
                                       self.HID)) * 0.3, jnp.float32),
            jnp.asarray(r.normal(size=(self.LAYERS, self.HID)) * 0.1,
                        jnp.float32),
            jnp.asarray(r.normal(size=(self.LAYERS, self.HID,
                                       self.HID)) * 0.3, jnp.float32),
        )}
        xs = jnp.asarray(
            r.normal(size=(4, self.DP * self.M, self.MB, self.HID)),
            jnp.float32)
        ys = jnp.asarray(
            r.normal(size=(4, self.DP * self.M, self.MB, self.HID)),
            jnp.float32)
        mesh = Mesh(np.array(jax.devices()[:self.DP * self.PP])
                    .reshape(self.DP, self.PP), ("data", "pipe"))
        tx = fused_adam(1e-2)   # ONE transform: shared static treedef

        def make_state():
            staged = {"stages": pl.stage_split(init["stages"],
                                               self.PP)}
            state = amp.initialize(
                None, staged, tx, opt_level="O0",
                zero=ZeroConfig(axis="data", axis_size=self.DP,
                                stage=2))
            state = pl.stage_local_zero(state, num_stages=self.PP)
            # committed stage placement — doubles as the
            # checkpoint-restore target
            return jax.device_put(
                state, pl.pipeline_state_shardings(state, mesh=mesh))

        def layer_apply(x, args):
            w1, b1, w2 = args
            h = jnp.tanh(x @ w1 + b1)
            return x + h @ w2, None

        def stage_fn(params, x):
            x, _ = jax.lax.scan(layer_apply, x, params)
            return x

        traces = [0]

        def body(state, mbs, labels):
            traces[0] += 1

            def loss_fn(out, i):
                yl = jax.lax.dynamic_index_in_dim(labels, i, 0,
                                                  keepdims=False)
                return jnp.mean((out - yl) ** 2)

            loss, grads = pl.run_1f1b(stage_fn, loss_fn,
                                      state.params["stages"], mbs)
            grads = pl.sync_grad_overflow({"stages": grads})
            new_state, _ = state.apply_gradients(grads=grads)
            return new_state, jax.lax.pmean(loss, "data")

        state0 = make_state()
        # donate=False: the checkpointer's async save may still be
        # reading the state buffers when the next step runs
        step = pl.wrap_pipeline_step(
            body, state=state0, mesh=mesh,
            batch_specs=(P("data"), P("data")), donate=False)

        # runtime placement oracle (ISSUE-16): the declared pipeline
        # layout — stage-stacked params on pipe, stage-local masters
        # on (pipe, data), replicated pmean'd loss — verified against
        # every compiled step's actual outputs
        declared = (pl.pipeline_state_shardings(state0, mesh=mesh),
                    jax.sharding.NamedSharding(mesh, P()))
        step = shardcheck.wrap_step(step, declared=declared,
                                    mesh=mesh,
                                    name="pipeline.train_step",
                                    strict=True)

        def loop_step(state, batch):
            state, loss = step(state, batch[0], batch[1])
            return state, {"loss": loss}

        def data_fn(i):
            return (xs[i % 4], ys[i % 4])

        return make_state, step, loop_step, data_fn, traces

    def _rows(self, writer):
        return {s: r["loss"] for s, r in writer.history}

    def test_pipeline_preempt_resume_matches_uninterrupted(
            self, tmp_path):
        make_state, step, loop_step, data_fn, traces = self._make()

        # ------------------------- the uninterrupted reference run
        state = make_state()
        ref = []
        for i in range(self.STEPS):
            x, y = data_fn(i)
            state, loss = step(state, x, y)
            ref.append(float(loss))
        assert np.all(np.isfinite(ref))
        assert ref[-1] < ref[0]

        # ------------------- run 1: killed by injected preemption
        ckpt_dir = str(tmp_path / "ckpts")
        kill_at = 17
        writer1 = MetricsWriter(sink=lambda s, m: None)
        loop1 = ResilientLoop(
            loop_step,
            checkpointer=ResilientCheckpointer(ckpt_dir, keep=3),
            checkpoint_every=self.CKPT_EVERY,
            scalars_of=lambda aux: {"loss": aux["loss"]},
            metrics=writer1)
        plan = FaultPlan([FaultSpec(site="train.step", kind="preempt",
                                    step=kill_at, times=1)])
        with active(plan):
            _carry, report1 = loop1.run(make_state(), data_fn,
                                        self.STEPS)
        assert report1.preempted
        assert report1.final_step == kill_at

        # ------------------- run 2: auto-resume onto the STAGE
        # placement (the target is the pipeline_state_shardings-
        # placed state)
        writer2 = MetricsWriter(sink=lambda s, m: None)
        loop2 = ResilientLoop(
            loop_step,
            checkpointer=ResilientCheckpointer(ckpt_dir, keep=3),
            checkpoint_every=self.CKPT_EVERY,
            scalars_of=lambda aux: {"loss": aux["loss"]},
            metrics=writer2)
        carry2, report2 = loop2.run(make_state(), data_fn, self.STEPS)
        assert report2.resumed_from == kill_at
        assert report2.final_step == self.STEPS
        assert not report2.preempted

        # stage-local masters came back ON their (pipe, data) rows:
        # each chip holds one stage's one data-shard
        for leaf in jax.tree.leaves(carry2.opt_state.master):
            assert tuple(leaf.sharding.spec)[:2] == ("pipe", "data")
            assert leaf.sharding.shard_shape(leaf.shape)[:2] == (1, 1)
            assert leaf.dtype == jnp.float32

        # ------------------------- the spliced trajectory matches
        rows1, rows2 = self._rows(writer1), self._rows(writer2)
        spliced = [rows1[i] if i <= report2.resumed_from else rows2[i]
                   for i in range(1, self.STEPS + 1)]
        np.testing.assert_allclose(
            spliced, ref, rtol=0, atol=1e-5,
            err_msg="pipelined resume diverged from uninterrupted")

        # ------------------- the oracles: numerics clean, placement
        # clean, and the whole soak held ONE trace of the 1F1B body
        jax.effects_barrier()
        numcheck.assert_clean()
        shardcheck.assert_clean()
        psite = shardcheck.site_shardings()["pipeline.train_step"]
        assert psite["checked"] > 0
        assert psite["mismatched"] == 0
        assert traces[0] == 1, (
            f"1F1B body traced {traces[0]} times across the soak — "
            f"the declared budget is ONE shape-keyed executable")
