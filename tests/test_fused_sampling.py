"""ops.fused_sampling — the one-pass fused decode-step epilogue.

Contracts under test (ISSUE 14):

- the XLA reference (the engines' ``sample_dynamic`` target) is
  BITWISE the historical sort-based composition — the ``lax.cond``
  sort short-circuit added for all-greedy / plain-temperature steps
  must be invisible in the tokens on either side of its predicate;
- the Pallas kernel (interpret mode — hermetic on CPU) is
  token-identical to the reference across the whole parameter grid:
  greedy / temperature-only / top-k / top-p / combined / disabled
  filters, bf16 logits, every vocab tile, ragged row counts (the
  row-block padding path), and the spec-step width axis (``1 + K``
  positions per row, per-position keys);
- the in-kernel Gumbel field replays jax's threefry-2x32 PRNG
  bit-for-bit (the key-for-key chain-identity guarantee rests on it —
  a jax PRNG change must fail HERE, loudly, not as a silent sampling
  drift in serving);
- the serving engines ride the fused epilogue at the unchanged 5×1
  executable budget with zero steady-state retraces, sampled chains
  stay identical between the spec (width-axis) and plain decode
  paths under eos/budget truncation, and the vocab-tile autotune
  winner is adopted through ``fused_sample(block_v=0)``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.ops import autotune
from apex_tpu.ops.fused_sampling import (
    fused_sample,
    fused_sample_reference,
    sampling_cost_bytes,
)
from apex_tpu.serving import PagedEngine
from apex_tpu.serving.engine import sample_dynamic
from apex_tpu.utils import tracecheck

V = 512                       # % 128 == 0: inside the kernel envelope
R = 13                        # not a row-block multiple: padding path


def _legacy_sample_dynamic(logits, keys, temperature, top_k, top_p,
                           vocab_size):
    """The pre-fusion ``sample_dynamic`` body, verbatim — the golden
    pin the refactored reference must reproduce bit-for-bit."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / safe_t
    k = jnp.where(top_k > 0, top_k, vocab_size)
    ordered = jnp.sort(scaled, axis=-1)
    kth = jnp.take_along_axis(
        ordered, (vocab_size - k)[:, None], axis=-1)
    scaled = jnp.where(scaled < kth, -1e30, scaled)
    p_on = (top_p > 0.0) & (top_p < 1.0)
    rev = ordered[:, ::-1]
    desc = jnp.where(rev < kth, -1e30, rev)
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = cum - probs < jnp.where(p_on, top_p, 1.0)[:, None]
    thresh = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                     keepdims=True)
    scaled = jnp.where(p_on[:, None] & (scaled < thresh), -1e30,
                       scaled)
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    return jnp.where(temperature > 0.0, sampled.astype(jnp.int32),
                     greedy)


def _grid_case(rng, r=R, v=V):
    logits = jnp.asarray(rng.normal(size=(r, v)) * 3, jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(
        jnp.asarray(rng.integers(0, 2**31, r), jnp.uint32))
    temp = jnp.asarray(rng.choice([0.0, 0.3, 0.7, 1.0, 1.5], r),
                       jnp.float32)
    tk = jnp.asarray(rng.choice([0, 1, 5, 40, v], r), jnp.int32)
    tp = jnp.asarray(rng.choice([0.0, 0.1, 0.5, 0.9, 0.99, 1.0], r),
                     jnp.float32)
    return logits, keys, temp, tk, tp


class TestReferenceIsLegacySampler:
    """The cond-gated reference == the historical sort-based math."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_mixed_grid_bitwise(self, seed):
        rng = np.random.default_rng(seed)
        logits, keys, temp, tk, tp = _grid_case(rng)
        ref = _legacy_sample_dynamic(logits, keys, temp, tk, tp, V)
        got = fused_sample_reference(logits, keys, temp, tk, tp, V)
        assert jnp.array_equal(ref, got)
        # serving's sample_dynamic delegates here
        assert jnp.array_equal(
            ref, sample_dynamic(logits, keys, temp, tk, tp, V))

    def test_short_circuit_side_is_exact(self):
        """All filters disabled — the cond takes the sort-free branch
        (top_k == 0 everywhere, top_p disabled both ways) and must
        still be bitwise the full legacy path."""
        rng = np.random.default_rng(7)
        logits = jnp.asarray(rng.normal(size=(R, V)), jnp.float32)
        keys = jax.vmap(jax.random.PRNGKey)(
            jnp.asarray(rng.integers(0, 2**31, R), jnp.uint32))
        temp = jnp.asarray(rng.choice([0.0, 0.7, 1.3], R), jnp.float32)
        zeros = jnp.zeros((R,), jnp.int32)
        for tp_off in (jnp.zeros((R,), jnp.float32),
                       jnp.ones((R,), jnp.float32)):
            ref = _legacy_sample_dynamic(logits, keys, temp, zeros,
                                         tp_off, V)
            got = fused_sample_reference(logits, keys, temp, zeros,
                                         tp_off, V)
            assert jnp.array_equal(ref, got)

    def test_top_k_equal_vocab_is_filter_branch_noop(self):
        """top_k == vocab crosses the predicate (filters branch) but
        masks nothing — exactness of the disabled-filter contract on
        the OTHER side of the cond."""
        rng = np.random.default_rng(9)
        logits = jnp.asarray(rng.normal(size=(R, V)), jnp.float32)
        keys = jax.vmap(jax.random.PRNGKey)(
            jnp.asarray(rng.integers(0, 2**31, R), jnp.uint32))
        temp = jnp.full((R,), 0.9, jnp.float32)
        full_k = jnp.full((R,), V, jnp.int32)
        tp = jnp.zeros((R,), jnp.float32)
        ref = _legacy_sample_dynamic(logits, keys, temp,
                                     jnp.zeros((R,), jnp.int32), tp, V)
        got = fused_sample_reference(logits, keys, temp, full_k, tp, V)
        assert jnp.array_equal(ref, got)


class TestKernelGoldenParity:
    """Interpret-mode kernel vs reference, token for token."""

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    @pytest.mark.parametrize("block_v", [V, 128])
    def test_mixed_grid(self, seed, block_v):
        rng = np.random.default_rng(seed)
        logits, keys, temp, tk, tp = _grid_case(rng)
        ref = fused_sample_reference(logits, keys, temp, tk, tp, V)
        got = fused_sample(logits, keys, temp, tk, tp,
                           implementation="pallas_interpret",
                           block_v=block_v)
        assert jnp.array_equal(ref, got)

    def test_bf16_logits(self):
        rng = np.random.default_rng(5)
        logits, keys, temp, tk, tp = _grid_case(rng)
        lb = logits.astype(jnp.bfloat16)
        ref = fused_sample_reference(lb, keys, temp, tk, tp, V)
        got = fused_sample(lb, keys, temp, tk, tp,
                           implementation="pallas_interpret",
                           block_v=256)
        assert jnp.array_equal(ref, got)

    def test_single_row_and_tiny_batch(self):
        rng = np.random.default_rng(6)
        for r in (1, 2):
            logits, keys, temp, tk, tp = _grid_case(rng, r=r)
            ref = fused_sample_reference(logits, keys, temp, tk, tp, V)
            got = fused_sample(logits, keys, temp, tk, tp,
                               implementation="pallas_interpret")
            assert jnp.array_equal(ref, got)

    @pytest.mark.parametrize("w", [2, 4])
    def test_width_axis_matches_per_position_loop(self, w):
        """The spec-step form: (rows, w, vocab) + per-position keys in
        ONE call == w separate sample_dynamic passes."""
        rng = np.random.default_rng(8)
        logits = jnp.asarray(rng.normal(size=(R, w, V)) * 3,
                             jnp.float32)
        keys = jnp.stack(
            [jax.vmap(jax.random.PRNGKey)(
                jnp.asarray(rng.integers(0, 2**31, R), jnp.uint32))
             for _ in range(w)], axis=1)
        _, _, temp, tk, tp = _grid_case(rng)
        ref = jnp.stack(
            [_legacy_sample_dynamic(logits[:, j], keys[:, j], temp,
                                    tk, tp, V) for j in range(w)],
            axis=1)
        for impl in ("xla", "pallas_interpret"):
            got = fused_sample(logits, keys, temp, tk, tp,
                               implementation=impl, block_v=128)
            assert jnp.array_equal(ref, got), impl

    def test_greedy_rows_are_pure_argmax(self):
        """temperature <= 0 == fp32 argmax — the generate() parity
        anchor (same argmax the static sample_logits path takes)."""
        rng = np.random.default_rng(4)
        logits = jnp.asarray(rng.normal(size=(R, V)), jnp.float32)
        keys = jax.vmap(jax.random.PRNGKey)(jnp.zeros(R, jnp.uint32))
        zt = jnp.zeros((R,), jnp.float32)
        zk = jnp.zeros((R,), jnp.int32)
        got = fused_sample(logits, keys, zt, zk, zt,
                           implementation="pallas_interpret")
        assert jnp.array_equal(got, jnp.argmax(logits, axis=-1))

    def test_validation(self):
        rng = np.random.default_rng(0)
        logits, keys, temp, tk, tp = _grid_case(rng)
        with pytest.raises(ValueError, match="keys shape"):
            fused_sample(logits, keys[:-1], temp, tk, tp)
        with pytest.raises(ValueError, match="vocab_size"):
            fused_sample(logits, keys, temp, tk, tp, vocab_size=V + 1)
        with pytest.raises(ValueError, match="temperature shape"):
            fused_sample(logits, keys, temp[:-1], tk, tp)
        with pytest.raises(ValueError, match="logits must be"):
            fused_sample(logits[0], keys, temp, tk, tp)

    def test_unaligned_vocab_falls_back_to_reference(self):
        """V % 128 != 0 is outside the kernel envelope: auto must
        resolve to the reference, not crash."""
        rng = np.random.default_rng(2)
        logits, keys, temp, tk, tp = _grid_case(rng, v=300)
        ref = fused_sample_reference(logits, keys, temp, tk, tp, 300)
        got = fused_sample(logits, keys, temp, tk, tp)
        assert jnp.array_equal(ref, got)


class TestThreefryReplay:
    """The kernel's Gumbel field == jax.random's, bit for bit.  If a
    jax upgrade changes the default PRNG layout this fails loudly —
    the serving chain-identity contract depends on it."""

    def test_gumbel_bits_match(self):
        from apex_tpu.ops.fused_sampling import _gumbel
        keys = jax.vmap(jax.random.PRNGKey)(
            jnp.arange(5, dtype=jnp.uint32) * 13 + 1)
        pos = jnp.arange(V, dtype=jnp.uint32)[None, :]
        mine = _gumbel(keys[:, 0:1], keys[:, 1:2], pos)
        ref = jax.vmap(
            lambda k: jax.random.gumbel(k, (V,), jnp.float32))(keys)
        assert jnp.array_equal(mine, ref), (
            "jax's threefry/gumbel layout changed — the fused sampling "
            "kernel's key-for-key chain identity no longer holds; "
            "update _sampling_kernel's pass 5 to the new layout")

    def test_categorical_decision_matches(self):
        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.normal(size=(4, V)), jnp.float32)
        keys = jax.vmap(jax.random.PRNGKey)(
            jnp.asarray(rng.integers(0, 2**31, 4), jnp.uint32))
        temp = jnp.ones((4,), jnp.float32)
        zk = jnp.zeros((4,), jnp.int32)
        zp = jnp.zeros((4,), jnp.float32)
        got = fused_sample(logits, keys, temp, zk, zp,
                           implementation="pallas_interpret")
        ref = jax.vmap(jax.random.categorical)(keys, logits)
        assert jnp.array_equal(got, ref.astype(jnp.int32))


class TestAutotuneAdoption:
    def test_cached_tile_adopted_by_block_v_zero(self, monkeypatch):
        """fused_sample(block_v=0) queries the (vocab, width) winner —
        the engine-side adoption path (the engines always pass 0)."""
        calls = []
        real = autotune.cached_sampling_tile

        def spy(vocab, width):
            calls.append((vocab, width))
            return 128

        monkeypatch.setattr(autotune, "cached_sampling_tile", spy)
        rng = np.random.default_rng(3)
        logits, keys, temp, tk, tp = _grid_case(rng)
        got = fused_sample(logits, keys, temp, tk, tp,
                           implementation="pallas_interpret",
                           block_v=0)
        assert calls == [(V, 1)]
        monkeypatch.setattr(autotune, "cached_sampling_tile", real)
        ref = fused_sample(logits, keys, temp, tk, tp,
                           implementation="pallas_interpret",
                           block_v=128)
        assert jnp.array_equal(ref, got)

    def test_tune_fused_sampling_writes_width_qualified_keys(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("APEX_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "at.json"))
        autotune.clear_cache()
        try:
            best = autotune.tune_fused_sampling(
                n_rows=4, width=256, sample_width=1,
                candidates=(128, 256),
                implementation="pallas_interpret")
            assert best in (128, 256)
            assert autotune.cached_sampling_tile(256, 1) == best
            # width-qualified: the spec step's entry is separate
            assert autotune.cached_sampling_tile(256, 3) is None
            assert autotune.cached_sampling_tile(512, 1) is None
        finally:
            autotune.clear_cache()

    def test_cost_model_is_one_pass(self):
        """The declared kernel traffic ~ one logits read: the analytic
        number the decode_epilogue bench leg reports."""
        got = sampling_cost_bytes(8, V, jnp.float32)
        assert 8 * V * 4 <= got <= 8 * V * 4 + 8 * 64
        assert sampling_cost_bytes(8, V, jnp.bfloat16) < got


def _tiny_gpt():
    cfg = GPTConfig.tiny(position_embedding="learned",
                         scan_layers=True)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return model, {"params": params["params"]}


@pytest.fixture(scope="module")
def gpt():
    return _tiny_gpt()


class TestEngineFusedEpilogue:
    """Engine-level acceptance: the fused epilogue rides the serving
    engines at the unchanged 5×1 executable budget, and the spec
    step's width-axis sampling keeps chains identical to plain decode
    under eos/budget truncation."""

    def test_spec_chain_identical_to_plain_decode_with_eos(self, gpt):
        """The width-axis call's eos/budget interaction: a drafted
        engine (forced drafts) and an undrafted engine must emit
        IDENTICAL sampled chains for the same seeds — eos and budget
        truncation included (acceptance-invariance rides the same
        sequential key chain the fused width call consumes)."""
        model, params = gpt
        prompt = np.asarray([5, 9, 2, 9, 2, 9], np.int32)

        def run(spec):
            eng = PagedEngine(model, params, max_slots=2,
                              block_size=8, prefill_chunk=4,
                              spec_tokens=(3 if spec else 0))
            if spec:
                eng._drafter = lambda context, k, ngram: np.zeros(
                    (k,), np.int32)
            eng.admit(0, prompt, max_new_tokens=8, temperature=0.9,
                      top_k=7, top_p=0.9, eos_id=3, seed=123)
            out = []
            for _ in range(40):
                step = eng.step()
                n = int(step.counts[0])
                out.extend(int(t) for t in step.tokens[0, :n])
                if step.finished[0]:
                    break
                if eng._tenants[0] is None:
                    break
            eng.release(0)
            return out

        assert run(spec=True) == run(spec=False)

    def test_zero_retrace_soak_at_5x1_budget(self, gpt):
        """The trace-budget acceptance: mixed greedy/temp/top-k/top-p
        traffic + drafting through the fused epilogue — FIVE
        executables × 1 trace, zero steady-state retraces."""
        model, params = gpt
        eng = PagedEngine(model, params, max_slots=3, block_size=8,
                          prefill_chunk=4, spec_tokens=2)
        eng.warmup()
        budget = {"decode_step": 1, "prefill_step": 1, "spec_step": 1,
                  "admit": 1, "release": 1}
        assert eng.trace_counts == budget
        before = tracecheck.trace_event_count()
        rng = np.random.default_rng(0)
        cases = [dict(temperature=0.0),
                 dict(temperature=0.8),
                 dict(temperature=0.9, top_k=5),
                 dict(temperature=1.1, top_p=0.9),
                 dict(temperature=0.7, top_k=9, top_p=0.8)]
        slot_live = {}
        seq = 0
        for it in range(25):
            for slot in range(3):
                if slot_live.get(slot) is None and seq < len(cases) * 2:
                    kw = cases[seq % len(cases)]
                    plen = int(rng.integers(2, 9))
                    eng.admit(slot,
                              rng.integers(1, 40, plen).astype(np.int32),
                              max_new_tokens=int(rng.integers(2, 6)),
                              seed=seq, **kw)
                    slot_live[slot] = True
                    seq += 1
            if not any(slot_live.values()):
                break
            out = eng.step()
            for slot in range(3):
                if slot_live.get(slot) and (
                        bool(out.finished[slot])
                        or eng._tenants[slot] is None):
                    if eng._tenants[slot] is not None:
                        eng.release(slot)
                    slot_live[slot] = False
        assert tracecheck.trace_event_count() == before, (
            "fused-epilogue soak retraced after warmup")
        assert eng.trace_counts == budget


class TestReviewRegressions:
    """Pinned repros from the ISSUE-14 review pass."""

    def test_greedy_argmax_survives_temperature_scale_collision(self):
        """A greedy row's /1e-6 temperature scaling is monotone but
        NOT injective: two adjacent fp32 logits can collide into one
        scaled value, and an argmax taken on the SCALED row would
        flip to the earlier index.  The kernel must argmax the raw
        fp32 logits, like the reference."""
        a = np.float32(1.5611286e-06)
        b = np.nextafter(a, np.float32(1.0))       # adjacent, larger
        assert b > a
        assert np.float32(a / np.float32(1e-6)) == \
            np.float32(b / np.float32(1e-6)), "repro precondition"
        row = np.full((V,), -50.0, np.float32)
        row[5] = a                                  # earlier, smaller
        row[90] = b                                 # later, the argmax
        logits = jnp.asarray(row)[None, :]
        keys = jax.vmap(jax.random.PRNGKey)(jnp.zeros(1, jnp.uint32))
        z = jnp.zeros((1,), jnp.float32)
        got = fused_sample(logits, keys, z, jnp.zeros((1,), jnp.int32),
                           z, implementation="pallas_interpret")
        assert int(got[0]) == 90
        ref = fused_sample_reference(logits, keys, z,
                                     jnp.zeros((1,), jnp.int32), z, V)
        assert int(ref[0]) == 90

    def test_released_slots_filter_params_are_masked(self):
        """``release_slot`` only clears the active bit — the engines
        must neutralize a released slot's stale top_k/top_p before the
        epilogue call, or the runtime sort short-circuit never fires
        again after the first sampled tenant."""
        from apex_tpu.serving import cache as slot_cache
        from apex_tpu.serving.engine import _active_sampling_params

        state = slot_cache.init_slot_state(3)
        state = slot_cache.admit_slot(
            state, jnp.int32(1), jnp.int32(7), jnp.int32(4),
            jnp.float32(0.9), jnp.int32(40), jnp.float32(0.9),
            jnp.int32(-1), jnp.uint32(0))
        temp, tk, tp = _active_sampling_params(state)
        assert int(tk[1]) == 40 and float(tp[1]) == pytest.approx(0.9)
        state = slot_cache.release_slot(state, jnp.int32(1))
        temp, tk, tp = _active_sampling_params(state)
        assert not bool(jnp.any(tk > 0))
        assert not bool(jnp.any((tp > 0.0) & (tp < 1.0)))

    def test_tuner_refuses_out_of_envelope_geometry(self, tmp_path,
                                                    monkeypatch):
        """An out-of-envelope sweep (vocab % 128 != 0) must cache
        NOTHING — every candidate would silently time the XLA
        reference, not the kernel."""
        monkeypatch.setenv("APEX_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "at.json"))
        autotune.clear_cache()
        try:
            best = autotune.tune_fused_sampling(
                n_rows=4, width=1000, sample_width=1,
                candidates=(128, 256),
                implementation="pallas_interpret")
            assert best is None
            assert autotune.cached_sampling_tile(1000, 1) is None
        finally:
            autotune.clear_cache()
