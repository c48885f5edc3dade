"""ops.paged_attention — block-table-gathered decode attention.

Contracts under test:

- the Pallas kernel (interpret mode — hermetic on CPU) is numerically
  identical to the XLA gather reference across decode (s=1) and chunk
  queries, GQA head ratios, ragged per-row lengths, and bf16;
- the computation depends only on the LOGICAL cache content: permuting
  the physical placement (new block tables, same logical pages) and
  poisoning every unallocated pool block with garbage must not change
  a single output bit — the position mask makes non-live pool content
  unreachable (the null-page invariant the serving engine relies on);
- the paged reference reproduces the dense cache attention of
  ``models/transformer.py`` on the same K/V (the greedy-parity anchor
  between the paged and dense serving engines);
- cost-analysis: the compiled per-step bytes of the paged path scale
  with LIVE pages while the dense cache einsum's bytes are pinned at
  ``max_seq_len`` regardless of how little of the cache is live (the
  PR-3-style bytes assertion for the serving datapath; the analytic
  model lives in ``bench_configs._serving_traffic_model``);
- quantized KV pages (ISSUE 8): the in-register-dequant Pallas kernel
  against the explicit quantize-dequant XLA reference (decode, GQA,
  ragged, spec-verify chunk, interpret mode), page+scale placement /
  pool-garbage invariance, the stated quantization-error bound vs the
  float pool, and the scale-argument validation contract;
- the in-kernel page sweep (ISSUE 30): both kernels on cursors at
  every edge of the chunked sweep (page and chunk boundaries, a full
  table, at and past ``max_seq_len``), a result that does not depend
  on the table's width, and a ``pallas_call`` grid with no page axis;
- the chunk write (ISSUE 36): ``paged_write``'s kernel against the XLA
  scatter it replaces, bit for bit on every live page, over pool
  dtypes, widths, head counts, page-crossing and null-routed lanes,
  in ONE aliased ``pallas_call`` with a body of a few dozen equations.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.ops.paged_attention import (
    kv_quant_spec,
    paged_attention,
    paged_attention_reference,
    quantize_kv_pages,
)

_KV_DTYPES = [
    "int8",
    pytest.param("fp8", marks=pytest.mark.skipif(
        not hasattr(jnp, "float8_e4m3fn"),
        reason="no float8_e4m3fn in this jax build")),
]


def _pool_setup(rng, *, b, hk, d, NB, BS, MB, lengths, s, dtype,
                cap=False):
    """Random pool + per-row tables covering ``lengths[i] + s`` tokens
    with disjoint physical blocks (block 0 left as the null page);
    ``cap`` lets a row run to the table's end and past it."""
    kp = jnp.asarray(rng.normal(size=(hk, NB, BS, d)), dtype)
    vp = jnp.asarray(rng.normal(size=(hk, NB, BS, d)), dtype)
    tables = np.zeros((b, MB), np.int32)
    free = list(range(1, NB))
    for i, L in enumerate(lengths):
        n = -(-(L + s) // BS)
        if cap:
            n = min(n, MB)
        assert n <= MB and len(free) >= n, "test pool too small"
        for j in range(n):
            tables[i, j] = free.pop()
    return kp, vp, tables


#: batches whose neighbouring rows hand the kernels' stream of chunk
#: fetches on across every kind of row boundary (pages of 8 tokens,
#: sweep chunks of 128 keys, ``max_seq_len`` 1280): rows of 1, 2 and 9
#: chunks side by side, a row at ``max_seq_len``, two rows on one
#: copy-on-write prefix (each writing a page of its own), one row alone
_HANDOFF_S = 1280
_HANDOFFS = {
    "chunks-1-2-9": dict(lengths=[100, 200, 1100, 5, 1100, 130]),
    "row-at-S": dict(lengths=[300, _HANDOFF_S, 40]),
    "cow-prefix": dict(lengths=[300, 500, 60], share=37),
    "one-row": dict(lengths=[700]),
}
#: a pad lane of a coded pool (``chunk_lens`` 0) between real rows
_PAD_ROW = dict(lengths=[300, 5, 1100, 40], pads=(1,))


def _kept_ids(argvalues, *handoffs):
    """An existing parametrisation's cases, on their own rows (batch
    ``None``) under the ids they always had, then the hand-off batches
    ``(name, args...)`` appended."""
    kept = []
    for v in argvalues:
        vals = v.values if hasattr(v, "values") else (
            v if isinstance(v, tuple) else (v,))
        kept.append(pytest.param(
            *vals, None, marks=getattr(v, "marks", ()),
            id="-".join(getattr(x, "__name__", str(x)) for x in vals)))
    return kept + [pytest.param(*args, name, id="-".join(
        [*(getattr(x, "__name__", str(x)) for x in args), name]))
        for name, *args in handoffs]


class TestGoldenKernel:
    @pytest.mark.parametrize("s,h,hk,dtype", [
        (1, 4, 4, jnp.float32),        # pure decode, MHA
        (1, 8, 2, jnp.float32),        # decode, GQA 4:1
        (4, 4, 2, jnp.float32),        # chunk queries, GQA
        (4, 4, 4, jnp.bfloat16),       # chunk, bf16
        (1, 10, 2, jnp.float32),       # decode, GQA 5:1 (Falcon-H1)
        (4, 10, 2, jnp.bfloat16),      # chunk, 5 x 4 = 20 query rows
    ])
    def test_kernel_matches_reference(self, s, h, hk, dtype):
        rng = np.random.default_rng(0)
        b, d, NB, BS, MB = 3, 32, 24, 8, 6
        lengths = [9, 0, 27]
        kp, vp, tables = _pool_setup(
            rng, b=b, hk=hk, d=d, NB=NB, BS=BS, MB=MB,
            lengths=lengths, s=s, dtype=dtype)
        q = jnp.asarray(rng.normal(size=(b, s, h, d)), dtype)
        lens = jnp.asarray(lengths, jnp.int32)
        ref = paged_attention_reference(q, kp, vp,
                                        jnp.asarray(tables), lens)
        out = paged_attention(q, kp, vp, jnp.asarray(tables), lens,
                              implementation="pallas_interpret")
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=tol, rtol=tol)

    def test_explicit_xla_matches_auto_on_cpu(self):
        rng = np.random.default_rng(1)
        b, s, h, hk, d, NB, BS, MB = 2, 1, 2, 2, 16, 10, 8, 4
        kp, vp, tables = _pool_setup(
            rng, b=b, hk=hk, d=d, NB=NB, BS=BS, MB=MB,
            lengths=[5, 11], s=s, dtype=jnp.float32)
        q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        lens = jnp.asarray([5, 11], jnp.int32)
        auto = paged_attention(q, kp, vp, jnp.asarray(tables), lens)
        xla = paged_attention(q, kp, vp, jnp.asarray(tables), lens,
                              implementation="xla")
        np.testing.assert_array_equal(np.asarray(auto),
                                      np.asarray(xla))


class TestLogicalContentOnly:
    """Outputs are a function of the logical cache, never of physical
    placement or non-live pool garbage."""

    @pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
    def test_placement_and_garbage_invariance(self, impl):
        rng = np.random.default_rng(2)
        b, s, h, hk, d, NB, BS, MB = 2, 2, 4, 2, 16, 30, 8, 5
        lengths = [10, 3]
        kp, vp, tables = _pool_setup(
            rng, b=b, hk=hk, d=d, NB=NB, BS=BS, MB=MB,
            lengths=lengths, s=s, dtype=jnp.float32)
        q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        lens = jnp.asarray(lengths, jnp.int32)
        base = paged_attention(q, kp, vp, jnp.asarray(tables), lens,
                               implementation=impl)

        # migrate every live page to a fresh physical block and poison
        # everything else (incl. the old homes and the null page)
        live = sorted({int(t) for t in tables.ravel() if t})
        dest = {blk: i + 1 for i, blk in enumerate(live)}
        assert not (set(dest.values()) & set(live))
        kp2 = np.asarray(rng.normal(size=(hk, NB, BS, d)),
                         np.float32) * 1e3
        vp2 = np.asarray(rng.normal(size=(hk, NB, BS, d)),
                         np.float32) * 1e3
        for src, dst in dest.items():
            kp2[:, dst] = np.asarray(kp[:, src])
            vp2[:, dst] = np.asarray(vp[:, src])
        tables2 = np.where(tables > 0,
                           np.vectorize(lambda t: dest.get(t, 0))(
                               tables), 0).astype(np.int32)
        moved = paged_attention(
            q, jnp.asarray(kp2), jnp.asarray(vp2),
            jnp.asarray(tables2), lens, implementation=impl)
        np.testing.assert_array_equal(np.asarray(base),
                                      np.asarray(moved))


class TestSpeculativeVerifyChunk:
    """The multi-query verify path (ISSUE 7): one ``s = 1 + k``
    application scores a draft run with per-position context identical
    to k+1 sequential one-token steps, and a REJECTED tail's stale
    K/V — live pages past a rolled-back cursor — is unreachable."""

    @pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
    def test_verify_chunk_matches_sequential_decode(self, impl):
        rng = np.random.default_rng(7)
        b, h, hk, d, NB, BS, MB, k = 2, 4, 2, 16, 24, 8, 6, 3
        lengths = [9, 17]
        kp, vp, tables = _pool_setup(
            rng, b=b, hk=hk, d=d, NB=NB, BS=BS, MB=MB,
            lengths=lengths, s=1 + k, dtype=jnp.float32)
        q = jnp.asarray(rng.normal(size=(b, 1 + k, h, d)), jnp.float32)
        lens = jnp.asarray(lengths, jnp.int32)
        chunk = paged_attention(q, kp, vp, jnp.asarray(tables), lens,
                                implementation=impl)
        # sequential: query j alone at its own position (the pool
        # already holds every draft's K/V — write-then-attend)
        for j in range(1 + k):
            one = paged_attention(
                q[:, j:j + 1], kp, vp, jnp.asarray(tables), lens + j,
                implementation=impl)
            np.testing.assert_allclose(
                np.asarray(chunk[:, j]), np.asarray(one[:, 0]),
                atol=2e-6, rtol=2e-6)

    def test_rejected_tail_garbage_is_unreachable(self):
        """Rollback contract: after the engine rejects a draft tail,
        its K/V stays in LIVE pages past the new cursor — the next
        step's queries must not see it.  Poison those positions; the
        masked output must not change a bit."""
        rng = np.random.default_rng(8)
        b, h, hk, d, NB, BS, MB = 1, 4, 2, 16, 16, 8, 4
        L = 10                     # cursor after rolling 3 drafts back
        kp, vp, tables = _pool_setup(
            rng, b=b, hk=hk, d=d, NB=NB, BS=BS, MB=MB,
            lengths=[L], s=1, dtype=jnp.float32)
        q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
        lens = jnp.asarray([L], jnp.int32)
        base = paged_attention(q, kp, vp, jnp.asarray(tables), lens,
                               implementation="xla")
        kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
        blk, off = tables[0, (L + 1) // BS], (L + 1) % BS
        kp2[:, blk, off:] = 1e3    # stale draft K/V in the live page
        vp2[:, blk, off:] = 1e3
        poisoned = paged_attention(
            q, jnp.asarray(kp2), jnp.asarray(vp2),
            jnp.asarray(tables), lens, implementation="xla")
        np.testing.assert_array_equal(np.asarray(base),
                                      np.asarray(poisoned))


class TestQuantizedKernel:
    """Quantized KV pages (ISSUE 8): int8/fp8 codes + per-(kv_head,
    page) fp32 amax scales.  The explicit quantize-dequant XLA
    reference is the parity anchor; the Pallas kernel dequantizes
    in-register (the per-page scale factors out of both contractions)
    and must agree to the same fp32-noise tolerance the unquantized
    golden suite uses — the two paths share the online-softmax
    algebra, only the dequant site differs."""

    @pytest.mark.parametrize("s,h,hk,kv_dtype,batch", _kept_ids(
        [pytest.param(*shape, kv, marks=marks) for shape in [
            (1, 4, 4),        # pure decode, MHA
            (1, 8, 2),        # decode, GQA 4:1
            (4, 4, 2),        # chunk queries (spec-verify shape), GQA
        ] for kv, marks in (("int8", ()), ("fp8", _KV_DTYPES[1].marks))],
        ("chunks-1-2-9", 1, 10, 2, "int8"), ("chunks-1-2-9", 4, 8, 2, "int8"),
        ("one-row", 1, 8, 2, "fp8")))
    def test_kernel_matches_quant_dequant_reference(self, s, h, hk,
                                                    kv_dtype, batch):
        rng = np.random.default_rng(10)
        b, d, NB, BS, MB = 3, 32, 24, 8, 6
        lengths = [9, 0, 27]
        if batch is not None:
            lengths = [min(n, _HANDOFF_S - s)
                       for n in _HANDOFFS[batch]["lengths"]]
            b, MB = len(lengths), _HANDOFF_S // BS
            NB = sum(-(-(n + s) // BS) for n in lengths) + 1
        kp, vp, tables = _pool_setup(
            rng, b=b, hk=hk, d=d, NB=NB, BS=BS, MB=MB,
            lengths=lengths, s=s, dtype=jnp.float32)
        kq, vq, ks, vs = quantize_kv_pages(kp, vp, kv_dtype)
        q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        lens = jnp.asarray(lengths, jnp.int32)
        ref = paged_attention_reference(
            q, kq, vq, jnp.asarray(tables), lens,
            k_scales=ks, v_scales=vs)
        out = paged_attention(
            q, kq, vq, jnp.asarray(tables), lens,
            k_scales=ks, v_scales=vs,
            implementation="pallas_interpret")
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("kv_dtype", _KV_DTYPES)
    def test_explicit_xla_matches_auto_on_cpu(self, kv_dtype):
        """On CPU a quantized pool auto-dispatches to the reference:
        bitwise."""
        rng = np.random.default_rng(11)
        b, s, h, hk, d, NB, BS, MB = 2, 1, 2, 2, 16, 10, 8, 4
        kp, vp, tables = _pool_setup(
            rng, b=b, hk=hk, d=d, NB=NB, BS=BS, MB=MB,
            lengths=[5, 11], s=s, dtype=jnp.float32)
        kq, vq, ks, vs = quantize_kv_pages(kp, vp, kv_dtype)
        q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        lens = jnp.asarray([5, 11], jnp.int32)
        auto = paged_attention(q, kq, vq, jnp.asarray(tables), lens,
                               k_scales=ks, v_scales=vs)
        xla = paged_attention(q, kq, vq, jnp.asarray(tables), lens,
                              k_scales=ks, v_scales=vs,
                              implementation="xla")
        np.testing.assert_array_equal(np.asarray(auto),
                                      np.asarray(xla))

    @pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
    def test_placement_and_garbage_invariance(self, impl):
        """A page's SCALE travels with it: migrating live pages (and
        their scale entries) to fresh physical blocks while poisoning
        every dead block's codes AND scales must not change one output
        bit — the invariant that lets shared/CoW/preempted quantized
        pages move without rescaling."""
        rng = np.random.default_rng(12)
        b, s, h, hk, d, NB, BS, MB = 2, 2, 4, 2, 16, 30, 8, 5
        lengths = [10, 3]
        kp, vp, tables = _pool_setup(
            rng, b=b, hk=hk, d=d, NB=NB, BS=BS, MB=MB,
            lengths=lengths, s=s, dtype=jnp.float32)
        kq, vq, ks, vs = quantize_kv_pages(kp, vp, "int8")
        q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        lens = jnp.asarray(lengths, jnp.int32)
        base = paged_attention(q, kq, vq, jnp.asarray(tables), lens,
                               k_scales=ks, v_scales=vs,
                               implementation=impl)

        live = sorted({int(t) for t in tables.ravel() if t})
        dest = {blk: i + 1 for i, blk in enumerate(live)}
        assert not (set(dest.values()) & set(live))
        kq2 = np.asarray(rng.integers(-127, 128, size=(hk, NB, BS, d)),
                         np.int8)
        vq2 = np.asarray(rng.integers(-127, 128, size=(hk, NB, BS, d)),
                         np.int8)
        ks2 = np.asarray(rng.normal(size=(hk, NB)),
                         np.float32) * 1e3            # garbage scales
        vs2 = np.asarray(rng.normal(size=(hk, NB)), np.float32) * 1e3
        for src, dst in dest.items():
            kq2[:, dst] = np.asarray(kq[:, src])
            vq2[:, dst] = np.asarray(vq[:, src])
            ks2[:, dst] = np.asarray(ks[:, src])
            vs2[:, dst] = np.asarray(vs[:, src])
        tables2 = np.where(tables > 0,
                           np.vectorize(lambda t: dest.get(t, 0))(
                               tables), 0).astype(np.int32)
        moved = paged_attention(
            q, jnp.asarray(kq2), jnp.asarray(vq2),
            jnp.asarray(tables2), lens,
            k_scales=jnp.asarray(ks2), v_scales=jnp.asarray(vs2),
            implementation=impl)
        np.testing.assert_array_equal(np.asarray(base),
                                      np.asarray(moved))

    @pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
    def test_verify_chunk_matches_sequential_decode(self, impl):
        """The spec-verify chunk (s = 1+k) rides the quantized path
        unchanged: chunk positions == k+1 sequential decode steps over
        the same quantized pool."""
        rng = np.random.default_rng(13)
        b, h, hk, d, NB, BS, MB, k = 2, 4, 2, 16, 24, 8, 6, 3
        lengths = [9, 17]
        kp, vp, tables = _pool_setup(
            rng, b=b, hk=hk, d=d, NB=NB, BS=BS, MB=MB,
            lengths=lengths, s=1 + k, dtype=jnp.float32)
        kq, vq, ks, vs = quantize_kv_pages(kp, vp, "int8")
        q = jnp.asarray(rng.normal(size=(b, 1 + k, h, d)), jnp.float32)
        lens = jnp.asarray(lengths, jnp.int32)
        chunk = paged_attention(q, kq, vq, jnp.asarray(tables), lens,
                                k_scales=ks, v_scales=vs,
                                implementation=impl)
        for j in range(1 + k):
            one = paged_attention(
                q[:, j:j + 1], kq, vq, jnp.asarray(tables), lens + j,
                k_scales=ks, v_scales=vs, implementation=impl)
            np.testing.assert_allclose(
                np.asarray(chunk[:, j]), np.asarray(one[:, 0]),
                atol=2e-6, rtol=2e-6)

    @pytest.mark.parametrize("kv_dtype,bound", [
        ("int8", 0.05),
        pytest.param("fp8", 0.2, marks=pytest.mark.skipif(
            not hasattr(jnp, "float8_e4m3fn"),
            reason="no float8_e4m3fn in this jax build")),
    ])
    def test_error_vs_float_pool_within_stated_bound(self, kv_dtype,
                                                     bound):
        """The ISSUE-8 accuracy bound, stated: for unit-variance K/V,
        symmetric per-page amax quantization perturbs each element by
        at most scale/254 (int8 round-to-nearest) / one e4m3 ulp
        (~6% relative, fp8); through the softmax-weighted average the
        per-step attention output error stays under 0.05 (int8) /
        0.2 (fp8) absolute — measured ~0.02 / ~0.1 on this fixture,
        asserted at 2× headroom."""
        rng = np.random.default_rng(14)
        b, s, h, hk, d, NB, BS, MB = 3, 4, 8, 2, 32, 24, 8, 6
        lengths = [9, 0, 27]
        kp, vp, tables = _pool_setup(
            rng, b=b, hk=hk, d=d, NB=NB, BS=BS, MB=MB,
            lengths=lengths, s=s, dtype=jnp.float32)
        q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        lens = jnp.asarray(lengths, jnp.int32)
        base = paged_attention_reference(q, kp, vp,
                                         jnp.asarray(tables), lens)
        kq, vq, ks, vs = quantize_kv_pages(kp, vp, kv_dtype)
        quant = paged_attention_reference(
            q, kq, vq, jnp.asarray(tables), lens,
            k_scales=ks, v_scales=vs)
        err = np.abs(np.asarray(quant) - np.asarray(base)).max()
        assert err <= bound, (kv_dtype, err)

    def test_zero_pages_quantize_to_exact_zero(self):
        """An all-zero page (scale 0) must quantize AND dequantize to
        exact zeros — the near-zero guard, not NaN from 0 × inf."""
        kp = jnp.zeros((2, 4, 8, 16), jnp.float32)
        kq, vq, ks, vs = quantize_kv_pages(kp, kp, "int8")
        assert not np.asarray(kq).any()
        assert not np.asarray(ks).any()
        q = jnp.ones((1, 1, 2, 16), jnp.float32)
        out = paged_attention_reference(
            q, kq, vq, jnp.ones((1, 2), jnp.int32),
            jnp.asarray([9], jnp.int32), k_scales=ks, v_scales=vs)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_array_equal(np.asarray(out), 0.0)

    def test_scale_argument_validation(self):
        rng = np.random.default_rng(15)
        kp = jnp.asarray(rng.normal(size=(2, 4, 8, 16)), jnp.float32)
        kq, vq, ks, vs = quantize_kv_pages(kp, kp, "int8")
        q = jnp.zeros((1, 1, 2, 16), jnp.float32)
        tables = jnp.zeros((1, 2), jnp.int32)
        lens = jnp.zeros((1,), jnp.int32)
        with pytest.raises(ValueError, match="need k_scales"):
            paged_attention(q, kq, vq, tables, lens)
        with pytest.raises(ValueError, match="only apply"):
            paged_attention(q, kp, kp, tables, lens,
                            k_scales=ks, v_scales=vs)
        with pytest.raises(ValueError, match="k_scales shape"):
            paged_attention(q, kq, vq, tables, lens,
                            k_scales=ks[:, :2], v_scales=vs)
        with pytest.raises(ValueError, match="dtypes differ"):
            paged_attention(q, kq, vq.astype(jnp.float32), tables,
                            lens, k_scales=ks, v_scales=vs)

    def test_kv_quant_spec_contract(self):
        assert kv_quant_spec(None) == (None, None)
        dt, qmax = kv_quant_spec("int8")
        assert jnp.dtype(dt) == jnp.dtype(jnp.int8) and qmax == 127.0
        with pytest.raises(ValueError, match="kv_dtype"):
            kv_quant_spec("int4")
        if hasattr(jnp, "float8_e4m3fn"):
            dt, qmax = kv_quant_spec("fp8")
            assert qmax == 448.0
        with pytest.raises(ValueError, match="int8"):
            quantize_kv_pages(jnp.zeros((1, 2, 8, 8)),
                              jnp.zeros((1, 2, 8, 8)), None)


class TestDenseParityAnchor:
    def test_reference_matches_dense_cache_attention(self):
        """Paged reference == the dense engine's cache attention on
        the same logical K/V (shared-length rows, s=1): the numerics
        bridge behind engine-level greedy parity."""
        from apex_tpu.models.transformer import _cache_attention

        rng = np.random.default_rng(3)
        b, h, hk, d, BS = 2, 4, 2, 16, 8
        S = 32                     # dense cache length == MB * BS
        MB = S // BS
        NB = b * MB + 1
        L = 19                     # shared live length (scalar idx)
        dense_k = jnp.asarray(rng.normal(size=(b, S, hk, d)),
                              jnp.float32)
        dense_v = jnp.asarray(rng.normal(size=(b, S, hk, d)),
                              jnp.float32)
        q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
        # pack the dense rows into pool pages
        kp = np.zeros((hk, NB, BS, d), np.float32)
        vp = np.zeros((hk, NB, BS, d), np.float32)
        tables = np.zeros((b, MB), np.int32)
        nxt = 1
        for i in range(b):
            for j in range(MB):
                kp[:, nxt] = np.asarray(
                    dense_k[i, j * BS:(j + 1) * BS]).transpose(1, 0, 2)
                vp[:, nxt] = np.asarray(
                    dense_v[i, j * BS:(j + 1) * BS]).transpose(1, 0, 2)
                tables[i, j] = nxt
                nxt += 1
        scale = d ** -0.5
        dense = _cache_attention(q, dense_k, dense_v,
                                 jnp.int32(L), scale)
        paged = paged_attention_reference(
            q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
            jnp.full((b,), L, jnp.int32), scale=scale)
        np.testing.assert_allclose(np.asarray(paged),
                                   np.asarray(dense), atol=1e-5,
                                   rtol=1e-5)


class TestValidation:
    def test_shape_mismatches_raise(self):
        q = jnp.zeros((2, 1, 4, 16))
        kp = jnp.zeros((2, 4, 8, 16))
        tables = jnp.zeros((2, 2), jnp.int32)
        lens = jnp.zeros((2,), jnp.int32)
        with pytest.raises(ValueError, match="head_dim"):
            paged_attention(q, jnp.zeros((2, 4, 8, 8)),
                            jnp.zeros((2, 4, 8, 8)), tables, lens)
        with pytest.raises(ValueError, match="divide"):
            paged_attention(jnp.zeros((2, 1, 3, 16)), kp, kp,
                            tables, lens)
        with pytest.raises(ValueError, match="batch"):
            paged_attention(q, kp, kp, tables,
                            jnp.zeros((3,), jnp.int32))
        with pytest.raises(ValueError, match="differ"):
            paged_attention(q, kp, jnp.zeros((2, 5, 8, 16)),
                            tables, lens)


class TestAutotune:
    def test_sweep_caches_under_the_engine_lookup_key(
            self, tmp_path, monkeypatch):
        """tune_paged_attention must produce an entry the engine's
        ``block_size=0`` lookup actually finds: keyed on head_dim +
        dtype, pool auto-sized to the sweep (regression: the original
        fixed pool made every candidate raise, silently caching
        nothing)."""
        monkeypatch.setenv("APEX_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "at.json"))
        from apex_tpu.ops import autotune

        autotune.clear_cache()
        try:
            # kv_dtypes=(None,) = the pre-ISSUE-8 sweep, unchanged
            best, kvd = autotune.tune_paged_attention(
                n_rows=2, width=16, kv_heads=2, live_tokens=64,
                dtype="float32", candidates=(8, 16),
                kv_dtypes=(None,))
            assert best in (8, 16) and kvd is None
            autotune.clear_cache()     # force a reload from the file
            assert autotune.cached_block_rows(
                "paged_attention", 16,
                str(jnp.dtype("float32")), kv_heads=2) == best
            # entries are kv-head-qualified (ISSUE 13): a TP engine
            # querying with its per-shard count must NOT find the
            # full-head-count winner
            assert autotune.cached_block_rows(
                "paged_attention", 16,
                str(jnp.dtype("float32")), kv_heads=1) is None
            assert autotune.cached_block_rows(
                "paged_attention", 16,
                str(jnp.dtype("float32"))) is None
        finally:
            autotune.clear_cache()     # drop the tmp-file cache state

    def test_joint_kv_dtype_sweep_caches_pair_and_per_dtype_entries(
            self, tmp_path, monkeypatch):
        """The ISSUE-8 joint sweep: every storage dtype gets a
        block-size entry under ITS key (the engine's explicit-kv_dtype
        lookup), and the winning (block, kv_dtype) pair lands under
        the compute-dtype pair key that kv_dtype='auto' consults."""
        monkeypatch.setenv("APEX_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "at.json"))
        from apex_tpu.ops import autotune

        autotune.clear_cache()
        try:
            pair = autotune.tune_paged_attention(
                n_rows=2, width=16, kv_heads=2, live_tokens=64,
                dtype="float32", candidates=(8, 16),
                kv_dtypes=(None, "int8"))
            assert pair is not None
            bs, kvd = pair
            assert bs in (8, 16) and kvd in (None, "int8")
            autotune.clear_cache()
            assert autotune.cached_block_rows(
                "paged_attention", 16, "float32", kv_heads=2) in (8, 16)
            assert autotune.cached_block_rows(
                "paged_attention", 16, "int8", kv_heads=2) in (8, 16)
            assert autotune.cached_paged_pair(
                16, "float32", kv_heads=2) == pair
            # untuned (device, width, dtype, kv_heads) stays a miss —
            # incl. the same width at a different (per-shard) head
            # count
            assert autotune.cached_paged_pair(
                32, "float32", kv_heads=2) is None
            assert autotune.cached_paged_pair(
                16, "float32", kv_heads=1) is None
        finally:
            autotune.clear_cache()


class TestPerStepBytesScaleWithLiveTokens:
    """The paged datapath's cost-model bytes grow with LIVE pages; the
    dense cache einsum reads the full ``max_seq_len`` slab per step no
    matter how little is live (the measured defect the paged tentpole
    fixes — documented in ``bench_configs._serving_traffic_model``)."""

    def _bytes(self, fn, *args):
        lowered = jax.jit(fn).lower(*args)
        ca = lowered.compile().cost_analysis()
        if isinstance(ca, list):            # older jax: per-computation
            ca = ca[0]
        if not ca or "bytes accessed" not in ca:
            pytest.skip("cost_analysis without bytes on this backend")
        return float(ca["bytes accessed"])

    def test_paged_bytes_track_live_pages_dense_bytes_do_not(self):
        from apex_tpu.models.transformer import _cache_attention

        rng = np.random.default_rng(4)
        b, h, hk, d, BS = 2, 4, 4, 64, 16
        S = 512                              # dense slab length
        NB = 2 * (S // BS) + 1
        kp = jnp.asarray(rng.normal(size=(hk, NB, BS, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(hk, NB, BS, d)), jnp.float32)
        q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
        dense_k = jnp.asarray(rng.normal(size=(b, S, hk, d)),
                              jnp.float32)
        dense_v = jnp.asarray(rng.normal(size=(b, S, hk, d)),
                              jnp.float32)

        def paged_at(mb):
            tables = jnp.asarray(
                np.arange(1, b * mb + 1).reshape(b, mb), jnp.int32)
            lens = jnp.full((b,), mb * BS - 1, jnp.int32)
            return self._bytes(
                lambda q: paged_attention_reference(
                    q, kp, vp, tables, lens), q)

        # live = 64 vs 256 tokens: paged bytes must scale ~linearly
        paged_small = paged_at(64 // BS)
        paged_big = paged_at(256 // BS)
        ratio = paged_big / paged_small
        assert 2.0 <= ratio <= 8.0, (paged_small, paged_big)

        def dense_at(live):
            idx = jnp.int32(live - 1)
            return self._bytes(
                lambda q: _cache_attention(q, dense_k, dense_v, idx,
                                           d ** -0.5), q)

        # the dense einsum's bytes are live-independent (the cursor
        # only masks) — THE defect: reads pinned at max_seq_len
        dense_small = dense_at(64)
        dense_big = dense_at(256)
        assert abs(dense_big - dense_small) / dense_big < 0.05, (
            dense_small, dense_big)
        # and at short live lengths the paged step reads far less than
        # the dense slab pass
        assert paged_small < 0.5 * dense_small, (paged_small,
                                                 dense_small)


# --------------------------------------------------------------------- #
# fused decode prologue (ISSUE 14) — RoPE + write + attend in one op
# --------------------------------------------------------------------- #
class TestFusedDecodePrologue:
    """``paged_decode_fused``: the width-1 decode step's prologue
    (per-row RoPE → [quantize] → page write) folded into the attend.

    Both sides run under jit (the only way the engines run them): the
    reference must be the historical unfused sequence verbatim, and
    the interpret-mode kernel must reproduce the reference's written
    pages / codes / scales BITWISE on live pages (the null page stays
    garbage-by-contract on every path) with the attend output equal up
    to the kernel's blocked accumulation order."""

    def _setup(self, rng, *, b=3, h=8, hk=4, d=32, BS=8, S=64,
               kv_dtype=None, lengths=None, share=0, pads=()):
        """``share``: row 1 reads row 0's first ``share`` pages (a
        copy-on-write prefix, both rows writing pages of their own);
        ``pads``: rows that are pad lanes of a coded pool."""
        from apex_tpu.ops.paged_attention import quantize_kv_pages
        from apex_tpu.ops.rope import rope_cos_sin

        if lengths is None:
            # fresh-page, mid-page and page-boundary-append rows
            lengths = np.array([5, BS, 3 * BS - 1], np.int32)[:b]
        lengths = np.asarray(lengths, np.int32)
        b = len(lengths)
        MB = S // BS
        NB = b * MB + 3
        kp = jnp.asarray(rng.normal(size=(hk, NB, BS, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(hk, NB, BS, d)), jnp.float32)
        scales = {}
        if kv_dtype is not None:
            kp, vp, ks, vs = quantize_kv_pages(kp, vp, kv_dtype)
            cl = np.ones((b,), np.int32)
            cl[list(pads)] = 0
            scales = dict(k_scales=ks, v_scales=vs,
                          chunk_lens=jnp.asarray(cl))
        tables = np.zeros((b, MB), np.int32)
        used = rng.permutation(np.arange(1, NB))[: b * MB] \
            .reshape(b, MB)
        for r in range(b):
            npages = min(MB, -(-int(min(lengths[r], S - 1) + 1) // BS))
            tables[r, :npages] = used[r, :npages]
        if share:
            # full pages before both rows' write pages
            assert share <= min(lengths[:2]) // BS
            tables[1, :share] = tables[0, :share]
        q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
        nk = jnp.asarray(rng.normal(size=(b, 1, hk, d)), jnp.float32)
        nv = jnp.asarray(rng.normal(size=(b, 1, hk, d)), jnp.float32)
        cos, sin = rope_cos_sin(S, d)
        pc = np.minimum(lengths[:, None], S - 1)
        rope = dict(cos_b=jnp.asarray(cos[pc][:, :, None, :]),
                    sin_b=jnp.asarray(sin[pc][:, :, None, :]))
        live = tables.ravel()
        return (q, nk, nv, kp, vp, jnp.asarray(tables),
                jnp.asarray(lengths), rope, scales, S,
                live[live > 0])

    @staticmethod
    def _run(impl, args, S, rope, scales):
        from apex_tpu.ops.paged_attention import paged_decode_fused
        return jax.jit(lambda *a: paged_decode_fused(
            *a, max_seq_len=S, implementation=impl, **rope,
            **scales))(*args)

    def test_reference_is_the_unfused_sequence(self):
        """XLA reference == rope_rows → scatter → gather-attend,
        composed by hand from the same public pieces — bitwise."""
        from apex_tpu.ops.paged_attention import (
            paged_attention_reference, paged_decode_fused_reference,
            rope_rows)

        rng = np.random.default_rng(3)
        (q, nk, nv, kp, vp, tables, lengths, rope, _sc, S,
         _live) = self._setup(rng)
        got = jax.jit(lambda *a: paged_decode_fused_reference(
            *a, max_seq_len=S, **rope))(
            q, nk, nv, kp, vp, tables, lengths)

        def manual(q, nk, nv, kp, vp, tables, lengths):
            BS, MB = kp.shape[2], tables.shape[1]
            qm = rope_rows(q, rope["cos_b"], rope["sin_b"])
            km = rope_rows(nk, rope["cos_b"], rope["sin_b"])
            pos = lengths[:, None]
            phys = jnp.take_along_axis(
                tables, jnp.minimum(pos // BS, MB - 1), axis=1)
            phys = jnp.where(pos < S, phys, 0)
            off = pos % BS
            kp = kp.at[:, phys, off].set(km.transpose(2, 0, 1, 3))
            vp = vp.at[:, phys, off].set(nv.transpose(2, 0, 1, 3))
            return (paged_attention_reference(qm, kp, vp, tables,
                                              lengths), kp, vp)

        ref = jax.jit(manual)(q, nk, nv, kp, vp, tables, lengths)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b))

    @pytest.mark.parametrize("h,hk,batch", _kept_ids(
        [(8, 4), (10, 2)],                                   # 2:1, 5:1
        *[(name, 10, 2) for name in _HANDOFFS]))
    def test_kernel_matches_reference_unquantized(self, h, hk, batch):
        rng = np.random.default_rng(4)
        kw = {} if batch is None else dict(S=_HANDOFF_S, **_HANDOFFS[batch])
        (q, nk, nv, kp, vp, tables, lengths, rope, sc, S,
         live) = self._setup(rng, h=h, hk=hk, **kw)
        args = (q, nk, nv, kp, vp, tables, lengths)
        ref = self._run("xla", args, S, rope, sc)
        got = self._run("pallas_interpret", args, S, rope, sc)
        np.testing.assert_allclose(np.asarray(got[0]),
                                   np.asarray(ref[0]),
                                   rtol=2e-5, atol=2e-5)
        # written pages bitwise on live pages (write-then-attend: the
        # new row IS in the returned pool)
        for i in (1, 2):
            np.testing.assert_array_equal(
                np.asarray(got[i][:, live]), np.asarray(ref[i][:, live]))

    @pytest.mark.parametrize("kv_dtype,batch", _kept_ids(
        _KV_DTYPES, ("chunks-1-2-9", "int8"), ("cow-prefix", "int8"),
        ("pad-row", "int8"), ("row-at-S", "int8")))
    def test_kernel_matches_reference_quantized(self, kv_dtype, batch):
        """Codes AND monotone running-amax scales bitwise on live
        pages — the PR-8 scale discipline survives the fusion; a pad
        lane between real rows writes nothing."""
        rng = np.random.default_rng(5)
        kw = {} if batch is None else dict(
            S=_HANDOFF_S, **_HANDOFFS.get(batch, _PAD_ROW))
        (q, nk, nv, kp, vp, tables, lengths, rope, sc, S,
         live) = self._setup(rng, kv_dtype=kv_dtype, **kw)
        args = (q, nk, nv, kp, vp, tables, lengths)
        ref = self._run("xla", args, S, rope, sc)
        got = self._run("pallas_interpret", args, S, rope, sc)
        np.testing.assert_allclose(np.asarray(got[0]),
                                   np.asarray(ref[0]),
                                   rtol=2e-5, atol=2e-5)
        for i in (1, 2, 3, 4):
            np.testing.assert_array_equal(
                np.asarray(got[i][:, live]), np.asarray(ref[i][:, live]))

    def test_no_rope_model_is_fully_bitwise(self):
        """Learned-position models skip the rotation: the written row
        is a pure insert, so kernel pool output == reference pool
        output bit-for-bit on live pages."""
        rng = np.random.default_rng(6)
        (q, nk, nv, kp, vp, tables, lengths, _rope, sc, S,
         live) = self._setup(rng, kv_dtype="int8")
        args = (q, nk, nv, kp, vp, tables, lengths)
        ref = self._run("xla", args, S, {}, sc)
        got = self._run("pallas_interpret", args, S, {}, sc)
        for i in (1, 2, 3, 4):
            np.testing.assert_array_equal(
                np.asarray(got[i][:, live]), np.asarray(ref[i][:, live]))

    def test_past_max_seq_len_routes_to_null_page(self):
        """A cursor at/past max_seq_len writes the null page on both
        paths: every LIVE page must be byte-identical to its input
        (nothing live was touched)."""
        rng = np.random.default_rng(7)
        (q, nk, nv, kp, vp, tables, lengths, rope, sc, S,
         live) = self._setup(rng, lengths=np.array([64, 70, 5],
                                                   np.int32))
        args = (q, nk, nv, kp, vp, tables, lengths)
        for impl in ("xla", "pallas_interpret"):
            got = self._run(impl, args, S, rope, sc)
            # rows 0/1 nulled; row 2 wrote its page — all OTHER rows'
            # live pages unchanged
            row2 = set(np.asarray(tables)[2].tolist())
            untouched = [p for p in live.tolist() if p not in row2]
            np.testing.assert_array_equal(
                np.asarray(got[1][:, untouched]),
                np.asarray(kp[:, untouched]))

    def test_width_gt_one_raises(self):
        from apex_tpu.ops.paged_attention import paged_decode_fused

        rng = np.random.default_rng(8)
        (q, nk, nv, kp, vp, tables, lengths, rope, sc, S,
         _live) = self._setup(rng)
        q2 = jnp.concatenate([q, q], axis=1)
        nk2 = jnp.concatenate([nk, nk], axis=1)
        with pytest.raises(ValueError, match="width-1"):
            paged_decode_fused(q2, nk2, nk2, kp, vp, tables, lengths,
                               max_seq_len=S)

    def test_scale_argument_validation(self):
        from apex_tpu.ops.paged_attention import paged_decode_fused
        from apex_tpu.ops.paged_attention import quantize_kv_pages

        rng = np.random.default_rng(9)
        (q, nk, nv, kp, vp, tables, lengths, rope, _sc, S,
         _live) = self._setup(rng)
        kq, vq, ks, vs = quantize_kv_pages(kp, vp, "int8")
        with pytest.raises(ValueError, match="need k_scales"):
            paged_decode_fused(q, nk, nv, kq, vq, tables, lengths,
                               max_seq_len=S)
        with pytest.raises(ValueError, match="only apply"):
            paged_decode_fused(q, nk, nv, kp, vp, tables, lengths,
                               max_seq_len=S, k_scales=ks, v_scales=vs)


# --------------------------------------------------------------------- #
# the in-kernel page sweep (ISSUE 30) — chunk edges, table width, grid
# --------------------------------------------------------------------- #
_WRITE_DTYPES = [
    "bfloat16", "float32", "int8",
    pytest.param("float8_e4m3fn", marks=pytest.mark.skipif(
        not hasattr(jnp, "float8_e4m3fn"),
        reason="no float8_e4m3fn in this jax build")),
]


class TestChunkWrite:
    """``paged_write``: a step wider than one token puts its K/V rows
    into the pool by one Pallas call aliased to both pools.  Against
    the XLA scatter it replaces (``paged_write_reference``, the
    model's historical write verbatim) the interpret-mode kernel is
    BITWISE equal on every page but the null page, whose content is
    garbage by contract (the kernel drops the lanes routed there and
    keeps its bytes); pages no lane touches keep theirs."""

    @staticmethod
    def _case(dtype, *, s, hk, cursors, n_tokens=None, BS=8, d=32,
              S=64, seed=0):
        """Pool, rows and the ``(phys, off)`` the model's branch
        computes for rows at ``cursors`` (``n_tokens``: the coded
        pool's real-lane counts, pad lanes routed to the null page);
        each row owns the pages its real lanes need, the rest of its
        table points at the null page."""
        rng = np.random.default_rng(seed)
        dtype = jnp.dtype(dtype)
        cursors = np.asarray(cursors, np.int32)
        b, MB = len(cursors), -(-S // BS)
        NB = b * MB + 1

        def vals(shape):
            x = rng.normal(size=shape) * 20
            if jnp.issubdtype(dtype, jnp.integer):
                x = np.clip(np.round(x), -127, 127)
            return jnp.asarray(x, dtype)

        kp, vp = vals((hk, NB, BS, d)), vals((hk, NB, BS, d))
        k, v = vals((b, s, hk, d)), vals((b, s, hk, d))
        lens = (np.full((b,), s) if n_tokens is None
                else np.asarray(n_tokens))
        tables = np.zeros((b, MB), np.int32)
        own = rng.permutation(np.arange(1, NB)).reshape(b, MB)
        for r in range(b):
            n = min(MB, -(-(int(cursors[r]) + int(lens[r])) // BS))
            tables[r, :n] = own[r, :n]
        pos = cursors[:, None] + np.arange(s)
        phys = np.take_along_axis(
            tables, np.minimum(pos // BS, MB - 1), axis=1)
        phys = np.where(pos < S, phys, 0)
        if n_tokens is not None:
            phys = np.where(np.arange(s)[None] < lens[:, None], phys, 0)
        return (k, v, kp, vp, jnp.asarray(phys, jnp.int32),
                jnp.asarray(pos % BS, jnp.int32))

    @staticmethod
    def _bits(x):
        x = np.asarray(x)
        return x.view({1: np.uint8, 2: np.uint16,
                       4: np.uint32}[x.dtype.itemsize])

    def _check(self, args):
        from apex_tpu.ops.paged_attention import (paged_write,
                                                  paged_write_reference)

        want = jax.jit(paged_write_reference)(*args)
        got = jax.jit(lambda *a: paged_write(
            *a, implementation="pallas_interpret"))(*args)
        phys = np.asarray(args[4])
        touched = np.unique(phys[phys > 0])
        for g, w, old in zip(got, want, args[2:4]):
            g, w, old = self._bits(g), self._bits(w), self._bits(old)
            np.testing.assert_array_equal(g[:, 1:], w[:, 1:])
            np.testing.assert_array_equal(g[:, 0], old[:, 0])
            rest = np.setdiff1d(np.arange(1, g.shape[1]), touched)
            np.testing.assert_array_equal(g[:, rest], old[:, rest])
            if touched.size:
                assert (g[:, touched] != old[:, touched]).any()

    @pytest.mark.parametrize("hk", [8, 4])
    @pytest.mark.parametrize("s", [32, 5])      # prefill chunk, verify
    @pytest.mark.parametrize("dtype", _WRITE_DTYPES)
    def test_kernel_is_the_scatter_bit_for_bit(self, dtype, s, hk):
        """Rows at a page's start, mid-page, at a page's last offset,
        and at the end of the cache; coded pools carry pad lanes."""
        coded = jnp.dtype(dtype).itemsize == 1
        self._check(self._case(
            dtype, s=s, hk=hk, cursors=[0, 3, 15, 8, 30],
            n_tokens=[s, 2, s - 1, 1, 3] if coded else None))

    @pytest.mark.parametrize("case", [
        # starts mid-page and crosses two page boundaries: 3 pages
        dict(s=16, cursors=[5], BS=8),
        dict(s=32, cursors=[11, 16, 1], BS=16, S=128),
        # pad lanes (coded pool) and a cursor at max_seq_len - 1: the
        # lanes past the cache go to the null page
        dict(s=32, cursors=[63, 40], n_tokens=[1, 7]),
        dict(s=5, cursors=[63, 62, 60]),
        # a row with n_tokens = 0 writes nothing at all
        dict(s=32, cursors=[9, 20, 0], n_tokens=[0, 32, 0]),
        # an idle batch: no page moves
        dict(s=5, cursors=[0, 0], n_tokens=[0, 0]),
    ], ids=["two_boundaries", "two_boundaries_bs16", "pad_and_last",
            "cursor_at_the_end", "empty_row", "all_empty"])
    @pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
    def test_edges(self, dtype, case):
        self._check(self._case(dtype, hk=4, **case))

    def test_reference_is_the_models_scatter(self):
        """The dispatch target off a TPU is the historical write,
        verbatim: ``pool.at[:, phys, off].set(rows)``."""
        from apex_tpu.ops.paged_attention import paged_write

        k, v, kp, vp, phys, off = self._case(
            "float32", s=5, hk=4, cursors=[0, 7, 62])
        got = jax.jit(paged_write)(k, v, kp, vp, phys, off)  # auto: xla
        np.testing.assert_array_equal(
            np.asarray(got[0]),
            np.asarray(kp.at[:, phys, off].set(k.transpose(2, 0, 1, 3))))
        np.testing.assert_array_equal(
            np.asarray(got[1]),
            np.asarray(vp.at[:, phys, off].set(v.transpose(2, 0, 1, 3))))

    def test_one_call_writes_both_pools_in_place(self):
        """ONE ``pallas_call`` for K and V, the pools aliased to its
        outputs, a grid step a row and no loop unrolled over lanes,
        pages or heads (what a server's start pays to lower it)."""
        from apex_tpu.ops.paged_attention import paged_write

        args = self._case("bfloat16", s=32, hk=8, cursors=[3] * 6)
        jaxpr = jax.make_jaxpr(lambda *a: paged_write(
            *a, implementation="pallas_interpret"))(*args)
        calls = [e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "pallas_call"]
        assert len(calls) == 1
        params = calls[0].params
        assert params["grid_mapping"].grid == (6,)
        assert dict(params["input_output_aliases"]) == {5: 0, 6: 1}
        body = params["jaxpr"]
        assert len(body.eqns) < 40, len(body.eqns)

    def test_validation_and_envelope(self):
        from apex_tpu.ops.paged_attention import paged_write

        k, v, kp, vp, phys, off = self._case(
            "bfloat16", s=5, hk=4, cursors=[0, 7])
        with pytest.raises(ValueError, match="shapes differ"):
            paged_write(k, v[:, :4], kp, vp, phys, off)
        with pytest.raises(ValueError, match="do not match pages"):
            paged_write(k[:, :, :2], v[:, :, :2], kp, vp, phys, off)
        with pytest.raises(ValueError, match=r"not \(b, s\)"):
            paged_write(k, v, kp, vp, phys[:, :3], off)
        # rows not in the pool's dtype: outside the kernel's envelope
        with pytest.raises(ValueError, match="outside its envelope"):
            paged_write(k.astype(jnp.float32), v.astype(jnp.float32),
                        kp, vp, phys, off,
                        implementation="pallas_interpret")
        # and so is a tensor-parallel pool: it keeps the scatter
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]),
                                 ("tensor",))
        with pytest.raises(ValueError, match="outside its envelope"):
            paged_write(k, v, kp, vp, phys, off, mesh=mesh,
                        shard_axis="tensor",
                        implementation="pallas_interpret")
        got = paged_write(k, v, kp, vp, phys, off, mesh=mesh,
                          shard_axis="tensor")
        np.testing.assert_array_equal(
            self._bits(got[0]),
            self._bits(kp.at[:, phys, off].set(k.transpose(2, 0, 1, 3))))


def _chunk_positions(bs):
    from apex_tpu.ops.paged_attention import _chunk_pages
    return _chunk_pages(bs) * bs


def _edge_lengths(bs, S, s=1):
    """Cursors on every edge the sweep has: an empty slot, one token,
    either side of a page boundary and of a chunk boundary, a full
    table, and a cursor at and past ``max_seq_len``."""
    t = _chunk_positions(bs)
    assert t + 1 < S - s
    return [0, 1, bs - 1, bs, t - 1, t, t + 1, S - s, S, S + bs + 3]


def _edge_pool(rng, *, lengths, s, hk, d, BS, MB, dtype):
    """Pool, tables and the live physical pages for ragged rows that
    may run to (or past) the table's end."""
    kp, vp, tables = _pool_setup(
        rng, b=len(lengths), hk=hk, d=d, NB=len(lengths) * MB + 1,
        BS=BS, MB=MB, lengths=lengths, s=s, dtype=dtype, cap=True)
    live = tables.ravel()
    return kp, vp, jnp.asarray(tables), live[live > 0]


_POOLS = [(jnp.float32, None), (jnp.bfloat16, None),
          (jnp.float32, "int8"),
          pytest.param(jnp.float32, "fp8", marks=pytest.mark.skipif(
              not hasattr(jnp, "float8_e4m3fn"),
              reason="no float8_e4m3fn in this jax build"))]


class TestSweepEdges:
    """Both kernels against their XLA references on rows whose live
    prefix ends on every edge of the chunked sweep."""

    BS, MB, HK, H, D = 8, 24, 2, 4, 32

    @pytest.mark.parametrize("s", [1, 4])
    @pytest.mark.parametrize("dtype,kv_dtype", _POOLS)
    def test_chunk_kernel(self, s, dtype, kv_dtype):
        rng = np.random.default_rng(30)
        BS, MB = self.BS, self.MB
        S = BS * MB
        lengths = _edge_lengths(BS, S, s)
        kp, vp, tables, _live = _edge_pool(
            rng, lengths=lengths, s=s, hk=self.HK, d=self.D, BS=BS,
            MB=MB, dtype=dtype)
        scales = {}
        if kv_dtype is not None:
            kp, vp, ks, vs = quantize_kv_pages(kp, vp, kv_dtype)
            scales = dict(k_scales=ks, v_scales=vs)
        q = jnp.asarray(rng.normal(size=(len(lengths), s, self.H, self.D)),
                        dtype)
        lens = jnp.asarray(lengths, jnp.int32)
        ref = paged_attention_reference(q, kp, vp, tables, lens, **scales)
        out = paged_attention(q, kp, vp, tables, lens,
                              implementation="pallas_interpret", **scales)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=tol, rtol=tol)

    @pytest.mark.parametrize("dtype,kv_dtype", _POOLS)
    def test_fused_kernel(self, dtype, kv_dtype):
        """The attend within tolerance; written pages, codes and
        scales bitwise on live pages, at every edge — a row at or past
        ``max_seq_len`` writes nothing live."""
        from apex_tpu.ops.paged_attention import paged_decode_fused
        from apex_tpu.ops.rope import rope_cos_sin

        rng = np.random.default_rng(31)
        BS, MB = self.BS, self.MB
        S = BS * MB
        lengths = np.asarray(_edge_lengths(BS, S), np.int32)
        b = len(lengths)
        kp, vp, tables, live = _edge_pool(
            rng, lengths=list(lengths), s=1, hk=self.HK, d=self.D,
            BS=BS, MB=MB, dtype=dtype)
        kw = {}
        if kv_dtype is not None:
            kp, vp, ks, vs = quantize_kv_pages(kp, vp, kv_dtype)
            cl = np.ones((b,), np.int32)
            cl[2] = 0                        # one pad lane: no write
            kw = dict(k_scales=ks, v_scales=vs,
                      chunk_lens=jnp.asarray(cl))
        q = jnp.asarray(rng.normal(size=(b, 1, self.H, self.D)), dtype)
        nk = jnp.asarray(rng.normal(size=(b, 1, self.HK, self.D)), dtype)
        nv = jnp.asarray(rng.normal(size=(b, 1, self.HK, self.D)), dtype)
        cos, sin = rope_cos_sin(S, self.D)
        pc = np.minimum(lengths[:, None], S - 1)
        kw.update(cos_b=jnp.asarray(cos[pc][:, :, None, :]),
                  sin_b=jnp.asarray(sin[pc][:, :, None, :]))
        args = (q, nk, nv, kp, vp, tables, jnp.asarray(lengths))

        def run(impl):
            return jax.jit(lambda *a: paged_decode_fused(
                *a, max_seq_len=S, implementation=impl, **kw))(*args)

        ref, got = run("xla"), run("pallas_interpret")
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(got[0], np.float32), np.asarray(ref[0], np.float32),
            atol=tol, rtol=tol)
        assert len(got) == len(ref) == (3 if kv_dtype is None else 5)
        for g, r in zip(got[1:], ref[1:]):
            np.testing.assert_array_equal(np.asarray(g[:, live]),
                                          np.asarray(r[:, live]))
        # the rows at and past max_seq_len left every live page alone
        own = np.asarray(tables)[-2:].ravel()
        own = own[own > 0]
        np.testing.assert_array_equal(np.asarray(got[1][:, own]),
                                      np.asarray(kp[:, own]))


class TestSweepFollowsLiveTokensNotTheTable:
    """The sweep is a loop inside the kernel over the row's live
    chunks: the table's width is neither in the grid nor in the
    result."""

    def _case(self, MB, s, fused):
        from apex_tpu.ops.paged_attention import paged_decode_fused

        rng = np.random.default_rng(32)
        b, h, hk, d, BS, NB = 3, 4, 2, 32, 8, 40
        lengths = [0, 13, 8 * BS - s]            # <= 8 pages a row
        kp = jnp.asarray(rng.normal(size=(hk, NB, BS, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(hk, NB, BS, d)), jnp.float32)
        tables = np.zeros((b, MB), np.int32)
        tables[:, :8] = 1 + np.arange(b * 8).reshape(b, 8)
        q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        lens = jnp.asarray(lengths, jnp.int32)
        if not fused:
            return (lambda q: paged_attention(
                q, kp, vp, jnp.asarray(tables), lens,
                implementation="pallas_interpret")), (q,)
        nk = jnp.asarray(rng.normal(size=(b, 1, hk, d)), jnp.float32)
        return (lambda q, nk: paged_decode_fused(
            q, nk, nk, kp, vp, jnp.asarray(tables), lens,
            max_seq_len=8 * BS, implementation="pallas_interpret")[0]
            ), (q, nk)

    @pytest.mark.parametrize("s,fused", [(1, False), (4, False),
                                         (1, True)])
    def test_table_width_changes_nothing(self, s, fused):
        narrow, args = self._case(8, s, fused)
        wide, _ = self._case(256, s, fused)
        np.testing.assert_array_equal(np.asarray(jax.jit(narrow)(*args)),
                                      np.asarray(jax.jit(wide)(*args)))

    @pytest.mark.parametrize("s,fused", [(4, False), (1, True)])
    def test_grid_has_no_page_axis(self, s, fused):
        fn, args = self._case(256, s, fused)
        calls = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
                 if e.primitive.name == "pallas_call"]
        assert len(calls) == 1
        assert tuple(calls[0].params["grid_mapping"].grid) == (3,)
        # the rows hand the stream of chunk fetches on: run in order
        assert calls[0].params["compiler_params"]["mosaic_tpu"] \
            .dimension_semantics == ("arbitrary",)


# --------------------------------------------------------------------- #
# sliding window (ISSUE 37): every read of the pool takes ``window=``
# --------------------------------------------------------------------- #
def _brute_force(q, kp, vp, tables, lengths, window):
    """Row by row, query by query, in numpy: the keys a query at
    position ``p`` sees are ``max(0, p - window + 1) .. p``."""
    q, kp, vp = (np.asarray(a, np.float64) for a in (q, kp, vp))
    tables = np.asarray(tables)
    b, s, h, d = q.shape
    hk, _nb, bs, _ = kp.shape
    rep = h // hk
    out = np.zeros((b, s, h, d))
    for r in range(b):
        keys = kp[:, tables[r]].reshape(hk, -1, d)
        vals = vp[:, tables[r]].reshape(hk, -1, d)
        for i in range(s):
            p = min(int(lengths[r]) + i, keys.shape[1] - 1)
            lo = 0 if window is None else max(0, p - window + 1)
            for head in range(h):
                k, v = keys[head // rep, lo:p + 1], vals[head // rep, lo:p + 1]
                sc = k @ q[r, i, head] * d ** -0.5
                w = np.exp(sc - sc.max())
                out[r, i, head] = (w / w.sum()) @ v
    return out


class TestWindow:
    """``window=`` in both kernels and both references: the window
    shorter than a page, one past a page boundary, as wide as a sweep
    chunk, as wide as the context; rows on every edge of the sweep; a
    wide chunk whose lanes' windows start in different chunks."""

    BS, MB, HK, H, D = 8, 40, 2, 4, 32

    def _rows(self, rng, lengths, s, dtype, kv_dtype=None):
        kp, vp, tables, live = _edge_pool(
            rng, lengths=list(lengths), s=s, hk=self.HK, d=self.D,
            BS=self.BS, MB=self.MB, dtype=dtype)
        scales = {}
        if kv_dtype is not None:
            kp, vp, ks, vs = quantize_kv_pages(kp, vp, kv_dtype)
            scales = dict(k_scales=ks, v_scales=vs)
        q = jnp.asarray(rng.normal(size=(len(lengths), s, self.H, self.D)),
                        dtype)
        return q, kp, vp, tables, jnp.asarray(lengths, jnp.int32), \
            scales, live

    @pytest.mark.parametrize("window", [5, 9, 128, 320])
    @pytest.mark.parametrize("s", [1, 4])
    def test_reference_is_the_brute_force(self, s, window):
        rng = np.random.default_rng(40)
        S = self.BS * self.MB
        lengths = [0, 3, 8, 127, 128, 129, 200, S - s]
        q, kp, vp, tables, lens, _, _ = self._rows(rng, lengths, s,
                                                   jnp.float32)
        ref = paged_attention_reference(q, kp, vp, tables, lens,
                                        window=window)
        np.testing.assert_allclose(
            np.asarray(ref), _brute_force(q, kp, vp, tables, lengths,
                                          window), atol=2e-5, rtol=2e-5)

    _FP8 = pytest.mark.skipif(not hasattr(jnp, "float8_e4m3fn"),
                              reason="no float8_e4m3fn in this jax build")

    #: rows whose window starts in a later chunk beside rows that the
    #: window does not cut: the stream hands on between sweeps that
    #: start at chunk 0 and sweeps that start at chunk 1 or 2
    _AFTER_SHORT = [5, 280, 40, 270, 131]

    @pytest.mark.parametrize("s,window,dtype,kv_dtype,batch", _kept_ids([
        (1, 5, jnp.float32, None), (1, 9, jnp.float32, "int8"),
        (1, 128, jnp.float32, None), (1, 130, jnp.bfloat16, None),
        (4, 5, jnp.float32, None), (4, 9, jnp.bfloat16, None),
        (4, 128, jnp.float32, "int8"),
        pytest.param(4, 130, jnp.float32, "fp8", marks=_FP8),
        (32, 5, jnp.float32, None), (32, 9, jnp.float32, "int8"),
        (32, 128, jnp.bfloat16, None), (32, 130, jnp.float32, None)],
        ("window-after-short", 1, 9, jnp.float32, None),
        ("window-after-short", 4, 130, jnp.float32, "int8"),
        ("window-after-short", 32, 9, jnp.float32, None)))
    def test_chunk_kernel(self, s, window, dtype, kv_dtype, batch):
        rng = np.random.default_rng(41)
        S = self.BS * self.MB
        # every edge of the sweep, and cursors that put the first
        # lane's window start and the last lane's in different chunks
        lengths = _edge_lengths(self.BS, S, s) + [
            window + 120, window + 127, window + 128, 2 * 128 + window - 3]
        if batch is not None:
            lengths = self._AFTER_SHORT
        # (a lane farther past the table's end than the window is
        # wide sees no key at all: out of the engine's contract, and
        # garbage of another kind in kernel and reference)
        lengths = [n for n in lengths if n <= S - s]
        q, kp, vp, tables, lens, scales, _ = self._rows(
            rng, lengths, s, dtype, kv_dtype)
        ref = paged_attention_reference(q, kp, vp, tables, lens,
                                        window=window, **scales)
        out = paged_attention(q, kp, vp, tables, lens, window=window,
                              implementation="pallas_interpret", **scales)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=tol, rtol=tol)

    @pytest.mark.parametrize("window,dtype,kv_dtype,batch", _kept_ids([
        (5, jnp.float32, None), (9, jnp.bfloat16, None),
        (128, jnp.float32, None), (5, jnp.float32, "int8"),
        pytest.param(128, jnp.float32, "fp8", marks=_FP8)],
        ("window-after-short", 9, jnp.float32, None),
        ("window-after-short", 130, jnp.float32, "int8")))
    def test_fused_kernel(self, window, dtype, kv_dtype, batch):
        """The windowed attend within tolerance; the write is the
        window's business not at all: pages, codes and scales bitwise
        the reference's on live pages."""
        from apex_tpu.ops.paged_attention import paged_decode_fused
        from apex_tpu.ops.rope import rope_cos_sin

        rng = np.random.default_rng(42)
        S = self.BS * self.MB
        lengths = np.asarray(
            self._AFTER_SHORT if batch is not None else
            [n for n in _edge_lengths(self.BS, S) if n <= S]
            + [window + 127, window + 128, 300], np.int32)
        b = len(lengths)
        q, kp, vp, tables, lens, scales, live = self._rows(
            rng, lengths, 1, dtype, kv_dtype)
        kw = dict(scales)
        if kv_dtype is not None:
            kw["chunk_lens"] = jnp.ones((b,), jnp.int32)
        nk = jnp.asarray(rng.normal(size=(b, 1, self.HK, self.D)), dtype)
        nv = jnp.asarray(rng.normal(size=(b, 1, self.HK, self.D)), dtype)
        cos, sin = rope_cos_sin(S, self.D)
        pc = np.minimum(lengths[:, None], S - 1)
        kw.update(cos_b=jnp.asarray(cos[pc][:, :, None, :]),
                  sin_b=jnp.asarray(sin[pc][:, :, None, :]))
        run = lambda impl: jax.jit(lambda *a: paged_decode_fused(
            *a, max_seq_len=S, window=window, implementation=impl,
            **kw))(q, nk, nv, kp, vp, tables, lens)
        ref, out = run("xla"), run("pallas_interpret")
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(out[0], np.float32), np.asarray(ref[0], np.float32),
            atol=tol, rtol=tol)
        for got, want in zip(out[1:3], ref[1:3]):
            np.testing.assert_array_equal(
                np.asarray(got[:, live], np.float32),
                np.asarray(want[:, live], np.float32))

    @pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
    @pytest.mark.parametrize("s", [1, 4])
    def test_no_window_and_a_window_as_wide_as_the_context(self, impl, s):
        """``window=None`` is bit for bit the call without the
        argument, and a window that holds the whole context masks
        nothing."""
        rng = np.random.default_rng(43)
        S = self.BS * self.MB
        lengths = _edge_lengths(self.BS, S, s)
        q, kp, vp, tables, lens, _, _ = self._rows(rng, lengths, s,
                                                   jnp.float32)
        plain = paged_attention(q, kp, vp, tables, lens,
                                implementation=impl)
        for window in (None, S + self.BS + 3 + s):
            got = paged_attention(q, kp, vp, tables, lens, window=window,
                                  implementation=impl)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(plain))

    def test_window_none_traces_the_kernel_it_always_was(self):
        """No equation of the kernel's body knows of a window when
        there is none: the jaxprs with and without the argument are
        the same text."""
        rng = np.random.default_rng(44)
        q, kp, vp, tables, lens, _, _ = self._rows(rng, [9, 130], 4,
                                                   jnp.float32)
        text = lambda **kw: str(jax.make_jaxpr(
            lambda *a: paged_attention(
                *a, implementation="pallas_interpret", **kw))(
            q, kp, vp, tables, lens))
        assert text() == text(window=None)
        assert text() != text(window=9)

    def test_a_window_below_one_raises(self):
        rng = np.random.default_rng(45)
        q, kp, vp, tables, lens, _, _ = self._rows(rng, [9], 1,
                                                   jnp.float32)
        with pytest.raises(ValueError, match="window"):
            paged_attention(q, kp, vp, tables, lens, window=0)


# --------------------------------------------------------------------- #
# the stream of chunk fetches across rows
# --------------------------------------------------------------------- #
class TestChunkStream:
    """Both kernels fetch ONE stream of chunks over the grid, two
    ahead of the chunk attended, across row boundaries: a row's result
    is what it is alone, and the schedule never refills a slot that is
    still to be read."""

    @pytest.mark.parametrize("fused,kv_dtype,s,batch", [
        (True, None, 1, "chunks-1-2-9"), (True, "int8", 1, "chunks-1-2-9"),
        (True, None, 1, "cow-prefix"), (False, None, 4, "chunks-1-2-9"),
        (False, "int8", 32, "cow-prefix")])
    def test_rows_together_are_the_rows_alone_bitwise(self, fused,
                                                      kv_dtype, s, batch):
        """The batch's outputs, and for the fused kernel its written
        pages and scales, bit for bit those of its rows run one call a
        row (no hand-off), the rows' writes applied in turn."""
        from apex_tpu.ops.paged_attention import paged_decode_fused

        rng = np.random.default_rng(44)
        b_kw = _HANDOFFS[batch]
        lengths = np.minimum(b_kw["lengths"], _HANDOFF_S - s)
        b, hk, h, d, BS = len(lengths), 2, 10, 32, 8
        MB = _HANDOFF_S // BS
        kp, vp, tables = _pool_setup(
            rng, b=b, hk=hk, d=d, NB=b * MB + 1, BS=BS, MB=MB,
            lengths=list(lengths), s=s, dtype=jnp.float32)
        if b_kw.get("share"):
            tables[1, :b_kw["share"]] = tables[0, :b_kw["share"]]
        pool = [kp, vp]
        if kv_dtype is not None:
            pool = list(quantize_kv_pages(kp, vp, kv_dtype))
        q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        nk = jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32)
        lens = jnp.asarray(lengths, jnp.int32)

        def call(rows, pool):
            sc = {} if kv_dtype is None else dict(
                k_scales=pool[2], v_scales=pool[3])
            if not fused:
                return [paged_attention(
                    q[rows], pool[0], pool[1], jnp.asarray(tables[rows]),
                    lens[rows], implementation="pallas_interpret", **sc)]
            if kv_dtype is not None:
                sc["chunk_lens"] = jnp.ones(lens[rows].shape, jnp.int32)
            return list(paged_decode_fused(
                q[rows], nk[rows], nk[rows], pool[0], pool[1],
                jnp.asarray(tables[rows]), lens[rows],
                max_seq_len=_HANDOFF_S, implementation="pallas_interpret",
                **sc))

        together = call(slice(None), pool)
        outs = []
        for r in range(b):
            alone = call(slice(r, r + 1), pool)
            outs.append(alone[0])
            pool = alone[1:] or pool
        np.testing.assert_array_equal(np.asarray(together[0]),
                                      np.concatenate(outs))
        for got, want in zip(together[1:], pool):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("window", [None, 130])
    def test_every_chunk_is_fetched_once_two_ahead(self, window):
        """The kernels' fetch schedule replayed on the host with the
        kernels' own index functions: every row's chunks are started
        once, in the stream's order, into a slot that holds no chunk
        still to be read; each is read from the slot it went to; the
        fused kernel's write-back slot is not refilled before the next
        row (which waits for it) begins."""
        from apex_tpu.ops.paged_attention import (_SLOTS, _row_span,
                                                  _stream_ahead)

        lengths = [100, 200, 1100, 5, 1100, 130, 2048, 0, 7, 900]
        spans = [tuple(int(v) for v in _row_span(
            jnp.int32(n), s=1, bs=8, mb=256, window=window)[1:])
            for n in lengths]
        rows = len(spans)
        held, started, writeback = {}, [], None

        def fetch(row, k, c, slot):
            (f1, n1), (f2, _) = (spans[min(row + on, rows - 1)]
                                 for on in (1, 2))
            on, chunk_no = (int(v) for v in _stream_ahead(
                k, c, spans[row][1], f1, n1, f2))
            if row + on < rows:
                assert slot not in held and slot != writeback
                held[slot] = (row + on, chunk_no)
                started.append((row + on, chunk_no))

        base = 0
        for row, (first, n_chunks) in enumerate(spans):
            writeback = None                 # waited as the row begins
            if row == 0:
                fetch(0, 0, first, 0)
                fetch(0, 1, first, 1)
            for c in range(first, n_chunks):
                slot = (base + c - first) % _SLOTS
                fetch(row, 2, c, (slot + 2) % _SLOTS)
                assert held.pop(slot) == (row, c)
                if c == n_chunks - 1:
                    writeback = slot
            base = (base + n_chunks - first) % _SLOTS
        assert started == [(r, c) for r, (f, n) in enumerate(spans)
                           for c in range(f, n)]
        assert not held
        assert any(f > 0 for f, _ in spans) == (window is not None)

    def test_tensor_parallel_shards_run_the_same_stream(self):
        """A coded pool sharded on its kv heads over two devices: each
        shard runs the kernel over the whole batch on its heads, so its
        stream hands on at the same row boundaries — outputs, pages
        and scales bit for bit the one-device call's."""
        from apex_tpu.ops.paged_attention import paged_decode_fused

        rng = np.random.default_rng(45)
        lengths = _HANDOFFS["chunks-1-2-9"]["lengths"]
        b, hk, h, d, BS = len(lengths), 2, 10, 32, 8
        MB = _HANDOFF_S // BS
        kp, vp, tables = _pool_setup(
            rng, b=b, hk=hk, d=d, NB=b * MB + 1, BS=BS, MB=MB,
            lengths=lengths, s=1, dtype=jnp.float32)
        kp, vp, ks, vs = quantize_kv_pages(kp, vp, "int8")
        kw = dict(k_scales=ks, v_scales=vs,
                  chunk_lens=jnp.ones((b,), jnp.int32))
        q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
        nk = jnp.asarray(rng.normal(size=(b, 1, hk, d)), jnp.float32)
        args = (q, nk, nk, kp, vp, jnp.asarray(tables),
                jnp.asarray(lengths, jnp.int32))
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tensor",))
        one = paged_decode_fused(*args, max_seq_len=_HANDOFF_S,
                                 implementation="pallas_interpret", **kw)
        two = paged_decode_fused(*args, max_seq_len=_HANDOFF_S,
                                 implementation="pallas_interpret",
                                 mesh=mesh, shard_axis="tensor", **kw)
        assert len(one) == len(two) == 5
        for a, b_ in zip(one, two):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
