"""Main-path kernels compiled for a described TPU v5e chip.

No chip is attached here: ``get_topology_desc`` describes one and the
TPU compiler that ships with jax compiles for it, raising what the
chip's compiler would raise (tiling, fast-memory and partitioning
refusals that interpret mode cannot see).  A compile that passes is
not a chip run — ``chip_smoke.py`` is the run.

Rules this file keeps (on-chip-measurement guide §2): the topology is
described inside a module-scoped, non-autouse fixture of THIS file —
only one process may load the TPU library, and under xdist every
worker imports every test file — so nothing topology-related happens
at import time, in ``skipif`` or in ``parametrize`` arguments; compiles
run in the test's own process, with the persistent compile cache off
(an entry compiled for a described chip cannot be read back here).
All chip compiles live in this one file so one worker owns the library.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from apex_tpu.ops import (fused_attention, fused_layer_norm,
                          fused_rms_norm)
from apex_tpu.ops import fused_sampling as fs
from apex_tpu.ops.paged_attention import (paged_attention,
                                          paged_decode_fused,
                                          paged_write)

bf16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compile_for_chip(topo):
    """``compile_for_chip(fn, *shapes)`` -> compiled text, for chip 0."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    return run


# ----------------------------------------------------------- norms
@pytest.mark.parametrize("op,rows,width", [
    ("layer_norm", 8192, 1024),       # BERT-Large b16 x s512 rows
    ("rms_norm", 2048, 4096),         # Mistral-7B hidden
])
def test_norm_fwd_bwd(compile_for_chip, op, rows, width):
    if op == "layer_norm":
        def loss(x, w, b):
            return jnp.sum(fused_layer_norm(
                x, w, b, implementation="pallas").astype(jnp.float32))
        shapes = [((rows, width), bf16), ((width,), jnp.float32),
                  ((width,), jnp.float32)]
        argnums = (0, 1, 2)
    else:
        def loss(x, w):
            return jnp.sum(fused_rms_norm(
                x, w, implementation="pallas").astype(jnp.float32))
        shapes = [((rows, width), bf16), ((width,), jnp.float32)]
        argnums = (0, 1)
    text = compile_for_chip(jax.value_and_grad(loss, argnums), *shapes)
    assert "tpu_custom_call" in text


# ------------------------------------------------------- flash attn
@pytest.mark.parametrize("b,s,h,hk,d,window", [
    (16, 512, 16, 16, 64, None),      # BERT-Large
    (1, 8192, 32, 8, 128, 4096),      # Mistral-7B, banded causal
])
def test_flash_attention_fwd_bwd(compile_for_chip, b, s, h, hk, d,
                                 window):
    causal = window is not None

    def loss(q, k, v):
        return jnp.sum(fused_attention(
            q, k, v, causal=causal, window=window,
            implementation="pallas").astype(jnp.float32))

    text = compile_for_chip(
        jax.value_and_grad(loss, (0, 1, 2)),
        ((b, s, h, d), bf16), ((b, s, hk, d), bf16),
        ((b, s, hk, d), bf16))
    assert "tpu_custom_call" in text


# ------------------------------------------- paged pool (Mistral-7B)
B, H, HK, D = 16, 32, 8, 128          # slots, q heads, kv heads, head dim
MAX_SEQ = 4096
POOLS = [32768, 65536]                # tokens; the serve cells hold 65536


def _pool(bs, dtype, pool_tokens):
    nb = pool_tokens // bs + 1
    return (((HK, nb, bs, D), dtype), ((HK, nb, bs, D), dtype),
            ((B, MAX_SEQ // bs), jnp.int32), ((B,), jnp.int32)), nb


@pytest.mark.parametrize("s", [1, 32])        # decode, prefill chunk
@pytest.mark.parametrize("bs", [16, 128])     # engine default, MXU-wide
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("pool_tokens", POOLS)
def test_paged_attention(compile_for_chip, s, bs, kv, pool_tokens):
    quant = kv == "int8"
    pool, nb = _pool(bs, jnp.int8 if quant else bf16, pool_tokens)
    shapes = [((B, s, H, D), bf16), *pool]
    if quant:
        shapes += [((HK, nb), jnp.float32)] * 2

    def fn(q, kp, vp, bt, ln, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_attention(q, kp, vp, bt, ln, k_scales=ks,
                               v_scales=vs, implementation="pallas")

    assert "tpu_custom_call" in compile_for_chip(fn, *shapes)


@pytest.mark.parametrize("rope", [False, True])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("pool_tokens", POOLS)
def test_paged_decode_fused(compile_for_chip, rope, kv, pool_tokens):
    bs = 16
    quant = kv == "int8"
    pool, nb = _pool(bs, jnp.int8 if quant else bf16, pool_tokens)
    shapes = [((B, 1, H, D), bf16), ((B, 1, HK, D), bf16),
              ((B, 1, HK, D), bf16), *pool]
    n_rope = 2 if rope else 0
    shapes += [((B, 1, 1, D // 2), jnp.float32)] * n_rope
    if quant:
        shapes += [((HK, nb), jnp.float32)] * 2 + [((B,), jnp.int32)]

    def fn(q, nk, nv, kp, vp, bt, ln, *rest):
        kw = {}
        if rope:
            kw.update(cos_b=rest[0], sin_b=rest[1])
        if quant:
            ks, vs, cl = rest[n_rope:]
            kw.update(k_scales=ks, v_scales=vs, chunk_lens=cl)
        return paged_decode_fused(q, nk, nv, kp, vp, bt, ln,
                                  max_seq_len=MAX_SEQ,
                                  implementation="pallas", **kw)

    assert "tpu_custom_call" in compile_for_chip(fn, *shapes)


def _write_shapes(b, hk, pool_tokens, s, dtype, bs=16):
    nb = pool_tokens // bs + 1
    return [((b, s, hk, D), dtype)] * 2 + [((hk, nb, bs, D), dtype)] * 2 \
        + [((b, s), jnp.int32)] * 2


def _write_in_place(k, v, kp, vp, phys, off):
    return paged_write(k, v, kp, vp, phys, off, implementation="pallas")


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("s", [32, 5])        # prefill chunk, verify
def test_paged_write(compile_for_chip, s, kv):
    text = compile_for_chip(_write_in_place, *_write_shapes(
        B, HK, POOLS[-1], s, jnp.int8 if kv == "int8" else bf16))
    assert "tpu_custom_call" in text
    assert "paged_write" in text


# ------------------- Falcon-H1-34B: 20 / 4 heads, the recurrent state
FB, FH, FHK = 64, 20, 4               # slots; 5 query heads a KV head
F_SEQ, F_POOL = 2048, 81920
M_H, M_G, M_P, M_N = 32, 2, 128, 256  # mixer heads, groups, d_head, d_state


def _falcon_pool(bs=16):
    nb = F_POOL // bs + 1
    return (((FHK, nb, bs, D), bf16), ((FHK, nb, bs, D), bf16),
            ((FB, F_SEQ // bs), jnp.int32), ((FB,), jnp.int32))


@pytest.mark.parametrize("s", [1, 32])
def test_paged_attention_five_query_heads_a_kv_head(compile_for_chip, s):
    def fn(q, kp, vp, bt, ln):
        return paged_attention(q, kp, vp, bt, ln, implementation="pallas")

    assert "tpu_custom_call" in compile_for_chip(
        fn, ((FB, s, FH, D), bf16), *_falcon_pool())


def test_paged_write_at_the_falcon_cells_pool(compile_for_chip):
    text = compile_for_chip(_write_in_place, *_write_shapes(
        FB, FHK, F_POOL, 32, bf16))
    assert "tpu_custom_call" in text


def test_paged_decode_fused_five_query_heads_a_kv_head(compile_for_chip):
    def fn(q, nk, nv, kp, vp, bt, ln, cos, sin):
        return paged_decode_fused(q, nk, nv, kp, vp, bt, ln,
                                  max_seq_len=F_SEQ, cos_b=cos, sin_b=sin,
                                  implementation="pallas")

    assert "tpu_custom_call" in compile_for_chip(
        fn, ((FB, 1, FH, D), bf16), ((FB, 1, FHK, D), bf16),
        ((FB, 1, FHK, D), bf16), *_falcon_pool(),
        *[((FB, 1, 1, D // 2), jnp.float32)] * 2)


# ------- Trinity-Large: 48 / 8 heads, window and full layers, experts
TB, TH, THK = 48, 48, 8               # slots; 6 query heads a KV head
T_SEQ, T_POOL, T_WINDOW = 8192, 122880, 4096


def _trinity_pool(dtype=bf16, bs=16):
    nb = T_POOL // bs + 1
    return (((THK, nb, bs, D), dtype), ((THK, nb, bs, D), dtype),
            ((TB, T_SEQ // bs), jnp.int32), ((TB,), jnp.int32)), nb


@pytest.mark.parametrize("s", [1, 32])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("window", [None, T_WINDOW])
def test_paged_attention_window_and_full_layers(compile_for_chip, s, kv,
                                                window):
    quant = kv == "int8"
    pool, nb = _trinity_pool(jnp.int8 if quant else bf16)
    shapes = [((TB, s, TH, D), bf16), *pool]
    if quant:
        shapes += [((THK, nb), jnp.float32)] * 2

    def fn(q, kp, vp, bt, ln, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_attention(q, kp, vp, bt, ln, k_scales=ks,
                               v_scales=vs, window=window,
                               implementation="pallas")

    assert "tpu_custom_call" in compile_for_chip(fn, *shapes)


@pytest.mark.parametrize("kind", ["window", "full"])
def test_paged_decode_fused_window_and_full_layers(compile_for_chip,
                                                   kind):
    """A window layer rotates and reads from its window's start; a
    full layer does neither."""
    pool, _ = _trinity_pool()
    shapes = [((TB, 1, TH, D), bf16), ((TB, 1, THK, D), bf16),
              ((TB, 1, THK, D), bf16), *pool]
    if kind == "window":
        shapes += [((TB, 1, 1, D // 2), jnp.float32)] * 2

    def fn(q, nk, nv, kp, vp, bt, ln, *rope):
        kw = dict(cos_b=rope[0], sin_b=rope[1], window=T_WINDOW) \
            if rope else {}
        return paged_decode_fused(q, nk, nv, kp, vp, bt, ln,
                                  max_seq_len=T_SEQ,
                                  implementation="pallas", **kw)

    assert "tpu_custom_call" in compile_for_chip(fn, *shapes)


@pytest.mark.parametrize("rows", [256, 4224])  # decode, mixed step
def test_expert_products_at_the_cells_shapes(compile_for_chip, rows):
    """32 held experts of 3072 x 3072: the gate/up product and the
    down product over ``rows`` sorted assignments."""
    from apex_tpu.ops.expert_gmm import expert_gmm

    def fn(x, w_in, w_down, counts):
        y = expert_gmm(x, w_in, counts, implementation="pallas")
        return expert_gmm(y[:, :3072], w_down, counts,
                          implementation="pallas")

    text = compile_for_chip(
        fn, ((rows, 3072), bf16), ((32, 3072, 6144), bf16),
        ((32, 3072, 3072), bf16), ((32,), jnp.int32))
    assert text.count("tpu_custom_call") >= 2 and "expert_gmm" in text


@pytest.mark.parametrize("s", [1, 32])        # decode update, mixed step
def test_ssm_kernels_at_the_cells_shapes(compile_for_chip, s):
    from apex_tpu.ops import ssm

    state = ((FB, M_H, M_P, M_N), jnp.float32)
    rows = [((FB,), jnp.int32), ((FB,), jnp.bool_)]
    if s == 1:
        def fn(x, dt, a, bm, cm, st, lens, reset):
            return ssm.ssm_decode_update(x, dt, a, bm, cm, st, lens,
                                         reset, implementation="pallas")
        shapes = [((FB, M_H, M_P), bf16), ((FB, M_H), jnp.float32),
                  ((M_H,), jnp.float32), ((FB, M_G, M_N), bf16),
                  ((FB, M_G, M_N), bf16), state, *rows]
        name = "ssm_decode_update"
    else:
        def fn(x, dt, a, bm, cm, st, lens, reset):
            return ssm.ssd_chunk_scan(x, dt, a, bm, cm, st, lens, reset,
                                      implementation="pallas")
        shapes = [((FB, s, M_H, M_P), bf16), ((FB, s, M_H), jnp.float32),
                  ((M_H,), jnp.float32), ((FB, s, M_G, M_N), bf16),
                  ((FB, s, M_G, M_N), bf16), state, *rows]
        name = "ssm_chunk_scan"
    text = compile_for_chip(fn, *shapes)
    # the scope names the kernel's instruction: what the trace shows
    assert "tpu_custom_call" in text and f"%{name}." in text


# ------------------- the serve engine's step programs, whole (PR 33)
# family, config of its cell, layers, width: width 1 at the cells'
# depth, the mixed step at 2 layers (its findings are a layer's)
STEP_PROGRAMS = [
    ("mistral", "mistral_7b_l8", 8, 1), ("mistral", "mistral_7b_l8", 2, 32),
    ("falcon_h1", "falcon_h1_34b_l4", 4, 1),
    ("falcon_h1", "falcon_h1_34b_l4", 2, 32),
    # every kind of layer: dense window, expert window x 3, expert full
    ("afmoe", "trinity_large_l5_e32", 5, 1),
    ("afmoe", "trinity_large_l5_e32", 5, 32),
]
#: what may hold a whole pool or state: the program's arguments and
#: results, and the Pallas kernels, which alias theirs
_IN_PLACE = ("parameter", "tuple", "get-tuple-element", "bitcast",
             "custom-call")


def _cell_engine(family, config, layers):
    """The cell's ``PagedEngine`` at ``layers`` layers, over shapes
    alone: built under ``eval_shape``, so neither weights nor pool are
    ever allocated (its ``cache`` and ``state`` hold what the trace
    left there, good for their shapes)."""
    import json
    import pathlib

    from apex_tpu.models import (AfmoeConfig, AfmoeModel,
                                 FalconH1Config, FalconH1Model,
                                 LlamaConfig, LlamaModel)
    from apex_tpu.serving import PagedEngine

    c = json.loads((pathlib.Path(__file__).resolve().parents[1]
                    / "benchmarks" / "configs" / f"{config}.json"
                    ).read_text())
    c["num_hidden_layers"] = layers
    kw = dict(dtype=bf16, param_dtype=bf16)
    if family == "falcon_h1":
        model = FalconH1Model(FalconH1Config.from_hf(c, **kw))
    elif family == "afmoe":
        model = AfmoeModel(AfmoeConfig.from_hf(c, **kw))
    else:
        model = LlamaModel(LlamaConfig(
            vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
            num_layers=layers, num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"],
            ffn_hidden_size=c["intermediate_size"],
            max_seq_len=c["max_position_embeddings"],
            layernorm_eps=c["rms_norm_eps"], rope_base=c["rope_theta"],
            **kw))
    params = {"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]}
    server = c["serve"]["server"]
    made = []
    jax.eval_shape(lambda: made.append(PagedEngine(
        model, params, max_slots=server["max_slots"],
        pool_tokens=server["pool_tokens"])))
    return made[0], params


def _moved(text, sizes):
    """``{(opcode, type): count}`` over the instructions of the
    compiled text (fused computations included) whose result holds one
    of ``sizes`` elements and is not held in place."""
    import collections
    import math
    import re

    found = collections.Counter()
    for line in text.splitlines():
        m = re.search(r" = (.*?) ([a-z][a-z\-]*)\(", line)
        if m is None or m.group(2) in _IN_PLACE:
            continue
        for dtype, dims in re.findall(r"\b([a-z]+\d+)\[([\d,]+)\]",
                                      m.group(1)):
            if math.prod(map(int, dims.split(","))) in sizes:
                found[m.group(2), f"{dtype}[{dims}]"] += 1
    return dict(found)


@pytest.mark.parametrize("family,config,layers,width", STEP_PROGRAMS)
def test_serve_step_programs_copy_no_cache(topo, monkeypatch, family,
                                           config, layers, width):
    """A decode application does not scan over its cache, so the
    compiled step holds no copy, slice or update of a pool or of the
    recurrent state around the kernels that write them in place."""
    # "auto" resolves as it does on the chip: the kernels where their
    # envelopes admit the call, XLA elsewhere
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    engine, params = _cell_engine(family, config, layers)
    assert engine._chunk == 32 or width == 1
    one_chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    slots = engine.max_slots
    step = engine._decode if width == 1 else engine._prefill
    off = np.zeros((slots,), bool)
    compiled = step.lower(*on_chip((
        params, engine.cache, engine.state, engine._packed(
            np.zeros((slots, width), np.int32),
            np.ones((slots,), np.int32), off, off)))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text

    big = [a for path, a in
           jax.tree_util.tree_flatten_with_path(engine.cache)[0]
           if path[-1].key in ("paged_key", "paged_value", "ssm_state")]
    # a leaf a layer, none stacked
    assert len(big) == (3 if family == "falcon_h1" else 2) * layers
    a_layer = {a.size for a in big}
    stacked = {n * layers for n in a_layer}
    assert not _moved(text, stacked)
    moved = _moved(text, a_layer)
    if width == 1:
        assert not moved, moved
        assert "paged_write" not in text
        cache_bytes = sum(a.size * a.dtype.itemsize
                          for a in jax.tree.leaves(engine.cache))
        temp = compiled.memory_analysis().temp_size_in_bytes
        # the scan's temporaries exceeded the cache they copied
        assert temp < cache_bytes / 2, (temp, cache_bytes)
    else:
        # the chunk write moves the touched pages through its aliased
        # pools (the XLA scatter it replaced transposed each pool and
        # back, four pool-shaped copies a layer)
        assert not moved, moved
        assert text.count("paged_write") >= layers


# --------------------------------------------------- fused sampling
def _sample_with_the_kernel(logits, keys, t, k, p):
    return fs.fused_sample(logits, keys, t, k, p,
                           implementation="pallas")


def _largest_admitted_vocab(rows, dtype):
    vocab = 128
    while fs.pallas_envelope_ok(rows, vocab * 2, dtype, vocab * 2):
        vocab *= 2
    return vocab


@pytest.mark.parametrize("vocab,width", [
    (32000, 1), (32000, 4),           # Mistral-7B decode / spec verify
    (None, 1),                        # the envelope's own upper edge
])
def test_fused_sample(compile_for_chip, vocab, width):
    rows = B
    if vocab is None:
        vocab = _largest_admitted_vocab(rows, bf16)
        assert vocab >= 32768
    assert fs.pallas_envelope_ok(rows * width, vocab, bf16, vocab)
    lead = (rows,) if width == 1 else (rows, width)
    text = compile_for_chip(
        _sample_with_the_kernel, (lead + (vocab,), bf16),
        (lead + (2,), jnp.uint32),
        ((rows,), jnp.float32), ((rows,), jnp.int32),
        ((rows,), jnp.float32))
    assert "tpu_custom_call" in text


def test_explicit_pallas_outside_envelope_raises(compile_for_chip):
    """What the envelope refuses, an explicit ``"pallas"`` must refuse
    too — never the reference under the kernel's name."""
    rows, vocab = B, 2 * _largest_admitted_vocab(B, bf16)
    assert not fs.pallas_envelope_ok(rows, vocab, bf16, vocab)
    with pytest.raises(ValueError, match="envelope"):
        compile_for_chip(
            _sample_with_the_kernel, ((rows, vocab), bf16),
            ((rows, 2), jnp.uint32),
            ((rows,), jnp.float32), ((rows,), jnp.int32),
            ((rows,), jnp.float32))
