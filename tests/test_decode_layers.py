"""A ``decode=True`` application never scans over its cache (PR 33):
``models/transformer.py::decode_layers`` runs a scanned stack's layers
as a Python loop over slices of the STACKED parameters, a cache subtree
a layer.

What is held here, for Llama paged, Llama dense and Falcon-H1 tiny:

- the ``params`` tree keeps its stacked form (``layers/layer`` with a
  leading layer axis), whether ``init`` ran with ``decode`` or without;
- the cache tree of ``cache_shapes`` holds ``num_layers`` subtrees, no
  leaf with a layer axis, every leaf under a name ``serving/cache.py``
  knows;
- logits and updated cache are BITWISE those of an unrolled twin fed
  the unstacked parameters when jitted (to rounding when eager);
- ``PagedEngine`` serves the stack within its retrace budgets of 1.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import (FalconH1Config, FalconH1Model, LlamaConfig,
                             LlamaModel)
from apex_tpu.models.falcon_h1 import FalconH1Block
from apex_tpu.models.generate import (apply_decode, cache_shapes,
                                      init_cache)
from apex_tpu.serving import PagedEngine, Request, Scheduler
from apex_tpu.serving import cache as slot_cache

LEAF_NAMES = {
    "paged_key", "paged_value", "key_scales", "value_scales",
    "block_tables", "cursors", "chunk_lens", "ssm_state", "conv_state",
    "cached_key", "cached_value", "cache_index", "slot_positions"}
PAGED = dict(kv_cache="paged", kv_block_size=8, kv_pool_blocks=9)
BATCH, WIDTH = 2, 4


def _models(which):
    """``(scanned model, unrolled twin)``; the twin is ``None`` for
    Falcon-H1, whose blocks the test applies one by one itself."""
    if which == "falcon_h1":
        return FalconH1Model(FalconH1Config.tiny(**PAGED)), None
    kw = PAGED if which == "llama_paged" else {}
    cfg = LlamaConfig.tiny(scan_layers=True, **kw)
    return (LlamaModel(cfg),
            LlamaModel(dataclasses.replace(cfg, scan_layers=False)))


def _stack(params):
    """The subtree that holds ``layers`` (under ``transformer`` for the
    zoo's models, at the root for Falcon-H1)."""
    return params.get("transformer", params)


def _unstacked(params, num_layers):
    """The twin's parameters: ``layers/layer`` sliced into
    ``layer_{i}`` children, everything else as it is."""
    params = nn.meta.unbox(params)
    stack = dict(_stack(params))
    stacked = stack.pop("layers")["layer"]
    for i in range(num_layers):
        stack[f"layer_{i}"] = jax.tree.map(lambda a: a[i], stacked)
    return ({**params, "transformer": stack} if "transformer" in params
            else stack)


def _falcon_twin(cfg, flat, cache, ids):
    """Falcon-H1 with its blocks applied one by one to the unstacked
    parameters ``flat``, between the model's own embedding, norm and
    head, so only the stack differs."""

    class Twin(nn.Module):
        @nn.compact
        def __call__(self, ids):
            from apex_tpu.models.falcon_h1 import _scaled
            from apex_tpu.models.transformer import _norm
            from apex_tpu.transformer.layers import (
                ColumnParallelLinear, VocabParallelEmbedding)

            x = VocabParallelEmbedding(
                num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="embedding")(ids)
            x = _scaled(x.astype(cfg.dtype), cfg.embedding_multiplier)
            for i in range(cfg.num_layers):
                x = FalconH1Block(cfg, name=f"layer_{i}")(x, decode=True)
            x = _norm(cfg, "final_norm")(x).astype(cfg.dtype)
            logits = ColumnParallelLinear(
                features=cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="lm_head")(x)
            return _scaled(logits, cfg.lm_head_multiplier)

    logits, upd = Twin().apply({"params": flat, "cache": cache}, ids,
                               mutable=["cache"])
    return logits, upd["cache"]


@pytest.fixture(scope="module", params=["llama_paged", "llama_dense",
                                        "falcon_h1"])
def case(request):
    model, twin = _models(request.param)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, model.cfg.vocab_size, size=(BATCH, WIDTH)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    return request.param, model, twin, params, ids


def test_params_stay_stacked_under_layers_layer(case):
    which, model, twin, params, ids = case
    n = model.cfg.num_layers
    stacked = nn.meta.unbox(_stack(params)["layers"]["layer"])
    assert not any(k.startswith("layer_") for k in _stack(params))
    assert all(a.shape[0] == n for a in jax.tree.leaves(stacked))
    # a layer's own tree, with the layer axis in front of every leaf
    if twin is not None:
        one = twin.init(jax.random.PRNGKey(0), ids)["params"]
        one = nn.meta.unbox(one["transformer"]["layer_0"])
    else:
        one = nn.meta.unbox(FalconH1Block(model.cfg).init(
            jax.random.PRNGKey(0),
            jnp.zeros((BATCH, WIDTH, model.cfg.hidden_size)))["params"])
    assert jax.tree.structure(one) == jax.tree.structure(stacked)
    assert jax.tree.map(lambda a: (n,) + a.shape, one) \
        == jax.tree.map(lambda a: a.shape, stacked)
    # the boxes name the layer axis (None) first, then the layer's own
    boxed = _stack(params)["layers"]["layer"]["attention"]["qkv_proj"]
    assert boxed["kernel"].names == (None, None, "tensor")


def test_init_under_decode_makes_the_same_parameters(case):
    which, model, twin, params, ids = case
    both = model.init(jax.random.PRNGKey(0), ids, decode=True)
    assert set(both) == {"params", "cache"}
    assert jax.tree.structure(both["params"]) \
        == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(both["params"]),
                    jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert "layers" not in _stack(both["cache"])


def test_cache_is_one_subtree_a_layer_without_a_layer_axis(case):
    which, model, twin, params, ids = case
    n = model.cfg.num_layers
    shapes = cache_shapes(model, BATCH)
    stack = _stack(shapes)
    assert sorted(stack) == [f"layer_{i}" for i in range(n)]
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    assert {path[-1].key for path, _ in flat} <= LEAF_NAMES
    if twin is not None:
        # the unrolled stack's very tree: a cache fits either model
        assert cache_shapes(twin, BATCH) == shapes
    cfg = model.cfg
    want = {"llama_dense": {
        "cached_key": (BATCH, cfg.max_seq_len, cfg.kv_heads,
                       cfg.head_dim),
        "cache_index": ()}}.get(which, {
            "paged_key": (cfg.kv_heads, 9, 8, cfg.head_dim),
            "block_tables": (BATCH, cfg.max_seq_len // 8),
            "cursors": (BATCH,)})
    if which == "falcon_h1":
        want["ssm_state"] = (BATCH, cfg.mamba_n_heads, cfg.mamba_d_head,
                             cfg.mamba_d_state)
        want["conv_state"] = (BATCH, cfg.mamba_d_conv - 1,
                              cfg.conv_channels)
    for i in range(n):
        layer = jax.tree_util.tree_flatten_with_path(
            stack[f"layer_{i}"])[0]
        got = {path[-1].key: leaf.shape for path, leaf in layer}
        assert {k: got[k] for k in want} == want


@pytest.mark.parametrize("jitted", [True, False])
def test_decode_is_the_unrolled_twin(case, jitted):
    """Two applications (a chunk, then one token on the updated cache)
    so that the second reads what the first wrote.  Jitted, as every
    engine and ``generate()`` apply the model, the two are one program
    after inlining and agree BITWISE; an eager application compiles each
    layer whole (``decode_layers`` jits the layer) where the twin runs
    operation by operation, so there they agree to rounding."""
    same = np.testing.assert_array_equal if jitted else (
        lambda a, b, err_msg="": np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-5, err_msg=err_msg))
    which, model, twin, params, ids = case
    n = model.cfg.num_layers
    flat = _unstacked(params, n)
    if twin is not None:
        def twin_step(cache, ids):
            return apply_decode(twin, {"params": flat}, cache, ids)
    else:
        def twin_step(cache, ids):
            return _falcon_twin(model.cfg, flat, cache, ids)

    def step(cache, ids):
        return apply_decode(model, {"params": params}, cache, ids)

    if jitted:
        step, twin_step = jax.jit(step), jax.jit(twin_step)
    cache = init_cache(model, BATCH)
    if which != "llama_dense":
        # every row owns its own pages; the first page is the null one
        tables = 1 + jnp.arange(BATCH * 4, dtype=jnp.int32).reshape(
            BATCH, 4)
        blank = jnp.zeros((BATCH, model.cfg.max_seq_len // 8), jnp.int32)
        cache = slot_cache.set_paged_leaves(
            cache, blank.at[:, :4].set(tables), jnp.zeros((BATCH,)))
    theirs = cache
    for width, cursor in ((WIDTH, 0), (1, WIDTH)):
        if which != "llama_dense":
            fix = lambda c: slot_cache.set_paged_leaves(
                c, _stack(c)["layer_0"]["attention"]["block_tables"],
                jnp.full((BATCH,), cursor))
            cache, theirs = fix(cache), fix(theirs)
        feed = ids[:, :width]
        logits, cache = step(cache, feed)
        want, theirs = twin_step(theirs, feed)
        same(np.asarray(logits), np.asarray(want))
        assert jax.tree.structure(cache) == jax.tree.structure(theirs)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(cache)[0],
                jax.tree.leaves(theirs)):
            same(np.asarray(a), np.asarray(b),
                 err_msg=jax.tree_util.keystr(path))
    assert float(jnp.abs(logits).max()) > 0


@pytest.mark.parametrize("which", ["llama", "falcon_h1"])
def test_paged_engine_keeps_its_retrace_budgets(which):
    model, _ = _models("llama_dense" if which == "llama" else which)
    params = {"params": model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]}
    engine = PagedEngine(model, params, max_slots=2, block_size=8,
                         prefill_chunk=8, pool_tokens=128)
    names = {path[-1].key for path, _ in
             jax.tree_util.tree_flatten_with_path(engine.cache)[0]}
    assert names <= LEAF_NAMES
    assert sorted(_stack(engine.cache)) == [
        f"layer_{i}" for i in range(model.cfg.num_layers)]
    sched = Scheduler(engine)
    rng = np.random.default_rng(1)
    reqs = [sched.submit(Request(
        prompt=rng.integers(0, model.cfg.vocab_size,
                            size=(L,)).astype(np.int32),
        max_new_tokens=4)) for L in (11, 3, 8)]
    sched.drain()
    assert all(len(r.tokens) == 4 for r in reqs)
    assert engine.trace_counts == {"decode_step": 1, "prefill_step": 1,
                                   "admit": 1, "release": 1}
    assert engine.blocks_in_use == 0
