"""Autoregressive generation over the KV-cached decoder models.

The reference is a training library (no inference engine), but "switch
frameworks and find everything you need" includes sampling from the
model you just trained.  This module is the minimal TPU-idiomatic
decode loop over :class:`~apex_tpu.models.gpt.GPTModel` /
:class:`~apex_tpu.models.llama.LlamaModel`'s ``decode=True`` path:

- the KV cache is a plain pytree (``init_cache`` — all-zero arrays of
  shape ``(b, max_seq_len, kv_heads, head_dim)`` per layer; GQA shrinks
  it by ``num_heads/num_kv_heads``),
- prefill runs the prompt through the model's ``decode=True`` chunk
  path — one call for short prompts, a ``lax.scan`` of fixed-size
  chunks above ``prefill_chunk`` tokens (long prompts: the chunk path
  uses the flash kernel / blocked cache attention, so a 32k prompt
  compiles and its score temps stay O(chunk), see
  ``models/transformer.py::ParallelAttention``),
- the per-token loop is a ``lax.scan`` inside one ``jit`` — no host
  round-trips between tokens; greedy or temperature/top-k sampling via
  ``jax.random.categorical``.

Static-shape discipline: prompts share one length (pad-free; ragged
batches should be bucketed by the caller) and ``max_new_tokens`` is
static.  The compiled loop is cached per ``(model, max_new_tokens,
temperature, top_k, top_p, eos_id, prefill_chunk)`` signature (jit
handles the shape axis), so repeated same-shape calls do not retrace.

The building blocks — :func:`apply_decode` (one cached-decode model
application), :func:`prefill_tokens` (single-call or chunked prefill)
and :func:`sample_logits` (greedy / temperature / top-k / nucleus
top-p) — are public:
``apex_tpu.serving`` composes them into the continuous-batching engine,
so the two inference surfaces share one prefill and one sampling
definition.

Memoization: results are keyed on a *value signature* of the model —
``(type(model), model.cfg)`` — never on the instance.  Flax modules
hash and compare by field values, so an equal-config model revives a
cached entry, and (the round-1 regression) the memos do not pin up to
64 model instances for the process lifetime: compiled runners hold the
model through a weakref that :func:`generate` re-binds on every call.
"""

from __future__ import annotations

import functools
import weakref
from typing import Any, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "init_cache",
    "cache_shapes",
    "generate",
    "apply_decode",
    "prefill_tokens",
    "sample_logits",
]

# bound on each memo below, matching the old lru_cache(maxsize=64);
# eviction is insertion-order (FIFO) — generation signatures are
# long-lived, LRU precision buys nothing here
_MEMO_MAX = 64


def _memo_put(memo: dict, key, value) -> None:
    if key not in memo and len(memo) >= _MEMO_MAX:
        memo.pop(next(iter(memo)))
    memo[key] = value


def _model_signature(model):
    """Hashable value-identity of ``model`` that does NOT reference the
    instance.

    Flax modules hash/compare by (type, dataclass fields), so the
    signature is ``(type, *field values)`` over every module field
    except the tree-wiring ones (``parent``/``name``) — capturing field
    *values* (configs, flags) keeps two equal models on one memo entry
    without referencing either instance.  ``cfg`` alone would NOT be
    enough: a module carrying extra fields (say a ``use_flash: bool``
    beside its cfg) must not collide with its sibling.  A model with
    unhashable field values (arrays) falls back to an
    :class:`_IdentityKey`: identity-scoped, but still collectible (a
    plain ``weakref.ref`` would not do — its hash delegates to the
    unhashable referent — and a raw ``id()`` key could be revived by
    an id-reusing new object after collection).
    """
    import dataclasses

    try:
        fields = tuple(
            (f.name, getattr(model, f.name))
            for f in dataclasses.fields(model)
            if f.name not in ("parent", "name"))
        key = (type(model),) + fields
        hash(key)
        return key
    except (TypeError, AttributeError):
        return _IdentityKey(model)


class _IdentityKey:
    """Identity-scoped memo key for unhashable models: hashes by
    ``id``, compares equal only while the referent is alive and
    identical — a dead entry can never be revived by an id-reusing
    new object, it just ages out of the bounded memo."""

    __slots__ = ("_id", "_ref")

    def __init__(self, model):
        self._id = id(model)
        self._ref = weakref.ref(model)

    def __hash__(self):
        return self._id

    def __eq__(self, other):
        if not isinstance(other, _IdentityKey):
            return NotImplemented
        mine, theirs = self._ref(), other._ref()
        return mine is not None and mine is theirs


_shape_memo: dict = {}


def _cache_shapes(model, batch_size: int, prompt_len: int):
    """Memoized cache structure: one abstract trace of ``model.init``
    per (model-signature, batch) key — repeated generate() calls skip
    the whole-model eval_shape."""
    key = (_model_signature(model), batch_size, prompt_len)
    out = _shape_memo.get(key)
    if out is None:
        ids = jnp.zeros((batch_size, prompt_len), jnp.int32)
        out = jax.eval_shape(
            functools.partial(model.init, decode=True),
            jax.random.PRNGKey(0), ids)["cache"]
        _memo_put(_shape_memo, key, out)
    return out


def cache_shapes(model, batch_size: int, *, prompt_len: int = 1):
    """``ShapeDtypeStruct`` pytree of ``model``'s decode cache.

    The abstract twin of :func:`init_cache` — ``apex_tpu.serving``
    builds its slot-stacked cache pool from this structure.
    """
    return _cache_shapes(model, batch_size, prompt_len)


def init_cache(model, batch_size: int, *, prompt_len: int = 1,
               rng=None) -> Any:
    """Build an all-zero KV cache pytree for ``model``.

    Uses ``jax.eval_shape`` over ``model.init`` to learn the cache
    structure without materializing parameters; every cache leaf's init
    value is zeros (arrays) or 0 (indices), so zeros-from-shape IS the
    initialized cache.  ``rng`` is accepted for API symmetry but never
    materialized (the trace is abstract).
    """
    del rng
    shapes = _cache_shapes(model, batch_size, prompt_len)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def apply_decode(model, variables, cache, ids, lanes=None):
    """One ``decode=True`` model application over ``cache``.

    Returns ``(logits, new_cache)``.  ``variables`` is the param dict
    WITHOUT a ``"cache"`` entry; the cache rides separately so callers
    can thread it functionally (scan carries, slot pools).  ``lanes``
    (the paged engine's mixed step): the model computes those lanes of
    ``ids`` only and returns each row's last one's logits.
    """
    extra = {} if lanes is None else {"lanes": lanes}
    logits, upd = model.apply(
        {**variables, "cache": cache}, ids,
        deterministic=True, decode=True, mutable=["cache"], **extra)
    return logits, upd["cache"]


def prefill_tokens(model, variables, cache, prompt_ids,
                   prefill_chunk: int = 0):
    """Run ``prompt_ids`` (b, plen) through the decode chunk path.

    Returns ``(last_logits, cache)`` with ``last_logits`` of shape
    ``(b, vocab)`` — the logits of the final prompt position.  With
    ``prefill_chunk`` > 0 and a longer prompt, the prompt runs as
    fixed-size chunks through the model's decode chunk path under one
    ``lax.scan`` (the leading remainder chunk keeps every scanned chunk
    the same static size); only the running last-token logits ride the
    carry, so nothing O(prompt·vocab) materializes.
    """
    b, plen = prompt_ids.shape
    if prefill_chunk and plen > prefill_chunk:
        C = prefill_chunk
        r = plen % C or C
        logits, cache = apply_decode(model, variables, cache,
                                     prompt_ids[:, :r])
        last = logits[:, -1]
        n = (plen - r) // C
        if n:
            chunks = prompt_ids[:, r:].reshape(b, n, C).swapaxes(0, 1)

            def pre(carry, chunk):
                cache, _ = carry
                lg, cache = apply_decode(model, variables, cache, chunk)
                return (cache, lg[:, -1]), None

            (cache, last), _ = jax.lax.scan(pre, (cache, last), chunks)
        return last, cache
    # prefill: one pass over the prompt populates every cache
    logits, cache = apply_decode(model, variables, cache, prompt_ids)
    return logits[:, -1], cache


def sample_logits(logits, key, *, temperature: float,
                  top_k: Optional[int] = None,
                  top_p: Optional[float] = None):
    """Sample next tokens from last-position ``logits`` (b, vocab).

    ``temperature`` / ``top_k`` / ``top_p`` are PYTHON statics (part
    of the jit signature): ``temperature <= 0`` is pure fp32 argmax
    (no rng use), otherwise logits/temperature are sampled, optionally
    truncated to the ``top_k`` highest-scoring tokens and/or the
    nucleus — the smallest set of tokens whose probability mass
    reaches ``top_p`` (Holtzman et al.; the HF default sampler).
    Filter order matches HF: top-k first, then top-p over the
    truncated distribution; ``top_p=1.0`` (or None) disables the
    nucleus filter exactly.  The serving engine's per-slot
    *array*-parameter variant of the same math lives in
    ``apex_tpu.serving.engine`` (device-carried params, one
    executable for mixed configs).
    """
    logits = logits.astype(jnp.float32)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / temperature
    asc = None
    if top_k is not None:
        asc = jnp.sort(scaled, axis=-1)                  # ascending
        kth = asc[:, -top_k][:, None]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    if top_p is not None and top_p < 1.0:
        if asc is None:
            desc = jnp.sort(scaled, axis=-1)[:, ::-1]    # descending
        else:
            # reuse the top-k sort: apply the SAME `< kth` criterion
            # that masked `scaled` (value-based, so k-th-boundary
            # ties land identically) instead of re-sorting the vocab
            rev = asc[:, ::-1]
            desc = jnp.where(rev < kth, -1e30, rev)
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep a token iff the mass BEFORE it is < top_p (the argmax
        # token is always kept); threshold = smallest kept logit
        keep = cum - probs < top_p
        thresh = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                         keepdims=True)
        scaled = jnp.where(scaled < thresh, -1e30, scaled)
    return jax.random.categorical(key, scaled).astype(jnp.int32)


class _Runner:
    """A compiled generate loop bound to its model *by value*.

    ``run`` is the jitted prefill+scan loop; its python body resolves
    the model through ``_ref`` at trace time, so the memo holds no
    strong reference to any module instance (the old lru_cache pinned
    up to 64 models for the process lifetime).  :func:`generate`
    re-binds ``_ref`` on every call: all models mapping to one memo key
    are value-equal, so whichever live instance is bound traces the
    identical computation, and an entry whose original instance was
    collected is revived by the next equal-config call.
    """

    __slots__ = ("_ref", "run")

    def bind(self, model) -> None:
        self._ref = weakref.ref(model)

    def model(self):
        m = self._ref()
        if m is None:           # pragma: no cover — generate binds first
            raise RuntimeError(
                "generate runner traced after its model was collected; "
                "call generate() with a live model")
        return m


_run_memo: dict = {}


def _compiled_run(model, max_new_tokens: int, temperature: float,
                  top_k: Optional[int], eos_id: Optional[int],
                  prefill_chunk: int = 0,
                  top_p: Optional[float] = None) -> _Runner:
    """One jitted prefill+scan loop per static signature.

    Keyed on the model's value signature (see :func:`_model_signature`);
    jit's own cache handles the (batch, prompt_len) shape axis on top.
    """
    key = (_model_signature(model), max_new_tokens, temperature,
           top_k, eos_id, prefill_chunk, top_p)
    runner = _run_memo.get(key)
    if runner is not None:
        runner.bind(model)
        return runner
    runner = _Runner()

    # the caller-supplied cache is freshly zero-initialized per
    # generate() call and dead after it — donate it so XLA reuses its
    # HBM for the updated cache instead of holding both copies live
    # (num_layers · b · S · kv_heads · d · 2 leaves; at llama_1b
    # b=32/S=8192 that is the difference between one and two ~2.7 GB
    # cache footprints).  Donation works only through input→output
    # aliasing, so run() must RETURN the final cache (generate()
    # discards it) — donating without the matching output would be
    # silently ignored with an unusable-donation warning.
    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(variables, cache, prompt_ids, rng):
        model = runner.model()
        b = prompt_ids.shape[0]
        last, cache = prefill_tokens(model, variables, cache,
                                     prompt_ids, prefill_chunk)
        rng, key = jax.random.split(rng)
        tok = sample_logits(last, key, temperature=temperature,
                            top_k=top_k, top_p=top_p)
        # eos latches only on PRODUCED tokens — a prompt-contained
        # eos_id (bos/document-separator usage) must not kill the batch
        done0 = jnp.zeros((b,), bool)

        def step(carry, _):
            cache, tok, done, rng = carry
            logits, cache = apply_decode(model, variables, cache,
                                         tok[:, None])
            rng, key = jax.random.split(rng)
            nxt = sample_logits(logits[:, -1], key,
                                temperature=temperature, top_k=top_k,
                                top_p=top_p)
            if eos_id is not None:
                done = done | (tok == eos_id)
                nxt = jnp.where(done, eos_id, nxt)
            return (cache, nxt, done, rng), tok

        (cache, last_tok, _, _), toks = jax.lax.scan(
            step, (cache, tok, done0, rng), None,
            length=max_new_tokens - 1)
        toks = jnp.moveaxis(toks, 0, 1)              # (b, n-1)
        return jnp.concatenate(
            [prompt_ids, toks, last_tok[:, None]], axis=1), cache

    runner.run = run
    runner.bind(model)
    _memo_put(_run_memo, key, runner)
    return runner


def generate(model, params, prompt_ids, *, max_new_tokens: int,
             temperature: float = 0.0, top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             rng=None, eos_id: Optional[int] = None,
             prefill_chunk: Optional[int] = None):
    """Generate ``max_new_tokens`` continuations of ``prompt_ids``.

    ``prompt_ids``: ``(batch, prompt_len)`` int32 (one shared length —
    bucket ragged prompts before calling).  ``temperature=0`` is greedy
    argmax; otherwise logits/temperature are sampled (optionally top-k
    and/or nucleus (``top_p``) truncated — ``top_p=1.0`` is exactly
    plain sampling, the HF-default convention).  After ``eos_id`` is
    *produced* a sequence keeps emitting ``eos_id`` (static shapes —
    no early exit under jit); eos tokens already in the prompt are
    ignored.

    ``prefill_chunk``: process the prompt in fixed-size chunks of this
    many tokens (bounds prefill score temps to O(chunk·window) /
    O(chunk·prefix)).  ``None`` = auto: single-call prefill up to 8k
    prompts, 2048-token chunks above.  Pass ``0`` to force single-call.

    Returns ``(batch, prompt_len + max_new_tokens)`` token ids.
    """
    prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
    b, prompt_len = prompt_ids.shape
    if getattr(model.cfg, "kv_cache", "dense") == "paged":
        raise ValueError(
            "generate() drives the dense KV-cache; a kv_cache='paged' "
            "model needs the block tables the serving engine owns — "
            "serve it through apex_tpu.serving.PagedEngine (dense and "
            "paged compute the same function, so build the generate() "
            "twin with dataclasses.replace(cfg, kv_cache='dense'))")
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    max_len = model.cfg.max_seq_len
    if prompt_len + max_new_tokens > max_len:
        raise ValueError(
            f"prompt_len ({prompt_len}) + max_new_tokens "
            f"({max_new_tokens}) exceeds the model's max_seq_len "
            f"({max_len}) — the KV cache cannot hold the sequence")
    if temperature > 0.0 and rng is None:
        raise ValueError("sampling (temperature>0) needs an rng key")
    if top_k is not None and not 1 <= top_k <= model.cfg.vocab_size:
        # an out-of-range top_k silently clamps under jit (negative
        # sort index -> minimum logit -> truncation silently disabled)
        raise ValueError(
            f"top_k must be in [1, vocab_size={model.cfg.vocab_size}], "
            f"got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if prefill_chunk is None:
        prefill_chunk = 2048 if prompt_len > 8192 else 0
    elif prefill_chunk < 0:
        raise ValueError(
            f"prefill_chunk must be >= 0, got {prefill_chunk}")
    rng = jax.random.PRNGKey(0) if rng is None else rng
    cache = init_cache(model, b)
    runner = _compiled_run(
        model, int(max_new_tokens), float(temperature),
        None if top_k is None else int(top_k),
        None if eos_id is None else int(eos_id),
        int(prefill_chunk),
        None if top_p is None else float(top_p))
    # the final cache rides along purely as the donation alias target
    ids, _final_cache = runner.run(dict(params), cache, prompt_ids, rng)
    return ids
