"""The serving engine's state: slot rows on the device, pages on the host.

Continuous batching needs per-sequence state (each tenant sits at its
own decode position), but TPU-friendly programs need *one* set of
shapes for the process lifetime.  Two pieces resolve that:

Per-slot scalar bookkeeping (active mask, next token, produced count,
token budget, sampling params, rng key) lives in :class:`SlotState` —
plain ``(max_slots,)`` device arrays carried through the jitted step,
NOT static jit arguments, so heterogeneous sampling configs share one
executable.  Admission and release are functional updates of those
rows (:func:`admit_slots`, :func:`release_slot`).

The K/V cache is a pool of fixed-size **pages** shared by every
tenant.  Which page holds which tokens is host state: the refcounted
:class:`BlockAllocator`, the :class:`PrefixTrie` of sharable prompt
pages, and the per-slot block tables and cursors the engine writes
over the cache tree's leaves before every step
(:func:`set_paged_leaves`).  Under a tensor-parallel mesh the pool
leaves shard on ``kv_heads`` and everything else stays replicated
(:func:`paged_pool_shardings`).
"""

from __future__ import annotations

import hashlib
import re
from typing import Any, Dict, List, NamedTuple

import jax
import jax.numpy as jnp

import numpy as np

__all__ = [
    "SlotState",
    "init_slot_state",
    "BlockAllocator",
    "BlockExhausted",
    "blocks_for",
    "set_paged_leaves",
    "PrefixTrie",
    "chain_digests",
]


def _leaf_name(path) -> str:
    """Last key of a tree path (DictKey / GetAttrKey / SequenceKey)."""
    last = path[-1]
    for attr in ("key", "name", "idx"):
        val = getattr(last, attr, None)
        if val is not None:
            return str(val)
    return str(last)


class SlotState(NamedTuple):
    """Per-slot device state — ``(max_slots,)`` arrays, one pytree.

    Sampling params ride here as DEVICE ARRAYS (not static jit args):
    a slot decoding greedily and a slot sampling at ``temperature=1.2,
    top_k=40, top_p=0.9`` run in the same compiled step.  Conventions:
    ``top_k == 0`` disables truncation, ``top_p <= 0`` (or ``>= 1``)
    disables the nucleus filter, ``eos_id == -1`` disables eos
    stopping, and ``rng`` is a per-slot PRNG key so a request's sampled
    tokens are a function of its own seed, independent of co-tenants.
    """

    active: jax.Array        # bool  — slot occupied
    tok: jax.Array           # int32 — next token to feed
    produced: jax.Array      # int32 — tokens produced so far
    budget: jax.Array        # int32 — max_new_tokens for the tenant
    temperature: jax.Array   # float32
    top_k: jax.Array         # int32 — 0 = disabled
    top_p: jax.Array         # float32 — <= 0 or >= 1 = disabled
    eos_id: jax.Array        # int32 — -1 = disabled
    rng: jax.Array           # uint32 (max_slots, 2) — per-slot key


def init_slot_state(max_slots: int) -> SlotState:
    """All-free slot state (inactive slots decode garbage that is
    ignored on the host and overwritten at admission)."""
    z = lambda dt: jnp.zeros((max_slots,), dt)   # noqa: E731
    return SlotState(
        active=z(bool),
        tok=z(jnp.int32),
        produced=z(jnp.int32),
        budget=jnp.ones((max_slots,), jnp.int32),
        temperature=z(jnp.float32),
        top_k=z(jnp.int32),
        top_p=z(jnp.float32),
        eos_id=jnp.full((max_slots,), -1, jnp.int32),
        rng=jnp.zeros((max_slots, 2), jnp.uint32),
    )


def admit_slot(state: SlotState, slot, tok, budget, temperature,
               top_k, top_p, eos_id, seed) -> SlotState:
    """Functional admission of one tenant into ``slot`` (traceable).

    ``seed`` derives the slot's private PRNG key inside the trace, so
    admission stays a single compiled executable for any seed.
    """
    key = jax.random.PRNGKey(seed)
    if key.dtype != jnp.uint32:      # typed-key jax: store the raw bits
        key = jax.random.key_data(key)
    return state._replace(
        active=state.active.at[slot].set(True),
        tok=state.tok.at[slot].set(tok),
        produced=state.produced.at[slot].set(0),
        budget=state.budget.at[slot].set(budget),
        temperature=state.temperature.at[slot].set(temperature),
        top_k=state.top_k.at[slot].set(top_k),
        top_p=state.top_p.at[slot].set(top_p),
        eos_id=state.eos_id.at[slot].set(eos_id),
        rng=state.rng.at[slot].set(key.astype(jnp.uint32)),
    )


def admit_slots(state: SlotState, ints, floats) -> SlotState:
    """Functional admission of every row that ``ints[0]`` marks, in ONE
    application (traceable): a burst of admissions costs the device one
    call and two small host arrays, however many tenants arrive.

    ``ints`` is int32 ``(6, max_slots)``: fresh (0/1), tok, budget,
    top_k, eos_id and the seed's 32 bits; ``floats`` is float32
    ``(2, max_slots)``: temperature, top_p.  A marked row ends exactly
    as :func:`admit_slot` leaves it; the others are untouched.
    """
    fresh = ints[0] != 0
    seeds = jax.lax.bitcast_convert_type(ints[5], jnp.uint32)
    keys = jax.vmap(jax.random.PRNGKey)(seeds)
    if keys.dtype != jnp.uint32:     # typed-key jax: store the raw bits
        keys = jax.random.key_data(keys)

    def put(new, old):
        return jnp.where(fresh, new.astype(old.dtype), old)

    return state._replace(
        active=state.active | fresh,
        tok=put(ints[1], state.tok),
        produced=put(jnp.zeros_like(state.produced), state.produced),
        budget=put(ints[2], state.budget),
        temperature=put(floats[0], state.temperature),
        top_k=put(ints[3], state.top_k),
        top_p=put(floats[1], state.top_p),
        eos_id=put(ints[4], state.eos_id),
        rng=jnp.where(fresh[:, None], keys.astype(jnp.uint32), state.rng),
    )


def release_slot(state: SlotState, slot) -> SlotState:
    """Mark ``slot`` free (traceable)."""
    return state._replace(active=state.active.at[slot].set(False))


__all__ += ["admit_slot", "admit_slots", "release_slot"]


# --------------------------------------------------------------------- #
# paged KV-cache: host-side block pool + device-leaf plumbing
# --------------------------------------------------------------------- #
# leaves of the PAGED cache tree the engine overwrites every step from
# its host allocator (block_tables/cursors per layer; position_index at
# the model level for learned-position models)
_TABLE_LEAF = "block_tables"
_CURSOR_LEAVES = ("cursors", "position_index")
_CHUNK_LENS_LEAF = "chunk_lens"
#: per-slot recurrent state a model may keep beside the pages: the
#: model owns the values (zeroed for a row at cursor 0, advanced over
#: ``chunk_lens`` real lanes), the engine counts the bytes
_RECURRENT_LEAVES = ("ssm_state", "conv_state")
#: an expert layer's per-step report: the assignments each held expert
#: got (``models/afmoe.py``); the model overwrites it every step and the
#: engine reads every layer's back beside the tokens
_EXPERT_COUNTS_LEAF = "expert_counts"


class BlockExhausted(RuntimeError):
    """The paged KV pool has no free blocks left.

    Raised by :meth:`BlockAllocator.alloc`; the engine's step loop
    catches it and preempts a tenant (whose requeue continues from its
    streamed prefix) instead of failing the step.
    """


def blocks_for(tokens: int, block_size: int) -> int:
    """Pages needed to hold ``tokens`` cache positions."""
    return -(-int(tokens) // int(block_size))


class BlockAllocator:
    """Host-side refcounted free list over the physical page pool.

    The pool is sized in TOKENS (``num_blocks × block_size``), shared
    by every tenant — no slot reserves ``max_seq_len`` positions of
    its own.  Physical block 0 is the
    reserved **null page**: unallocated block-table entries point at
    it, pad-token writes land in it, and the position mask keeps its
    contents unreachable — so it is never handed out.

    The allocator counts PAGES and is storage-dtype-agnostic: under a
    quantized pool (``kv_dtype="int8"``/``"fp8"``, ISSUE 8) the same
    page index addresses 1-byte K/V codes plus one fp32 amax scale per
    (kv_head, page) riding the cache beside the block table — a page's
    scale travels with it through sharing, CoW forks, preemption and
    reuse (the write path resets it at the page's first write), so
    nothing below this line changes; only how many tokens the same HBM
    buys does.

    Pages carry a **refcount** (the prefix-sharing substrate, ISSUE 7):
    :meth:`alloc` hands out pages at refcount 1, :meth:`incref` lets a
    second tenant reference the same physical page (a shared read-only
    prompt-prefix block), and :meth:`free` *decrements* — a page
    returns to the free list only when its last reference drops, so a
    hot system prompt's KV is charged to the pool once no matter how
    many tenants map it.  ``blocks_in_use`` stays EXACT under sharing:
    it counts physical pages, never logical references.

    Not thread-safe: the engine-owning thread is the only caller (the
    same single-writer discipline as the engine itself).
    """

    def __init__(self, num_blocks: int, block_size: int):
        if block_size < 1:
            raise ValueError(
                f"block_size must be >= 1, got {block_size}")
        if num_blocks < 2:
            raise ValueError(
                "num_blocks must be >= 2 (block 0 is the reserved "
                f"null page), got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # LIFO free stack: blocks freed together are reused together
        # (keeps a tenant's pages warm in any downstream cache level)
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        #: live refcounts — only allocated pages have an entry
        self._refs: Dict[int, int] = {}

    @property
    def blocks_total(self) -> int:
        """Allocatable pages (the null page is not allocatable)."""
        return self.num_blocks - 1

    @property
    def blocks_free(self) -> int:
        return len(self._free)

    @property
    def blocks_in_use(self) -> int:
        return self.blocks_total - len(self._free)

    @property
    def tokens_total(self) -> int:
        return self.blocks_total * self.block_size

    @property
    def tokens_free(self) -> int:
        return len(self._free) * self.block_size

    @property
    def shared_blocks(self) -> int:
        """Physical pages currently mapped by MORE than one reference
        — the prefix-sharing win gauge (:attr:`blocks_saved` counts
        the pool pages that sharing reclaims).  Snapshots the refcount
        dict first: health probes read this from other threads while
        the engine thread allocates/frees, and iterating a mutating
        dict raises."""
        return sum(1 for r in list(self._refs.values()) if r > 1)

    @property
    def blocks_saved(self) -> int:
        """Pool pages sharing reclaimed: ``Σ (refcount - 1)`` — the
        pages an unshared pool would additionally burn right now
        (snapshot semantics, as :attr:`shared_blocks`)."""
        return sum(r - 1 for r in list(self._refs.values()) if r > 1)

    def refcount(self, block: int) -> int:
        """Live references to ``block`` (0 = free)."""
        return self._refs.get(int(block), 0)

    def alloc(self, n: int) -> List[int]:
        """Take ``n`` pages (each at refcount 1); raises
        :class:`BlockExhausted` (taking none) when fewer than ``n``
        are free — allocation is atomic so a failed extension never
        leaks partial pages."""
        if n < 0:
            raise ValueError(f"n must be >= 0, got {n}")
        if n > len(self._free):
            raise BlockExhausted(
                f"need {n} blocks, {len(self._free)} free "
                f"(pool: {self.blocks_total} × {self.block_size} tok)")
        taken = self._free[-n:] if n else []
        del self._free[len(self._free) - n:]
        for blk in taken:
            self._refs[blk] = 1
        return taken

    def incref(self, block: int) -> int:
        """Add one reference to a LIVE page (prefix sharing: a new
        tenant maps an existing read-only prompt block).  Returns the
        new refcount; raises on a free/out-of-range page — sharing
        dead KV is a caller bug."""
        blk = int(block)
        if blk not in self._refs:
            raise ValueError(
                f"incref of block {blk} which is not allocated")
        self._refs[blk] += 1
        return self._refs[blk]

    def free(self, blocks) -> List[int]:
        """Drop one reference per page; pages whose LAST reference
        dropped return to the pool and are listed in the return value
        (the engine forgets them from its prefix trie).  Decrementing
        a free page — the old double-free — still raises."""
        freed: List[int] = []
        for blk in blocks:
            blk = int(blk)
            if not 1 <= blk < self.num_blocks:
                raise ValueError(
                    f"block {blk} outside the allocatable range "
                    f"[1, {self.num_blocks})")
            refs = self._refs.get(blk)
            if refs is None:
                raise ValueError(f"double free of block {blk}")
            if refs > 1:
                self._refs[blk] = refs - 1
            else:
                del self._refs[blk]
                self._free.append(blk)
                freed.append(blk)
        return freed


# --------------------------------------------------------------------- #
# prefix trie: block-granular prompt-prefix index over live pages
# --------------------------------------------------------------------- #
def chain_digests(tokens: np.ndarray, block_size: int) -> List[bytes]:
    """Chained content digests of every FULL ``block_size`` block of
    ``tokens``: ``digest_i = sha256(digest_{i-1} || block_i_tokens)``.

    The chaining makes each digest identify the whole prefix up to and
    including its block — two prompts share block ``i`` iff they agree
    on every token of blocks ``0..i`` — so a flat dict over digests IS
    a trie walk.  Content-addressed (sha256 over the raw int32 bytes):
    collisions are cryptographically negligible, so digest equality is
    treated as prefix equality.
    """
    tokens = np.ascontiguousarray(tokens, np.int32)
    out: List[bytes] = []
    digest = b"apex-tpu-prefix-v1"
    for i in range(tokens.size // int(block_size)):
        h = hashlib.sha256(digest)
        h.update(tokens[i * block_size:(i + 1) * block_size].tobytes())
        digest = h.digest()
        out.append(digest)
    return out


class PrefixTrie:
    """Digest → physical page index of LIVE read-only prompt blocks.

    The admission-time half of copy-on-write prefix sharing
    (:class:`~apex_tpu.serving.engine.PagedEngine`): a tenant that
    finishes prefilling a full prompt block :meth:`register`\\ s its
    page under the block's chain digest; a later admission
    :meth:`match`\\ es its own prompt's digests against the trie and
    maps the hit pages instead of recomputing (and re-storing) their
    KV.  Entries are removed by :meth:`forget` when the underlying
    page's last reference drops — the trie only ever points at live
    pool pages, so a hit can always be increfed.
    """

    def __init__(self):
        self._by_digest: Dict[bytes, int] = {}
        self._by_block: Dict[int, bytes] = {}

    def __len__(self) -> int:
        return len(self._by_digest)

    def register(self, digest: bytes, block: int) -> bool:
        """Index ``block`` under ``digest``; first writer wins (a
        concurrent tenant prefilling the same prompt keeps its private
        duplicate unregistered).  Returns whether the entry was
        added."""
        if digest in self._by_digest:
            return False
        block = int(block)
        if block in self._by_block:
            # one physical page per digest AND per block: re-keying a
            # live page would leave a stale digest→block entry behind
            return False
        self._by_digest[digest] = block
        self._by_block[block] = digest
        return True

    def forget(self, block: int) -> None:
        """Drop the entry for a page returning to the free list (a
        no-op for unregistered pages)."""
        digest = self._by_block.pop(int(block), None)
        if digest is not None:
            del self._by_digest[digest]

    def holds_block(self, block: int) -> bool:
        """Whether ``block`` is indexed (and therefore read-only for
        its current owner)."""
        return int(block) in self._by_block

    def match(self, digests: List[bytes]) -> List[int]:
        """Longest-prefix hit: the physical pages for the leading run
        of ``digests`` present in the trie (chain digests make any
        hit's whole prefix a hit too)."""
        pages: List[int] = []
        for digest in digests:
            block = self._by_digest.get(digest)
            if block is None:
                break
            pages.append(block)
        return pages


def _tp_spec_for_leaf(name: str, ndim: int, axis: str):
    """PartitionSpec of one paged-cache leaf under tensor-parallel
    serving: the K/V pool leaves shard their ``kv_heads`` dim (at
    ``ndim - 4``: counted from the back, so it holds for the decode
    cache's one leaf a layer, which has no layer axis, and would for a
    stacked one), the per-(kv_head, page) quant-scale leaves
    shard the same dim at ``ndim - 2``, and EVERYTHING else — block
    tables, cursors, chunk_lens, position_index, the SlotState twin —
    is replicated, which is what keeps the engine's host-side
    allocator / refcount / trie logic mesh-oblivious."""
    import jax.sharding as shd

    if name in ("paged_key", "paged_value"):
        dim = ndim - 4
    elif name in ("key_scales", "value_scales"):
        dim = ndim - 2
    else:
        return shd.PartitionSpec()
    spec = [None] * ndim
    spec[dim] = axis
    return shd.PartitionSpec(*spec)


def paged_pool_shardings(cache: Any, mesh, axis: str) -> Any:
    """``NamedSharding`` tree matching ``cache``: pool/scale leaves
    sharded on kv_heads over ``axis``, the rest replicated (see
    :func:`_tp_spec_for_leaf`)."""
    import jax.sharding as shd

    def f(path, leaf):
        return shd.NamedSharding(
            mesh, _tp_spec_for_leaf(_leaf_name(path),
                                    jnp.ndim(leaf), axis))

    return jax.tree_util.tree_map_with_path(f, cache)


def shard_paged_cache(cache: Any, mesh, axis: str) -> Any:
    """Place a paged cache tree on the replica's mesh (host-side
    ``device_put`` at engine construction)."""
    return jax.device_put(cache, paged_pool_shardings(cache, mesh,
                                                      axis))


def constrain_paged_cache(cache: Any, mesh, axis: str) -> Any:
    """The in-trace twin of :func:`shard_paged_cache`:
    ``with_sharding_constraint`` every leaf to the same placement, so
    the jitted step's OUTPUT cache lands exactly where its input was
    committed — shardings reach a fixed point and the retrace guards
    (budget 1) never see a second signature."""
    return jax.tree.map(jax.lax.with_sharding_constraint, cache,
                        paged_pool_shardings(cache, mesh, axis))


__all__ += ["recurrent_state_bytes", "expert_count_leaves", "expert_counts"]
__all__ += ["paged_pool_shardings", "shard_paged_cache",
            "constrain_paged_cache"]


def recurrent_state_bytes(cache: Any) -> int:
    """Bytes of the per-slot recurrent-state leaves of a cache tree
    (arrays or ShapeDtypeStructs), every layer's; 0 for a model that
    keeps none."""
    return sum(
        int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
        if _leaf_name(path) in _RECURRENT_LEAVES)


def expert_count_leaves(cache: Any) -> list:
    """The ``expert_counts`` leaves of a cache tree (arrays or
    ShapeDtypeStructs), one an expert layer, in layer order."""
    found = {
        int(re.search(r"layer_(\d+)", jax.tree_util.keystr(path)).group(1)):
        leaf for path, leaf
        in jax.tree_util.tree_flatten_with_path(cache)[0]
        if _leaf_name(path) == _EXPERT_COUNTS_LEAF}
    return [found[i] for i in sorted(found)]


def expert_counts(cache: Any):
    """Every expert layer's ``expert_counts``, stacked ``(expert
    layers, experts held)`` — or None for a model that has none."""
    leaves = expert_count_leaves(cache)
    return jnp.stack(leaves) if leaves else None


def set_paged_leaves(cache: Any, tables, cursors,
                     chunk_lens=None) -> Any:
    """Overwrite the paged cache tree's ``block_tables`` and cursor
    leaves (``cursors`` / ``position_index``) with the engine's
    host-authoritative values, broadcast to each leaf's shape (every
    layer keeps its own leaves — ``models.transformer.decode_layers``
    — and all of them share one logical→physical mapping because the
    per-layer pools are parallel).  ``chunk_lens`` (per-row REAL lane
    counts for the
    coming mixed step) overwrites the quantized pool's ``chunk_lens``
    leaf the same way — the write path routes lanes past it to the
    null page so pad-lane amax never reaches a live page scale; pass
    ``None`` to leave the leaf untouched (non-engine callers keep the
    model's every-lane-real default, and unquantized pools have no
    such leaf).  K/V pool leaves — and, under a quantized pool, the
    ``key_scales``/``value_scales`` per-page amax leaves that ride
    beside them — pass through untouched: the model's write path owns
    them.
    """
    tables = jnp.asarray(tables, jnp.int32)
    cursors = jnp.asarray(cursors, jnp.int32)
    if chunk_lens is not None:
        chunk_lens = jnp.asarray(chunk_lens, jnp.int32)

    def fix(path, leaf):
        name = _leaf_name(path)
        if name == _TABLE_LEAF:
            return jnp.broadcast_to(tables, leaf.shape).astype(leaf.dtype)
        if name in _CURSOR_LEAVES:
            return jnp.broadcast_to(cursors, leaf.shape).astype(leaf.dtype)
        if name == _CHUNK_LENS_LEAF and chunk_lens is not None:
            return jnp.broadcast_to(chunk_lens,
                                    leaf.shape).astype(leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)
