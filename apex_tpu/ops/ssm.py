"""State-space (Mamba-2 / SSD) mixer ops for the serving path.

The recurrence, per slot ``b`` and head ``h`` (``P`` = head width,
``N`` = state width, ``B`` and ``C`` shared by the heads of a group)::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (outer) B_t     S: (P, N)
    y_t = S_t C_t

Three ops, each with an XLA reference of the same signature (what runs
off a TPU, and the oracle the kernels are tested against):

- :func:`ssm_decode_update` — width 1, the serving engines' steady
  decode: every slot's ``(H, P, N)`` float32 state is read and written
  once, in place (the state is aliased through the kernel).  Memory
  bound: the state is the traffic.
- :func:`ssd_chunk_scan` — width ``s`` (the mixed step's chunk): takes
  the state a row arrives with, returns the state it leaves with and the
  ``s`` outputs, all inside one kernel (the chunk's quadratic form on
  the MXU, the state read and written once).
- :func:`causal_conv_step` — the depthwise causal convolution of width
  ``K`` in front of the recurrence, with its rolling ``K - 1`` rows of
  history a slot.  Plain XLA on every platform: it moves a thousandth of
  the recurrence's bytes.

Ragged rows.  ``chunk_lens[b]`` lanes of row ``b`` are real; the lanes
beyond them move neither the state nor the conv window (their ``dt`` is
taken as 0: decay 1, input 0) and their outputs are garbage nobody
reads.  ``reset[b]`` starts row ``b`` from zero state and an empty conv
window, whatever the buffers hold (a slot's previous tenant): the
serving engine sets it for a row whose cursor is 0.

Kernel layout notes (TPU).  The state block is ``(heads, P, N)`` with
``N`` on lanes.  What has to broadcast ALONG lanes (``dt * x`` and the
decay, one value a row of ``P``) is handed to the kernels column-wise —
``(P, heads)`` blocks the wrapper builds from arrays a thousand times
smaller than the state — so that no transpose happens in the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import resolve_impl

__all__ = ["ssm_decode_update", "ssd_chunk_scan", "causal_conv_step",
           "ssm_envelope_ok"]

_HIGHEST = jax.lax.Precision.HIGHEST
#: heads a grid step: 8 x (128, 256) float32 = 1 MiB of state in, 1 out
_HEAD_BLOCK = 8
#: widest chunk the scan kernel takes (its (s, s) forms sit in vregs)
_MAX_CHUNK = 128


def ssm_envelope_ok(heads: int, groups: int, p: int, n: int,
                    width: int = 1) -> bool:
    """Shapes the Pallas kernels take: state tiles aligned to the
    (8, 128) float32 tile, head blocks inside one B/C group, a chunk
    of at most ``_MAX_CHUNK`` lanes that fills whole sublanes."""
    if groups < 1 or heads % groups:
        return False
    return (p % 8 == 0 and n % 128 == 0
            and (heads // groups) % _head_block(heads, groups) == 0
            and (width == 1 or (width % 8 == 0 and width <= _MAX_CHUNK)))


def _head_block(heads: int, groups: int) -> int:
    """Heads a grid step: never more than one group's."""
    return min(_HEAD_BLOCK, heads // groups)


def _masked_dt(dt, chunk_lens):
    """``dt`` (b, s, H) with the lanes past a row's real ones at 0."""
    s = dt.shape[1]
    real = jnp.arange(s, dtype=jnp.int32)[None, :] < chunk_lens[:, None]
    return jnp.where(real[:, :, None], dt.astype(jnp.float32), 0.0)


# ------------------------------------------------------------------ XLA
def _ssd_scan_xla(x, dt, a, bmat, cmat, state, chunk_lens, reset):
    """The recurrence as a plain scan over the chunk's lanes."""
    b, s, h, p = x.shape
    g = bmat.shape[2]
    rep = h // g
    dtm = _masked_dt(dt, chunk_lens)
    s0 = jnp.where(reset[:, None, None, None], 0.0, state)
    xf = x.astype(jnp.float32)
    bf = jnp.repeat(bmat.astype(jnp.float32), rep, axis=2)   # (b,s,h,n)
    cf = jnp.repeat(cmat.astype(jnp.float32), rep, axis=2)

    def step(st, lane):
        xt, dtt, bt, ct = lane
        decay = jnp.exp(dtt * a)                             # (b, h)
        st = st * decay[:, :, None, None] \
            + (dtt[:, :, None] * xt)[..., None] * bt[:, :, None, :]
        return st, jnp.sum(st * ct[:, :, None, :], axis=-1)

    lanes = tuple(jnp.moveaxis(v, 1, 0) for v in (xf, dtm, bf, cf))
    new, y = jax.lax.scan(step, s0, lanes)
    return jnp.moveaxis(y, 0, 1), new


# --------------------------------------------------------------- decode
def _decode_kernel(col_ref, b_ref, c_ref, s_ref, y_ref, o_ref, *, hb):
    """One slot, ``hb`` heads of one group.  ``col_ref`` holds, a head,
    a column of ``dt * x`` and a column of the decay (0 = start from
    zero state)."""
    b_row = b_ref[0, 0]                                      # (1, N)
    c_row = c_ref[0, 0]
    for h in range(hb):
        dtx = col_ref[0, 0, :, h:h + 1]                      # (P, 1)
        decay = col_ref[0, 0, :, hb + h:hb + h + 1]
        st = s_ref[0, h].astype(jnp.float32)                 # (P, N)
        # a reset row's decay is 0: the select keeps whatever the
        # buffer held (another tenant's state, not finite at worst) out
        st = jnp.where(decay > 0.0, st * decay, 0.0) + dtx * b_row
        o_ref[0, h] = st
        y_ref[0, 0, :, h:h + 1] = jnp.sum(st * c_row, axis=1,
                                          keepdims=True)


def _columns(per_head, hb):
    """(b, H, P) -> (b, H/hb, P, hb): a head's values down a column."""
    b, h, p = per_head.shape
    return per_head.reshape(b, h // hb, hb, p).transpose(0, 1, 3, 2)


def _scalar_columns(per_head, p, hb):
    """(b, H) -> (b, H/hb, P, hb): a head's one value down its column."""
    b, h = per_head.shape
    return _columns(jnp.broadcast_to(per_head[:, :, None], (b, h, p)), hb)


def _group_rows(mat, h, hb):
    """(b, G, N) -> (b, H/hb, 1, N): the group's row of each head
    block (a block never straddles two groups)."""
    g = mat.shape[1]
    idx = (jnp.arange(h // hb) * hb) // (h // g)
    return mat.astype(jnp.float32)[:, idx][:, :, None, :]


def _decode_pallas(x, dt, a, bmat, cmat, state, chunk_lens, reset,
                   interpret):
    b, h, p = x.shape
    n = state.shape[-1]
    hb = _head_block(h, bmat.shape[1])
    dtm = jnp.where((chunk_lens > 0)[:, None], dt.astype(jnp.float32),
                    0.0)                                     # (b, H)
    decay = jnp.where(reset[:, None], 0.0, jnp.exp(dtm * a))
    dtx = dtm[:, :, None] * x.astype(jnp.float32)            # (b, H, P)
    cols = jnp.concatenate([_columns(dtx, hb),
                            _scalar_columns(decay, p, hb)],
                           axis=-1)                          # (b,H/hb,P,2hb)
    nblk = h // hb
    with jax.named_scope("ssm_decode_update"):
        y_cols, new = pl.pallas_call(
            functools.partial(_decode_kernel, hb=hb),
            grid=(b, nblk),
            in_specs=[
                pl.BlockSpec((1, 1, p, 2 * hb), lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, 1, n), lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, 1, n), lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec((1, hb, p, n), lambda i, j: (i, j, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, p, hb), lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec((1, hb, p, n), lambda i, j: (i, j, 0, 0)),
            ],
            out_shape=[jax.ShapeDtypeStruct((b, nblk, p, hb), jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, jnp.float32)],
            # the state moves in place: a slot's 4 MB a layer is read
            # and written once, never copied
            input_output_aliases={3: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(cols, _group_rows(bmat, h, hb), _group_rows(cmat, h, hb), state)
    y = y_cols.transpose(0, 1, 3, 2).reshape(b, h, p)
    return y, new


def ssm_decode_update(x, dt, a, bmat, cmat, state, chunk_lens, reset, *,
                      implementation=None):
    """One step of the recurrence for every slot.

    ``x`` (b, H, P); ``dt`` (b, H), already positive (softplus applied);
    ``a`` (H,) negative; ``bmat``, ``cmat`` (b, G, N); ``state``
    (b, H, P, N) float32; ``chunk_lens`` (b,) — a row with 0 stands
    still; ``reset`` (b,) bool.  Returns ``(y, new_state)``, ``y``
    (b, H, P) float32 without the ``D`` skip.
    """
    b, h, p = x.shape
    ok = state.dtype == jnp.float32 and ssm_envelope_ok(
        h, bmat.shape[1], p, state.shape[-1])
    impl = resolve_impl(implementation, pallas_ok=ok,
                        op="ssm_decode_update")
    a = a.astype(jnp.float32)
    if impl == "xla":
        y, new = _ssd_scan_xla(x[:, None], dt[:, None], a, bmat[:, None],
                               cmat[:, None], state,
                               jnp.minimum(chunk_lens, 1), reset)
        return y[:, 0], new
    return _decode_pallas(x, dt, a, bmat, cmat, state, chunk_lens, reset,
                          impl == "pallas_interpret")


# ---------------------------------------------------------------- chunk
def _chunk_kernel(x_ref, b_ref, c_ref, l_ref, col_ref, dec_ref, s_ref,
                  y_ref, o_ref, *, hb, p):
    """One slot, ``hb`` heads of one group, ``s`` lanes.

    ``l_ref[h]`` is the (s, s) lower-triangular ``exp(cum_t - cum_u) *
    dt_u``; ``col_ref`` holds a head's column of ``exp(cum_t)`` (what
    the arriving state still weighs at lane t) and of ``exp(cum_s -
    cum_u) * dt_u`` (what lane u still weighs at the chunk's end);
    ``dec_ref`` a column of the whole chunk's decay and one that is 0
    where the row starts from zero state."""
    bm = b_ref[0].astype(jnp.float32)                        # (s, N)
    cm = c_ref[0].astype(jnp.float32)
    # C B^T: which lanes' inputs each lane's output reads, a group
    gram = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)
    for h in range(hb):
        xh = x_ref[0, :, h * p:(h + 1) * p].astype(jnp.float32)  # (s, P)
        decay = dec_ref[0, 0, :, h:h + 1]                    # (P, 1)
        keep = dec_ref[0, 0, :, hb + h:hb + h + 1]
        # a reset row keeps nothing of what the buffer held (another
        # tenant's state, not finite at worst): a select, not a product
        st = jnp.where(keep > 0.0, s_ref[0, h], 0.0)         # (P, N)
        grow = col_ref[0, 0, :, h:h + 1]                     # (s, 1)
        left = col_ref[0, 0, :, hb + h:hb + h + 1]
        y = jnp.dot(l_ref[0, h] * gram, xh, precision=_HIGHEST,
                    preferred_element_type=jnp.float32)
        y += grow * jax.lax.dot_general(
            cm, st, (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)              # (s, P)
        y_ref[0, :, h * p:(h + 1) * p] = y
        # sum_u left_u x_u (outer) B_u: the lanes contracted away
        add = jax.lax.dot_general(
            xh * left, bm, (((0,), (0,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)              # (P, N)
        o_ref[0, h] = st * decay + add


def _chunk_pallas(x, dt, a, bmat, cmat, state, chunk_lens, reset,
                  interpret):
    b, s, h, p = x.shape
    g, n = bmat.shape[2], bmat.shape[3]
    hb = _head_block(h, g)
    nblk = h // hb
    per_group = (h // g) // hb        # head blocks a group
    dtm = _masked_dt(dt, chunk_lens)                         # (b, s, H)
    cum = jnp.cumsum(dtm * a, axis=1)                        # <= 0
    total = cum[:, -1]                                       # (b, H)
    cum_h = cum.transpose(0, 2, 1)                           # (b, H, s)
    tril = jnp.tril(jnp.ones((s, s), bool))
    # exponent <= 0 on and below the diagonal; masked above it BEFORE
    # the exp so that nothing overflows
    lmat = jnp.exp(jnp.where(tril, cum_h[..., :, None]
                             - cum_h[..., None, :], -jnp.inf)) \
        * dtm.transpose(0, 2, 1)[..., None, :]               # (b,H,s,s)
    grow = jnp.exp(cum_h)                                    # (b, H, s)
    left = jnp.exp(total[:, :, None] - cum_h) * dtm.transpose(0, 2, 1)
    cols = jnp.concatenate([_columns(grow, hb), _columns(left, hb)],
                           axis=-1)                          # (b,H/hb,s,2hb)
    keep = jnp.broadcast_to(1.0 - reset.astype(jnp.float32)[:, None],
                            (b, h))
    dec = jnp.concatenate([_scalar_columns(jnp.exp(total), p, hb),
                           _scalar_columns(keep, p, hb)], axis=-1)
    x2 = x.reshape(b, s, h * p)
    b2 = bmat.transpose(0, 2, 1, 3).reshape(b * g, s, n)
    c2 = cmat.transpose(0, 2, 1, 3).reshape(b * g, s, n)
    grp = lambda i, j: (i * g + j // per_group, 0, 0)
    with jax.named_scope("ssm_chunk_scan"):
        y2, new = pl.pallas_call(
            functools.partial(_chunk_kernel, hb=hb, p=p),
            grid=(b, nblk),
            in_specs=[
                pl.BlockSpec((1, s, hb * p), lambda i, j: (i, 0, j)),
                pl.BlockSpec((1, s, n), grp),
                pl.BlockSpec((1, s, n), grp),
                pl.BlockSpec((1, hb, s, s), lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, s, 2 * hb), lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, p, 2 * hb), lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec((1, hb, p, n), lambda i, j: (i, j, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, s, hb * p), lambda i, j: (i, 0, j)),
                pl.BlockSpec((1, hb, p, n), lambda i, j: (i, j, 0, 0)),
            ],
            out_shape=[jax.ShapeDtypeStruct((b, s, h * p), jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, jnp.float32)],
            input_output_aliases={6: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(x2, b2, c2, lmat, cols, dec, state)
    return y2.reshape(b, s, h, p), new


def ssd_chunk_scan(x, dt, a, bmat, cmat, state, chunk_lens, reset, *,
                   implementation=None):
    """``s`` steps of the recurrence for every slot, as one chunk.

    ``x`` (b, s, H, P); ``dt`` (b, s, H) positive; ``a`` (H,) negative;
    ``bmat``, ``cmat`` (b, s, G, N); ``state`` (b, H, P, N) float32;
    ``chunk_lens`` (b,) real lanes a row; ``reset`` (b,) bool.  Returns
    ``(y, new_state)``: ``y`` (b, s, H, P) float32 without the ``D``
    skip, the state after each row's last real lane.
    """
    b, s, h, p = x.shape
    ok = state.dtype == jnp.float32 and ssm_envelope_ok(
        h, bmat.shape[2], p, state.shape[-1], width=s) and s > 1
    impl = resolve_impl(implementation, pallas_ok=ok, op="ssd_chunk_scan")
    a = a.astype(jnp.float32)
    if impl == "xla":
        return _ssd_scan_xla(x, dt, a, bmat, cmat, state, chunk_lens,
                             reset)
    return _chunk_pallas(x, dt, a, bmat, cmat, state, chunk_lens, reset,
                         impl == "pallas_interpret")


# ----------------------------------------------------------------- conv
def causal_conv_step(u, window, weight, bias, chunk_lens, reset):
    """Depthwise causal convolution over a chunk, with its history.

    ``u`` (b, s, C); ``window`` (b, K-1, C), the last ``K - 1`` real
    inputs of each row (oldest first); ``weight`` (K, C), the last row
    multiplying the current input; ``bias`` (C,).  Returns ``(out,
    new_window)``: ``out`` (b, s, C) in float32 before the activation,
    the window rolled over the row's ``chunk_lens`` real lanes only.
    """
    k = weight.shape[0]
    s = u.shape[1]
    window = jnp.where(reset[:, None, None], 0, window).astype(u.dtype)
    full = jnp.concatenate([window, u], axis=1)              # (b, K-1+s, C)
    w = weight.astype(jnp.float32)
    out = bias.astype(jnp.float32) + sum(
        full[:, j:j + s].astype(jnp.float32) * w[j] for j in range(k))
    # rows chunk_lens .. chunk_lens + K - 2 of ``full``: the K - 1
    # inputs before the row's next real one
    idx = chunk_lens[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None]
    new = jnp.take_along_axis(full, idx[:, :, None], axis=1)
    return out, new
