"""State-space mixer ops: both Pallas kernels in interpret mode against
their XLA references and against the benchmark reference's plain scan
(``benchmarks/lib/reference/falcon_h1.py``), with a state that arrives,
ragged rows, rows that restart, and chunkings that must agree."""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import ssm

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

B, S, H, G, P, N = 4, 8, 4, 2, 8, 128     # two groups, two heads each
IMPLS = ["xla", "pallas_interpret"]


def inputs(seed=0, b=B, s=S):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (b, s, H, P)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, H)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7))
    bm = jax.random.normal(k[3], (b, s, G, N)).astype(jnp.bfloat16)
    cm = jax.random.normal(k[4], (b, s, G, N)).astype(jnp.bfloat16)
    st = jax.random.normal(k[5], (b, H, P, N), jnp.float32)
    return x, dt, a, bm, cm, st


def plain_scan(x, dt, a, bm, cm, st, lens, reset):
    """Row by row, lane by lane, in numpy float64: the recurrence as
    the module docstring writes it."""
    x, dt, bm, cm = (np.asarray(v, np.float64) for v in (x, dt, bm, cm))
    a = np.asarray(a, np.float64)
    st = np.array(st, np.float64)
    y = np.zeros(x.shape)
    for r in range(x.shape[0]):
        if reset[r]:
            st[r] = 0.0
        for t in range(int(lens[r])):
            for h in range(H):
                g = h // (H // G)
                st[r, h] = st[r, h] * np.exp(dt[r, t, h] * a[h]) \
                    + dt[r, t, h] * np.outer(x[r, t, h], bm[r, t, g])
                y[r, t, h] = st[r, h] @ cm[r, t, g]
    return y, st


RAGGED = (np.array([0, 1, 5, 8], np.int32),
          np.array([False, True, False, True]))


@pytest.mark.parametrize("impl", IMPLS)
def test_chunk_scan_matches_the_plain_recurrence(impl):
    """Initial state in, final state out; rows with 0, 1, some and all
    lanes real; a row that restarts from zero."""
    x, dt, a, bm, cm, st = inputs()
    lens, reset = RAGGED
    y, new = ssm.ssd_chunk_scan(x, dt, a, bm, cm, st, jnp.asarray(lens),
                                jnp.asarray(reset), implementation=impl)
    want_y, want_st = plain_scan(x, dt, a, bm, cm, st, lens, reset)
    real = np.arange(S)[None, :] < lens[:, None]
    # float32 sums over 128 state columns of O(1) terms: 1e-4 of the
    # outputs' size (about 10) covers their round-off
    np.testing.assert_allclose(np.asarray(y)[real], want_y[real],
                               rtol=1e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(new), want_st, rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_rows_without_real_lanes_stand_still(impl):
    x, dt, a, bm, cm, st = inputs(1)
    lens = jnp.zeros((B,), jnp.int32)
    none = jnp.zeros((B,), bool)
    _, new = ssm.ssd_chunk_scan(x, dt, a, bm, cm, st, lens, none,
                                implementation=impl)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(st))
    _, new = ssm.ssm_decode_update(x[:, 0], dt[:, 0], a, bm[:, 0],
                                   cm[:, 0], st, lens, none,
                                   implementation=impl)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(st))


@pytest.mark.parametrize("impl", IMPLS)
def test_one_chunk_is_three_chunks(impl):
    """24 lanes in one call = the same lanes in three calls of 8 that
    hand the state on."""
    x, dt, a, bm, cm, st = inputs(2, s=24)
    full = jnp.full((B,), 24, jnp.int32)
    none = jnp.zeros((B,), bool)
    y1, s1 = ssm.ssd_chunk_scan(x, dt, a, bm, cm, st, full, none,
                                implementation=impl)
    ys, s3 = [], st
    for i in range(3):
        cut = slice(8 * i, 8 * i + 8)
        y, s3 = ssm.ssd_chunk_scan(
            x[:, cut], dt[:, cut], a, bm[:, cut], cm[:, cut], s3,
            jnp.full((B,), 8, jnp.int32), none, implementation=impl)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(y1),
                               np.asarray(jnp.concatenate(ys, 1)),
                               rtol=1e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s3),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_decode_update_is_a_chunk_of_width_one(impl):
    x, dt, a, bm, cm, st = inputs(3)
    lens = jnp.asarray([0, 1, 1, 1], jnp.int32)
    reset = jnp.asarray([False, True, False, False])
    y, new = ssm.ssm_decode_update(x[:, 0], dt[:, 0], a, bm[:, 0],
                                   cm[:, 0], st, lens, reset,
                                   implementation=impl)
    want_y, want_st = ssm.ssd_chunk_scan(
        x[:, :1], dt[:, :1], a, bm[:, :1], cm[:, :1], st, lens, reset,
        implementation="xla")
    np.testing.assert_allclose(np.asarray(y)[1:], np.asarray(want_y)[1:, 0],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new), np.asarray(want_st),
                               rtol=1e-6, atol=1e-6)


def test_a_reset_row_ignores_a_state_that_is_not_finite():
    """A slot's previous tenant leaves whatever it leaves: a row that
    restarts must not multiply it by zero."""
    x, dt, a, bm, cm, st = inputs(4)
    st = st.at[1].set(jnp.nan).at[2].set(jnp.inf)
    reset = jnp.asarray([False, True, True, False])
    full = jnp.full((B,), S, jnp.int32)
    for impl in IMPLS:
        y, new = ssm.ssd_chunk_scan(x, dt, a, bm, cm, st, full, reset,
                                    implementation=impl)
        assert bool(jnp.all(jnp.isfinite(y))), impl
        assert bool(jnp.all(jnp.isfinite(new))), impl
        y, new = ssm.ssm_decode_update(
            x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], st,
            jnp.ones((B,), jnp.int32), reset, implementation=impl)
        assert bool(jnp.all(jnp.isfinite(new))) \
            and bool(jnp.all(jnp.isfinite(y))), impl


def test_the_kernel_is_refused_outside_its_envelope():
    x, dt, a, bm, cm, st = inputs(5, s=6)        # 6 lanes: no whole tile
    full = jnp.full((B,), 6, jnp.int32)
    none = jnp.zeros((B,), bool)
    with pytest.raises(ValueError, match="outside its envelope"):
        ssm.ssd_chunk_scan(x, dt, a, bm, cm, st, full, none,
                           implementation="pallas_interpret")
    with pytest.raises(ValueError, match="outside its envelope"):
        ssm.ssm_decode_update(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                              st.astype(jnp.bfloat16), full, none,
                              implementation="pallas_interpret")
    assert ssm.ssm_envelope_ok(32, 2, 128, 256, width=32)
    assert not ssm.ssm_envelope_ok(32, 2, 128, 200)
    assert not ssm.ssm_envelope_ok(32, 3, 128, 256)


@pytest.mark.parametrize("lens", [[8, 8, 8, 8], [0, 1, 5, 8]])
def test_conv_step_rolls_over_real_lanes_only(lens):
    k, c = 4, 16
    keys = jax.random.split(jax.random.PRNGKey(6), 4)
    u = jax.random.normal(keys[0], (B, S, c))
    win = jax.random.normal(keys[1], (B, k - 1, c))
    w = jax.random.normal(keys[2], (k, c))
    bias = jax.random.normal(keys[3], (c,))
    lens = np.asarray(lens, np.int32)
    reset = np.array([False, True, False, False])
    out, new = ssm.causal_conv_step(u, win, w, bias, jnp.asarray(lens),
                                    jnp.asarray(reset))
    for r in range(B):
        hist = np.zeros((k - 1, c)) if reset[r] else np.asarray(win[r])
        full = np.concatenate([hist, np.asarray(u[r])], 0)
        for t in range(int(lens[r])):
            want = (full[t:t + k] * np.asarray(w)).sum(0) + np.asarray(bias)
            np.testing.assert_allclose(np.asarray(out[r, t]), want,
                                       rtol=1e-5, atol=1e-5)
        n = int(lens[r])
        np.testing.assert_allclose(np.asarray(new[r]), full[n:n + k - 1],
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("impl", IMPLS)
def test_ops_match_the_benchmark_references_mixer_scan(impl):
    """The benchmark's plain reference carries conv history and state
    through one ``lax.scan``; conv step + chunk scan + the D skip, fed
    in chunks, must give its outputs and its final state."""
    from lib.reference import falcon_h1 as ref

    d_ssm, gn, k = H * P, G * N, 4
    cc = d_ssm + 2 * gn
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    hidden = 32
    w = {"in_proj": 0.3 * jax.random.normal(keys[0], (hidden,
                                                     2 * d_ssm + 2 * gn + H)),
         "conv_w": 0.5 * jax.random.normal(keys[1], (k, cc)),
         "conv_b": 0.1 * jax.random.normal(keys[2], (cc,)),
         "dt_bias": jnp.full((H,), -2.0), "A_log": jnp.log(
             jnp.linspace(1.0, 8.0, H)), "D": jnp.ones((H,)),
         "mnorm": jnp.ones((d_ssm,)),
         "out_proj": jnp.eye(d_ssm)}
    xin = jax.random.normal(keys[3], (16, hidden))
    mult = dict(ssm=(1.0,) * 5, ssm_in=1.0, ssm_out=1.0)
    init = (jnp.zeros((H, P, N)), jnp.zeros((k - 1, cc)))
    want, (want_state, want_hist) = ref.mixer(
        xin, w, init, m_heads=H, m_p=P, m_n=N, m_g=G, eps=1e-5, mult=mult,
        lower=None, restart_every=None)
    # the same through the ops, two chunks of 8, batch of 1
    proj = jnp.matmul(xin, w["in_proj"],
                      precision=jax.lax.Precision.HIGHEST)[None]
    z, u, dt = (proj[..., :d_ssm], proj[..., d_ssm:d_ssm + cc],
                proj[..., d_ssm + cc:])
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = -jnp.exp(w["A_log"])
    state, hist = jnp.zeros((1, H, P, N)), jnp.zeros((1, k - 1, cc))
    eight, ys = jnp.full((1,), 8, jnp.int32), []
    for i in range(2):
        cut = slice(8 * i, 8 * i + 8)
        act, hist = ssm.causal_conv_step(
            u[:, cut], hist, w["conv_w"], w["conv_b"], eight,
            jnp.asarray([i == 0]))
        act = jax.nn.silu(act)
        xs = act[..., :d_ssm].reshape(1, 8, H, P)
        bm = act[..., d_ssm:d_ssm + gn].reshape(1, 8, G, N)
        cm = act[..., d_ssm + gn:].reshape(1, 8, G, N)
        y, state = ssm.ssd_chunk_scan(xs, dt[:, cut], a, bm, cm, state,
                                      eight, jnp.asarray([i == 0]),
                                      implementation=impl)
        ys.append(y + w["D"][:, None] * xs)
    y = jnp.concatenate(ys, 1).reshape(16, d_ssm) * jax.nn.silu(z[0])
    yg = y.reshape(16, G, d_ssm // G)
    y = (yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                            + 1e-5)).reshape(16, d_ssm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(state[0]),
                               np.asarray(want_state), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(hist[0]), np.asarray(want_hist),
                               rtol=1e-6, atol=1e-6)
