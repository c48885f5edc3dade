"""SyncBatchNorm and DDP tests on the virtual CPU mesh — the hermetic
version of the reference's ``tests/distributed/synced_batchnorm`` and
``tests/distributed/DDP`` two-GPU suites (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import flax.linen as nn
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.core import mesh as mesh_lib
from apex_tpu import parallel as apx_parallel
from apex_tpu.parallel import (
    SyncBatchNorm, sync_batch_norm_stats, convert_syncbn_model,
    DistributedDataParallel, zero_param_specs,
)


def shard_map(fn, mesh, in_specs, out_specs, **kw):
    kw.setdefault("check_vma", False)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


@pytest.fixture
def dp_mesh():
    m = mesh_lib.initialize_mesh(data_parallel_size=8)
    yield m
    mesh_lib.destroy_mesh()


class TestSyncBatchNorm:
    def test_stats_match_global_batch(self, dp_mesh, rng):
        # stats over 8 shards == stats over the concatenated batch
        x = jnp.asarray(rng.normal(size=(16, 4, 4, 8)), jnp.float32)

        f = shard_map(
            lambda xs: sync_batch_norm_stats(
                xs, ("data",), reduce_dims=(0, 1, 2)),
            dp_mesh, (P("data"),), (P(), P()))
        mean, var = f(x)
        want_mean = np.mean(np.asarray(x), axis=(0, 1, 2))
        want_var = np.var(np.asarray(x), axis=(0, 1, 2))
        np.testing.assert_allclose(np.asarray(mean), want_mean, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(var), want_var,
                                   rtol=1e-4, atol=1e-6)

    @pytest.mark.l0
    def test_module_matches_single_device_bn(self, dp_mesh, rng):
        # the reference's canonical test: 2-process SyncBN == 1-process BN
        x = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
        sbn = SyncBatchNorm(use_running_average=False)
        variables = sbn.init(jax.random.PRNGKey(0), x)

        def fwd(xs):
            y, _ = sbn.apply(variables, xs, mutable=["batch_stats"])
            return y

        y_sharded = shard_map(fwd, dp_mesh, (P("data"),),
                              P("data"))(x)
        bn = nn.BatchNorm(use_running_average=False, momentum=0.9)
        bn_vars = bn.init(jax.random.PRNGKey(0), x)
        y_single, _ = bn.apply(bn_vars, x, mutable=["batch_stats"])
        np.testing.assert_allclose(np.asarray(y_sharded),
                                   np.asarray(y_single),
                                   rtol=1e-4, atol=1e-5)

    def test_gradients_cross_device_terms(self, dp_mesh, rng):
        # grad wrt x must include the cross-shard stat terms: compare
        # sharded-grad vs single-device autodiff of plain BN
        x = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
        sbn = SyncBatchNorm(use_running_average=False)
        variables = sbn.init(jax.random.PRNGKey(0), x)

        def g_sharded(xs):
            def loss(xs):
                y, _ = sbn.apply(variables, xs, mutable=["batch_stats"])
                return jnp.sum(y ** 3)  # nonlinear so stat grads matter
            return jax.grad(loss)(xs)

        gs = shard_map(g_sharded, dp_mesh, (P("data"),), P("data"))(x)

        bn = nn.BatchNorm(use_running_average=False)
        bn_vars = bn.init(jax.random.PRNGKey(0), x)

        def loss_single(x):
            y, _ = bn.apply(bn_vars, x, mutable=["batch_stats"])
            return jnp.sum(y ** 3)

        # NOTE: per-shard grad omits cross-shard x-terms of OTHER shards'
        # losses; but loss is a sum over shards and grads add — with the
        # shared global stats the sharded grad equals the global grad.
        gd = jax.grad(loss_single)(x)
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gd),
                                   rtol=1e-3, atol=1e-4)

    def test_running_stats_update(self, dp_mesh, rng):
        x = jnp.asarray(rng.normal(size=(16, 8)) + 3.0, jnp.float32)
        sbn = SyncBatchNorm(use_running_average=False, momentum=0.5)
        variables = sbn.init(jax.random.PRNGKey(0), x)

        def fwd(xs):
            _, upd = sbn.apply(variables, xs, mutable=["batch_stats"])
            return upd["batch_stats"]["mean"], upd["batch_stats"]["var"]

        mean, var = shard_map(fwd, dp_mesh, (P("data"),), (P(), P()))(x)
        want = 0.5 * 0.0 + 0.5 * np.mean(np.asarray(x), axis=0)
        np.testing.assert_allclose(np.asarray(mean), want, rtol=1e-4)
        # running_var stores the unbiased (ddof=1) estimate — torch
        # SyncBatchNorm parity
        want_var = 0.5 * 1.0 + 0.5 * np.var(np.asarray(x), axis=0, ddof=1)
        np.testing.assert_allclose(np.asarray(var), want_var,
                                   rtol=1e-4, atol=1e-5)

    def test_eval_mode_uses_running(self, rng):
        x = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
        sbn = SyncBatchNorm(use_running_average=True)
        variables = sbn.init(jax.random.PRNGKey(0), x)
        y = sbn.apply(variables, x)
        # running stats are (0, 1) at init → y == scale*x + bias == x
        np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                                   rtol=1e-5)

    def test_convert_syncbn_model(self):
        class Net(nn.Module):
            bn: nn.Module = None

            @nn.compact
            def __call__(self, x):
                return self.bn(x)

        net = Net(bn=nn.BatchNorm(use_running_average=False,
                                  momentum=0.8))
        converted = convert_syncbn_model(net)
        assert isinstance(converted.bn, SyncBatchNorm)
        assert converted.bn.momentum == 0.8

    def test_local_fallback_no_mesh(self, rng):
        # outside shard_map: behaves as plain BN (reference python impl
        # fallback path)
        x = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
        sbn = SyncBatchNorm(use_running_average=False)
        variables = sbn.init(jax.random.PRNGKey(0), x)
        y, _ = sbn.apply(variables, x, mutable=["batch_stats"])
        np.testing.assert_allclose(np.mean(np.asarray(y), axis=0), 0.0,
                                   atol=1e-5)


class TestSyncBatchNormFused:
    """ISSUE-3 acceptance: the fused-stats path (the kernels' partial
    Σx/Σx² psum'd over the data axis) must keep cross-device agreement
    on the 8-device CPU mesh — same contracts as TestSyncBatchNorm,
    with ``fused=True``."""

    @pytest.mark.l0
    def test_fused_module_matches_single_device_bn(self, dp_mesh, rng):
        x = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
        sbn = SyncBatchNorm(use_running_average=False, fused=True)
        variables = sbn.init(jax.random.PRNGKey(0), x)

        def fwd(xs):
            y, _ = sbn.apply(variables, xs, mutable=["batch_stats"])
            return y

        y_sharded = shard_map(fwd, dp_mesh, (P("data"),),
                              P("data"))(x)
        bn = nn.BatchNorm(use_running_average=False, momentum=0.9)
        bn_vars = bn.init(jax.random.PRNGKey(0), x)
        y_single, _ = bn.apply(bn_vars, x, mutable=["batch_stats"])
        np.testing.assert_allclose(np.asarray(y_sharded),
                                   np.asarray(y_single),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.slow
    def test_fused_matches_unfused_across_mesh(self, dp_mesh, rng):
        """fwd, running stats AND input grads agree between fused and
        unfused across the 8-shard mesh — including the fused relu +
        residual epilogue.  [slow: the grad-of-shard_map compile ≈
        17 s on CPU; the fast tier keeps the single-device-BN
        agreement test below]"""
        x = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
        res = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
        a = SyncBatchNorm(use_running_average=False, act="relu")
        b = SyncBatchNorm(use_running_average=False, act="relu",
                          fused=True)
        variables = a.init(jax.random.PRNGKey(0), x)

        def run(mod):
            def g(xs, rs):
                def loss(xs):
                    y, upd = mod.apply(variables, xs, residual=rs,
                                       mutable=["batch_stats"])
                    return jnp.sum(y ** 3), (y, upd)
                grads, (y, upd) = jax.grad(loss, has_aux=True)(xs)
                return y, grads, upd["batch_stats"]["mean"], \
                    upd["batch_stats"]["var"]
            return shard_map(
                g, dp_mesh, (P("data"), P("data")),
                (P("data"), P("data"), P(), P()))(x, res)

        ya, ga, ma, va = run(a)
        yb, gb, mb, vb = run(b)
        np.testing.assert_allclose(np.asarray(ya), np.asarray(yb),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(np.asarray(ma), np.asarray(mb),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(va), np.asarray(vb),
                                   rtol=1e-4, atol=1e-6)

    @pytest.mark.slow
    def test_fused_resnet_syncbn_step_on_mesh(self, dp_mesh, rng):
        """The resnet50_syncbn bench topology at test size: a fused_bn
        ResNet under shard_map over the data axis produces the same
        logits as the unfused module path.  [slow: two sharded resnet
        compiles ≈ 29 s on CPU]"""
        from apex_tpu.models.resnet import ResNet, ResNetConfig

        x = jnp.asarray(rng.normal(size=(16, 16, 16, 3)), jnp.float32)
        cfg = ResNetConfig(stage_sizes=(1,), num_classes=4, width=8,
                           bn_axis_names=("data",))
        m = ResNet(cfg)
        import dataclasses
        mf = ResNet(dataclasses.replace(cfg, fused_bn=True))
        variables = m.init(jax.random.PRNGKey(0), x[:2], train=True)

        def fwd(model):
            def f(xs):
                out, _ = model.apply(variables, xs, train=True,
                                     mutable=["batch_stats"])
                return out
            return shard_map(f, dp_mesh, (P("data"),), P("data"))(x)

        np.testing.assert_allclose(
            np.asarray(fwd(mf)), np.asarray(fwd(m)),
            rtol=1e-4, atol=1e-4)


class TestDDP:
    @pytest.mark.l0
    def test_sharded_training_matches_single_device(self, dp_mesh, rng):
        # end-to-end: DP training step over 8 shards == single-device
        # step on the full batch (apex DDP's correctness contract)
        import optax
        from apex_tpu import optim as ao

        x = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
        y = jnp.asarray(rng.normal(size=(32, 2)), jnp.float32)
        params = {"w": jnp.asarray(rng.normal(size=(8, 2)), jnp.float32),
                  "b": jnp.zeros((2,), jnp.float32)}
        tx = ao.fused_sgd(0.1, momentum=0.9)
        opt_state = tx.init(params)

        def local_loss(p, xs, ys):
            pred = xs @ p["w"] + p["b"]
            return jnp.mean((pred - ys) ** 2)

        def dp_step(p, s, xs, ys):
            g = jax.grad(local_loss)(p, xs, ys)
            g = apx_parallel.all_reduce_mean_grads(g, "data")
            updates, s2 = tx.update(g, s, p)
            import optax as _o
            return _o.apply_updates(p, updates), s2

        f = shard_map(dp_step, dp_mesh,
                      (P(), P(), P("data"), P("data")), (P(), P()))
        p_dp, _ = f(params, opt_state, x, y)

        g_full = jax.grad(local_loss)(params, x, y)
        updates, _ = tx.update(g_full, opt_state, params)
        import optax as _o
        p_single = _o.apply_updates(params, updates)
        for k in params:
            np.testing.assert_allclose(np.asarray(p_dp[k]),
                                       np.asarray(p_single[k]),
                                       rtol=1e-5, atol=1e-6)

    def test_ddp_wrapper_placement(self, dp_mesh, rng):
        ddp = DistributedDataParallel(dp_mesh)
        params = {"w": jnp.ones((4, 4))}
        p = ddp.replicate(params)
        batch = ddp.shard({"x": jnp.ones((16, 4))})
        assert p["w"].sharding.is_fully_replicated
        assert not batch["x"].sharding.is_fully_replicated

    def test_zero_param_specs(self, dp_mesh):
        params = {"w": jnp.ones((16, 4)), "scalar": jnp.ones(())}
        specs = zero_param_specs(params, axis="data", mesh=dp_mesh)
        assert specs["w"] == P("data", None)
        assert specs["scalar"] == P()


class TestCompressedAllreduce:
    def test_half_allreduce_close_to_fp32(self, dp_mesh, rng):
        g = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)

        def run(dtype):
            f = shard_map(
                lambda gs: apx_parallel.all_reduce_mean_grads(
                    {"g": gs}, allreduce_dtype=dtype)["g"],
                dp_mesh, (P("data"),), P("data"))
            return np.asarray(f(g))

        exact = run(None)
        half = run(jnp.bfloat16)
        assert half.dtype == np.float32
        np.testing.assert_allclose(half, exact, rtol=2e-2, atol=2e-2)

    def test_int8_allreduce_quantization_error_bounded(self, dp_mesh, rng):
        g = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)

        f = shard_map(
            lambda gs: apx_parallel.all_reduce_mean_grads(
                {"g": gs}, allreduce_dtype="int8")["g"],
            dp_mesh, (P("data"),), P("data"))
        exact = shard_map(
            lambda gs: apx_parallel.all_reduce_mean_grads(
                {"g": gs})["g"],
            dp_mesh, (P("data"),), P("data"))
        got, want = np.asarray(f(g)), np.asarray(exact(g))
        amax = np.abs(np.asarray(g)).max()
        # per-element error ≤ quantization step (amax/127)
        assert np.abs(got - want).max() <= amax / 127 + 1e-6

    def test_int8_zero_grads(self, dp_mesh):
        g = jnp.zeros((16, 4), jnp.float32)
        f = shard_map(
            lambda gs: apx_parallel.all_reduce_mean_grads(
                {"g": gs}, allreduce_dtype="int8")["g"],
            dp_mesh, (P("data"),), P("data"))
        np.testing.assert_array_equal(np.asarray(f(g)), 0.0)

    def test_int8_dtype_object_and_validation(self, dp_mesh, rng):
        g = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
        # jnp.int8 the dtype object routes to the quantized path
        f = shard_map(
            lambda gs: apx_parallel.all_reduce_mean_grads(
                {"g": gs}, allreduce_dtype=jnp.int8)["g"],
            dp_mesh, (P("data"),), P("data"))
        out = np.asarray(f(g))
        assert np.abs(out).max() > 0
        with pytest.raises(ValueError, match="allreduce_dtype"):
            apx_parallel.all_reduce_mean_grads(
                {"g": g}, allreduce_dtype="int4")
        with pytest.raises(ValueError, match="allreduce_dtype"):
            apx_parallel.all_reduce_mean_grads(
                {"g": g}, allreduce_dtype=jnp.int32)

    def test_int8_subnormal_amax_no_nan(self, dp_mesh):
        # amax in (0, ~3.7e-37): an unguarded 127/amax overflows to
        # +inf and 0*inf = NaN would poison zero grad elements.
        # 1e-37 > finfo.tiny, so a guard at finfo.tiny misses it
        g = jnp.full((16, 4), 1e-37, jnp.float32).at[0, 0].set(0.0)
        f = shard_map(
            lambda gs: apx_parallel.all_reduce_mean_grads(
                {"g": gs}, allreduce_dtype="int8")["g"],
            dp_mesh, (P("data"),), P("data"))
        out = np.asarray(f(g))
        assert np.isfinite(out).all(), \
            "subnormal amax must not produce NaN gradients"

    def test_int8_wire_dtype_is_int8(self, dp_mesh, rng):
        # the collectives that move O(n) payload must run on int8
        # operands — that IS the compression claim
        g = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
        f = jax.jit(shard_map(
            lambda gs: apx_parallel.all_reduce_mean_grads(
                {"g": gs}, allreduce_dtype="int8")["g"],
            dp_mesh, (P("data"),), P("data")))
        hlo = f.lower(g).as_text()  # StableHLO text
        for op in ("stablehlo.all_to_all", "stablehlo.all_gather"):
            ops = [l for l in hlo.splitlines() if op in l]
            assert ops, f"expected a {op} in the lowered module"
            assert all("xi8>" in l for l in ops), \
                f"{op} payload must be int8 on the wire:\n" + "\n".join(ops)

    def test_int8_propagates_nonfinite(self, dp_mesh):
        g = jnp.full((16, 4), jnp.inf, jnp.float32)
        f = shard_map(
            lambda gs: apx_parallel.all_reduce_mean_grads(
                {"g": gs}, allreduce_dtype="int8")["g"],
            dp_mesh, (P("data"),), P("data"))
        out = np.asarray(f(g))
        assert not np.isfinite(out).any(), \
            "overflow must survive the quantized all-reduce"

    def test_sum_mode_keeps_compression(self, dp_mesh, rng):
        g = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
        mean = shard_map(
            lambda gs: apx_parallel.all_reduce_mean_grads(
                {"g": gs}, allreduce_dtype="int8")["g"],
            dp_mesh, (P("data"),), P("data"))(g)
        total = shard_map(
            lambda gs: apx_parallel.all_reduce_mean_grads(
                {"g": gs}, allreduce_dtype="int8", average=False)["g"],
            dp_mesh, (P("data"),), P("data"))(g)
        np.testing.assert_allclose(np.asarray(total),
                                   np.asarray(mean) * 8, rtol=1e-5)


class TestZeroSharding:
    """distributed_fused_adam/zero_shardings (reference:
    apex/contrib/optimizers/distributed_fused_adam — ZeRO as placement,
    SURVEY.md §2.7): sharded-state training must match replicated
    training, lower to real reduce-scatter/all-gather collectives, and
    actually cut per-device state memory."""

    def test_zero_matches_replicated_and_shards_memory(self, rng):
        import optax

        from apex_tpu import amp
        from apex_tpu.parallel.distributed_optim import (
            distributed_fused_adam, zero_shardings)

        mesh = mesh_lib.initialize_mesh(fsdp_size=4,
                                        data_parallel_size=2)
        try:
            hid = 64
            w = jnp.asarray(rng.normal(size=(hid, hid)) * 0.1,
                            jnp.float32)
            b = jnp.zeros((hid,), jnp.float32)
            params = {"w": w, "b": b}
            x = jnp.asarray(rng.normal(size=(8, hid)), jnp.float32)
            y = jnp.asarray(rng.normal(size=(8, hid)), jnp.float32)

            def apply_fn(p, x):
                return jnp.tanh(x @ p["w"] + p["b"])

            def make_state():
                return amp.initialize(apply_fn, params,
                                      distributed_fused_adam(1e-2),
                                      opt_level="O2",
                                      half_dtype=jnp.bfloat16)

            def train_step(state, x, y):
                def loss_fn(p):
                    out = state.apply_fn(
                        state.policy.cast_to_compute(p), x)
                    loss = jnp.mean((out.astype(jnp.float32) - y) ** 2)
                    return state.scale_loss(loss), loss

                grads, loss = jax.grad(loss_fn, has_aux=True)(
                    state.params)
                new_state, _ = state.apply_gradients(grads=grads)
                return new_state, loss

            # replicated run (no sharding constraints)
            state_r = make_state()
            step_r = jax.jit(train_step)
            losses_r = []
            for _ in range(3):
                state_r, loss = step_r(state_r, x, y)
                losses_r.append(float(loss))

            # ZeRO run: params + optimizer state sharded over fsdp
            state_z = make_state()
            shardings = zero_shardings(state_z, mesh=mesh)
            state_z = jax.device_put(state_z, shardings)
            step_z = jax.jit(train_step,
                             in_shardings=(shardings,
                                           NamedSharding(mesh, P("data")),
                                           NamedSharding(mesh, P("data"))),
                             out_shardings=(shardings, None),
                             donate_argnums=(0,))
            xs = jax.device_put(x, NamedSharding(mesh, P("data")))
            ys = jax.device_put(y, NamedSharding(mesh, P("data")))
            lowered = step_z.lower(state_z, xs, ys)
            compiled = lowered.compile()
            losses_z = []
            for _ in range(3):
                state_z, loss = compiled(state_z, xs, ys)
                losses_z.append(float(loss))

            np.testing.assert_allclose(losses_z, losses_r,
                                       rtol=1e-5, atol=1e-6)
            # the GSPMD lowering must contain the ZeRO choreography
            hlo = compiled.as_text()
            assert ("reduce-scatter" in hlo or "all-gather" in hlo
                    or "all-reduce" in hlo), "no collectives in HLO"
            # per-device state memory: the (hid, hid) fp32 leaves of
            # params+masters+moments shard 4x over fsdp
            mat_bytes = hid * hid * 4
            arg_bytes = compiled.memory_analysis().argument_size_in_bytes
            # replicated state would hold >= 4 full fp32 matrices
            # (masters, m, v, bf16 copy) per device; sharded must be
            # well under that
            assert arg_bytes < 3 * mat_bytes, (arg_bytes, mat_bytes)
        finally:
            mesh_lib.destroy_mesh()


class TestLaunch:
    """init_distributed (reference: apex.parallel.multiproc launcher ->
    jax.distributed; MASTER_ADDR/RANK/WORLD_SIZE conventions)."""

    def test_single_host_noop_and_env_bootstrap(self):
        import subprocess
        import sys

        code = (
            "import os\n"
            "from apex_tpu.parallel import init_distributed, "
            "is_distributed\n"
            "assert init_distributed() is False\n"
            "assert not is_distributed()\n"
            "os.environ['MASTER_ADDR'] = '127.0.0.1'\n"
            "os.environ['MASTER_PORT'] = '29777'\n"
            "os.environ['WORLD_SIZE'] = '1'\n"
            "os.environ['RANK'] = '0'\n"
            "assert init_distributed() is True\n"
            "assert is_distributed()\n"
            "assert init_distributed() is True  # idempotent\n"
            "import jax\n"
            "assert jax.process_count() == 1\n"
            "print('LAUNCH_OK')\n")
        env = dict(__import__("os").environ)
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-1500:]
        assert "LAUNCH_OK" in r.stdout

    def test_partial_env_raises_descriptive(self, monkeypatch):
        # round-2 advisor: MASTER_ADDR without WORLD_SIZE/RANK must
        # surface as a descriptive error naming the missing vars, not a
        # JAX-internal failure from initialize(num_processes=None);
        # match the dynamic per-case prefix, not the static tail
        from apex_tpu.parallel import init_distributed

        monkeypatch.setenv("MASTER_ADDR", "127.0.0.1")
        monkeypatch.delenv("WORLD_SIZE", raising=False)
        monkeypatch.delenv("RANK", raising=False)
        with pytest.raises(ValueError,
                           match="WORLD_SIZE and RANK unresolved"):
            init_distributed()
        monkeypatch.setenv("WORLD_SIZE", "2")
        with pytest.raises(ValueError, match=r"with RANK unresolved"):
            init_distributed()
