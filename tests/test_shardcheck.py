"""Unit tier for the runtime placement sanitizer
(``apex_tpu.utils.shardcheck``) — the dynamic twin of graftlint's
sharding pass, the way ``tests/test_numcheck.py`` pins the numerics
sanitizer: instrument idempotence, strict mode in both directions (a
planted declared-vs-actual breach is recorded strict-only), the
``APEX_TPU_SHARDCHECK`` env gate, the declared-vs-actual positive
mismatch on the 8-device CPU mesh the conftest forces, transfer-event
attribution through the ``jax.monitoring`` seam, and the
tensor-parallel paged-engine integration (the committed pool/state
placement survives warmup → admit → step → release under the
recorder, with the ``trace_counts`` diagnostics still readable
through the proxies).

Every test runs under an autouse reset + ``uninstrument()`` so the
process-wide listener and wrapped steps never leak into the suite.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.utils import shardcheck


@pytest.fixture(autouse=True)
def _isolated():
    shardcheck.reset()
    yield
    shardcheck.uninstrument()
    shardcheck.reset()


@pytest.fixture(scope="module")
def mesh8():
    devs = jax.devices()
    assert len(devs) >= 8, "conftest forces 8 virtual CPU devices"
    return Mesh(np.array(devs[:8]), ("data",))


def _sharded_step(mesh8, out_spec):
    """A jitted step whose output placement is pinned to ``out_spec``
    — the ground truth the declared tree is checked against."""
    return jax.jit(lambda x: x * 2.0,
                   out_shardings=NamedSharding(mesh8, out_spec))


# --------------------------------------------------------------------- #
# env gate
# --------------------------------------------------------------------- #
class TestEnvGate:
    def test_env_strict_reads_the_chaos_smoke_setting(self, monkeypatch):
        monkeypatch.delenv("APEX_TPU_SHARDCHECK", raising=False)
        assert not shardcheck.env_strict()
        monkeypatch.setenv("APEX_TPU_SHARDCHECK", "strict")
        assert shardcheck.env_strict()
        monkeypatch.setenv("APEX_TPU_SHARDCHECK", "observe")
        assert not shardcheck.env_strict()

    def test_wrap_step_follows_env_default(self, monkeypatch, mesh8):
        monkeypatch.setenv("APEX_TPU_SHARDCHECK", "strict")
        step = shardcheck.wrap_step(
            _sharded_step(mesh8, P()),            # actually replicated
            declared=NamedSharding(mesh8, P("data")),   # claims sharded
            mesh=mesh8, name="env_step")
        step(jnp.arange(8.0))
        assert shardcheck.reports(), \
            "strict env + declared/actual mismatch must record"


# --------------------------------------------------------------------- #
# declared vs actual on the 8-device mesh
# --------------------------------------------------------------------- #
class TestDeclaredVsActual:
    def test_matching_placement_is_clean(self, mesh8):
        step = shardcheck.wrap_step(
            _sharded_step(mesh8, P("data")),
            declared=NamedSharding(mesh8, P("data")),
            mesh=mesh8, name="good_step", strict=True)
        step(jnp.arange(8.0))
        shardcheck.assert_clean()
        stats = shardcheck.site_shardings()["good_step"]
        assert stats["calls"] == 1
        assert stats["checked"] == 1
        assert stats["mismatched"] == 0

    def test_mismatch_recorded_in_strict(self, mesh8):
        step = shardcheck.wrap_step(
            _sharded_step(mesh8, P()),            # replication fallback
            declared=NamedSharding(mesh8, P("data")),
            mesh=mesh8, name="bad_step", strict=True)
        step(jnp.arange(8.0))
        found = shardcheck.reports()
        assert len(found) == 1
        assert "bad_step" in found[0]
        assert "placement mismatch" in found[0]
        with pytest.raises(shardcheck.ShardCheckError):
            shardcheck.assert_clean()
        # one report per distinct site, not per step
        step(jnp.arange(8.0))
        assert len(shardcheck.reports()) == 1

    def test_mismatch_observed_only_when_not_strict(self, mesh8,
                                                    monkeypatch):
        monkeypatch.delenv("APEX_TPU_SHARDCHECK", raising=False)
        step = shardcheck.wrap_step(
            _sharded_step(mesh8, P()),
            declared=NamedSharding(mesh8, P("data")),
            mesh=mesh8, name="observed_step", strict=False)
        step(jnp.arange(8.0))
        stats = shardcheck.site_shardings()["observed_step"]
        assert stats["mismatched"] == 1       # counted ...
        shardcheck.assert_clean()             # ... but never a violation

    def test_bare_partition_specs_resolve_against_mesh(self, mesh8):
        step = shardcheck.wrap_step(
            _sharded_step(mesh8, P("data")),
            declared=P("data"), mesh=mesh8,
            name="spec_step", strict=True)
        step(jnp.arange(8.0))
        shardcheck.assert_clean()
        assert shardcheck.site_shardings()["spec_step"]["checked"] == 1

    def test_declared_tree_covers_tuple_outputs(self, mesh8):
        base = jax.jit(
            lambda x: (x * 2.0, jnp.sum(x)),
            out_shardings=(NamedSharding(mesh8, P("data")),
                           NamedSharding(mesh8, P())))
        step = shardcheck.wrap_step(
            base,
            declared=(NamedSharding(mesh8, P("data")),
                      NamedSharding(mesh8, P())),
            mesh=mesh8, name="tuple_step", strict=True)
        step(jnp.arange(8.0))
        shardcheck.assert_clean()
        assert shardcheck.site_shardings()["tuple_step"]["checked"] == 2


# --------------------------------------------------------------------- #
# transfer accounting (the jax.monitoring seam; CPU zero-copies defeat
# jax.transfer_guard, so tests inject synthetic events)
# --------------------------------------------------------------------- #
class TestTransferAccounting:
    def test_in_window_transfer_is_a_strict_violation(self, mesh8):
        def leaky(x):
            jax.monitoring.record_event(
                "/shardcheck_test/transfer_d2h", num_bytes=64)
            return x * 2.0

        step = shardcheck.wrap_step(
            leaky, declared=None, mesh=mesh8,
            name="leaky_step", strict=True)
        step(jnp.arange(8.0))
        s = shardcheck.summary()
        assert s["d2h_events"] == 1
        assert s["d2h_bytes"] == 64
        assert s["transfer_sites"] == {"leaky_step": 1}
        found = shardcheck.reports()
        assert len(found) == 1
        assert "leaky_step" in found[0]

    def test_out_of_window_transfer_is_counted_not_flagged(self):
        shardcheck.instrument(object(), strict=True)  # listener only
        jax.monitoring.record_event(
            "/shardcheck_test/transfer_d2h", num_bytes=32)
        s = shardcheck.summary()
        assert s["d2h_events"] == 1
        assert s["d2h_bytes"] == 32
        assert s["transfer_sites"] == {}
        shardcheck.assert_clean()

    def test_unrelated_events_are_ignored(self):
        shardcheck.instrument(object(), strict=True)
        jax.monitoring.record_event("/shardcheck_test/compile_time")
        assert shardcheck.summary()["d2h_events"] == 0


# --------------------------------------------------------------------- #
# jax.shard_map's check_vma (ISSUE-16 satellite): the runtime twin of
# graftlint's unreplicated-out-spec rule — the trace-time error must
# keep the shape the rule's message quotes
# --------------------------------------------------------------------- #
class TestCheckVmaShim:
    def test_divergent_return_with_replicated_out_spec_raises(
            self, mesh8):
        def body(x):
            return x * 2.0        # shard-divergent, no reduction

        sm = jax.shard_map(
            body, mesh=mesh8, in_specs=(P("data"),), out_specs=P(),
            check_vma=True)
        with pytest.raises(ValueError) as exc:
            jax.jit(sm)(jnp.arange(8.0))
        # the rule-3 shape: the error
        # names out_specs and the replication contract it violates
        msg = str(exc.value)
        assert "out_specs" in msg
        assert "replicat" in msg.lower()

    def test_reduction_on_the_return_path_passes_the_check(
            self, mesh8):
        def body(x):
            return jax.lax.psum(x, "data")

        sm = jax.shard_map(
            body, mesh=mesh8, in_specs=(P("data"),), out_specs=P(),
            check_vma=True)
        out = jax.jit(sm)(jnp.arange(8.0))
        # per-shard (1,) inputs, psum'd and replicated: global (1,)
        np.testing.assert_allclose(np.asarray(out), [28.0])

    def test_check_vma_false_disables_the_check(self, mesh8):
        # the chaos-soak spelling
        def body(x):
            return x * 2.0

        sm = jax.shard_map(
            body, mesh=mesh8, in_specs=(P("data"),), out_specs=P("data"),
            check_vma=False)
        out = jax.jit(sm)(jnp.arange(8.0))
        assert out.shape == (8,)


# --------------------------------------------------------------------- #
# instrument mechanics on the TP paged engine (8-device CPU mesh)
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def tp_engine():
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serving import PagedEngine, tp_mesh

    cfg = GPTConfig.tiny(position_embedding="learned", scan_layers=True)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return PagedEngine(model, {"params": params["params"]},
                       mesh=tp_mesh(2), max_slots=2, block_size=8,
                       prefill_chunk=4)


class TestEngineInstrument:
    def test_idempotent_and_restorable(self, tp_engine):
        inner = tp_engine.__dict__["_decode"]
        shardcheck.instrument(tp_engine, strict=True)
        once = tp_engine.__dict__["_decode"]
        shardcheck.instrument(tp_engine, strict=True)   # no-op
        assert tp_engine.__dict__["_decode"] is once
        assert once is not inner
        shardcheck.uninstrument()
        assert tp_engine.__dict__["_decode"] is inner

    def test_committed_placement_holds_through_the_step_cycle(
            self, tp_engine):
        shardcheck.instrument(tp_engine, strict=True)
        tp_engine.warmup()
        tp_engine.admit(0, np.arange(5, dtype=np.int32),
                        max_new_tokens=3)
        for _ in range(4):
            tp_engine.step()
        tp_engine.release(0)
        # the diagnostics proxy through the wrappers untouched
        assert tp_engine.trace_counts == {"decode_step": 1,
                                          "prefill_step": 1,
                                          "admit": 1, "release": 1}
        sites = shardcheck.site_shardings()
        decode = sites["PagedEngine._decode"]
        assert decode["calls"] >= 1
        assert decode["checked"] > 0          # pool + state leaves
        assert decode["mismatched"] == 0
        assert sites["PagedEngine._admit"]["checked"] > 0
        shardcheck.assert_clean()
