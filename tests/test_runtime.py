"""Runtime tests: optimizer variants, data pipeline, checkpoint/failover,
compensated collectives, sharding rules."""

import dataclasses
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RunConfig
from repro.data import DataConfig, TokenStream, make_batch_iterator
from repro.optim import make_optimizer


class TestOptimizer:
    def _quadratic_losses(self, kind, steps=60):
        cfg = RunConfig(optimizer=kind, learning_rate=0.05, warmup_steps=5,
                        total_steps=steps, weight_decay=0.0, grad_clip=10.0)
        init, update = make_optimizer(cfg)
        target = jnp.asarray(np.random.default_rng(0).standard_normal((8, 8)),
                             jnp.float32)
        params = {"w": jnp.zeros((8, 8), jnp.float32)}
        state = init(params)
        losses = []
        for _ in range(steps):
            grads = {"w": 2 * (params["w"] - target)}
            losses.append(float(jnp.sum((params["w"] - target) ** 2)))
            params, state, _ = update(grads, state, params)
        return losses

    @pytest.mark.parametrize("kind", ["adamw", "adamw_int8", "adamw_dd"])
    def test_convergence(self, kind):
        losses = self._quadratic_losses(kind)
        assert losses[-1] < 0.05 * losses[0], (kind, losses[0], losses[-1])

    def test_dd_master_keeps_small_updates(self):
        # f32 update swallows tiny deltas; df32 master accumulates them
        from repro.core.efts import quick_two_sum, two_sum

        p32 = jnp.float32(1.0)
        hi, lo = jnp.float32(1.0), jnp.float32(0.0)
        delta = jnp.float32(1e-9)  # << ulp(1.0) in f32
        for _ in range(1000):
            p32 = p32 + delta
            s, e = two_sum(hi, delta)
            hi, lo = quick_two_sum(s, e + lo)
        assert float(p32) == 1.0                      # swallowed
        got = float(hi.astype(jnp.float64) + lo.astype(jnp.float64))
        assert abs(got - (1.0 + 1e-6)) < 1e-9         # df32 kept them

    def test_int8_state_roundtrip(self):
        from repro.optim.adamw import _dequantize_int8, _quantize_int8

        x = jnp.asarray(np.random.default_rng(1).standard_normal(1000) * 5,
                        jnp.float32)
        q, s = _quantize_int8(x)
        back = _dequantize_int8(q, s, x.shape)
        assert float(jnp.abs(back - x).max()) < 5 * (2 * 5 / 254)


class TestData:
    def test_deterministic_and_restart_safe(self):
        cfg = DataConfig(vocab_size=512, seq_len=32, global_batch=8)
        s1, s2 = TokenStream(cfg), TokenStream(cfg)
        b1, b2 = s1.batch_at(7), s2.batch_at(7)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        # labels are next-token shifted
        np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])

    def test_sharding_partitions_batch(self):
        full = DataConfig(vocab_size=128, seq_len=16, global_batch=8)
        parts = [
            DataConfig(vocab_size=128, seq_len=16, global_batch=8,
                       shard=i, num_shards=4)
            for i in range(4)
        ]
        assert all(TokenStream(p).local_batch == 2 for p in parts)
        # shards are distinct
        a = TokenStream(parts[0]).batch_at(3)["tokens"]
        b = TokenStream(parts[1]).batch_at(3)["tokens"]
        assert not np.array_equal(a, b)

    def test_prefetch_iterator_resumes(self):
        cfg = DataConfig(vocab_size=64, seq_len=8, global_batch=2)
        it = make_batch_iterator(cfg, start_step=5)
        b = next(it)
        assert b["step"] == 5
        np.testing.assert_array_equal(
            b["tokens"], TokenStream(cfg).batch_at(5)["tokens"])
        it.close()

    def test_markov_structure_is_learnable(self):
        # successor entropy must be far below uniform
        cfg = DataConfig(vocab_size=256, seq_len=256, global_batch=4)
        toks = TokenStream(cfg).batch_at(0)["tokens"]
        pairs = {}
        for row in toks:
            for a, b in zip(row[:-1], row[1:]):
                pairs.setdefault(int(a), set()).add(int(b))
        avg_successors = np.mean([len(v) for v in pairs.values()])
        assert avg_successors <= 8.5


class TestCheckpoint:
    def test_atomic_save_restore(self, tmp_path):
        from repro.checkpoint import CheckpointManager

        tree = {"a": jnp.arange(8.0), "b": {"c": jnp.ones((3, 3))}}
        mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
        mgr.save(tree, 10)
        mgr.save(jax.tree.map(lambda x: x * 2, tree), 20)
        restored, meta = mgr.restore(tree)
        assert meta["step"] == 20
        np.testing.assert_array_equal(np.asarray(restored["a"]),
                                      np.arange(8.0) * 2)

    def test_keep_k_gc(self, tmp_path):
        from repro.checkpoint import CheckpointManager

        tree = {"x": jnp.zeros(4)}
        mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
        for s in (1, 2, 3, 4):
            mgr.save(tree, s)
        steps = sorted(os.listdir(tmp_path))
        assert steps == ["step_00000003", "step_00000004"]

    def test_async_save(self, tmp_path):
        from repro.checkpoint import CheckpointManager

        tree = {"x": jnp.arange(1000.0)}
        mgr = CheckpointManager(str(tmp_path), async_save=True)
        mgr.save(tree, 1)
        mgr.wait()
        assert mgr.latest_step() == 1

    def test_elastic_reshard_restore(self, tmp_path):
        """Save from one mesh, restore onto a different mesh shape."""
        import subprocess
        import sys

        code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import save_pytree, restore_resharded
from repro.launch.mesh import compat_make_mesh

tree = {{"w": jnp.arange(64.0).reshape(8, 8)}}
mesh1 = compat_make_mesh((4, 2), ("data", "model"))
sh1 = NamedSharding(mesh1, P("data", "model"))
tree1 = {{"w": jax.device_put(tree["w"], sh1)}}
save_pytree(tree1, r"{tmp_path}", 1)

mesh2 = compat_make_mesh((2, 2), ("data", "model"),
                         devices=jax.devices()[:4])
sh2 = {{"w": NamedSharding(mesh2, P("model", "data"))}}
restored, meta = restore_resharded(tree, r"{tmp_path}", sh2)
assert meta["step"] == 1
np.testing.assert_array_equal(np.asarray(restored["w"]),
                              np.arange(64.0).reshape(8, 8))
assert restored["w"].sharding.mesh.shape["data"] == 2
print("ELASTIC_OK")
"""
        src = Path(__file__).resolve().parents[1] / "src"
        env = dict(os.environ, PYTHONPATH=os.pathsep.join(
            filter(None, [str(src), os.environ.get("PYTHONPATH")])))
        out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                             capture_output=True, text=True, env=env,
                             timeout=60)
        assert "ELASTIC_OK" in out.stdout, out.stderr[-2000:]


class TestFailover:
    def test_restart_recovers_and_replays(self, tmp_path):
        from repro.checkpoint import CheckpointManager
        from repro.runtime.failover import SimulatedFailure, run_with_restarts

        mgr = CheckpointManager(str(tmp_path), async_save=False)
        seen = []
        fail_at = {7, 13}

        def make_state(restore_step):
            if restore_step is None:
                return {"acc": jnp.zeros(())}, 0
            state, meta = mgr.restore({"acc": jnp.zeros(())})
            return state, meta["step"]

        def step_fn(state, step):
            if step in fail_at:
                fail_at.discard(step)
                raise SimulatedFailure(f"preempted at {step}")
            seen.append(step)
            return {"acc": state["acc"] + step}

        state, step, failures = run_with_restarts(
            make_state, step_fn, mgr, total_steps=20, checkpoint_every=5,
            max_failures=5)
        assert failures == 2 and step == 20
        # accumulator must equal the deterministic replay value
        assert float(state["acc"]) == sum(range(20))

    def test_failure_budget(self, tmp_path):
        from repro.checkpoint import CheckpointManager
        from repro.runtime.failover import SimulatedFailure, run_with_restarts

        mgr = CheckpointManager(str(tmp_path), async_save=False)

        def make_state(_):
            return {}, 0

        def always_fail(state, step):
            raise SimulatedFailure("dead node")

        with pytest.raises(RuntimeError, match="budget"):
            run_with_restarts(make_state, always_fail, mgr, total_steps=5,
                              max_failures=2)

    def test_restart_backoff_schedule(self):
        from repro.runtime.failover import restart_backoff

        # base=0 (the default) keeps the historical restart-immediately
        # behavior; so does attempt 0
        assert restart_backoff(3) == 0.0
        assert restart_backoff(0, base=0.5) == 0.0
        # exponential under the cap, capped beyond it (jitter disabled)
        waits = [restart_backoff(k, base=0.5, cap=2.0, jitter=0.0)
                 for k in (1, 2, 3, 4)]
        assert waits == [0.5, 1.0, 2.0, 2.0]
        # seeded jitter: deterministic per (seed, attempt), inside
        # [1, 1 + jitter], and distinct across attempts (de-synchronizes a
        # fleet that died at once)
        w1 = restart_backoff(1, base=1.0, jitter=0.25, seed=7)
        assert w1 == restart_backoff(1, base=1.0, jitter=0.25, seed=7)
        assert 1.0 <= w1 <= 1.25
        assert w1 != restart_backoff(2, base=1.0, cap=1.0, jitter=0.25,
                                     seed=7)

    def test_restart_waits_surface_in_on_restart(self, tmp_path):
        from repro.checkpoint import CheckpointManager
        from repro.runtime.failover import (SimulatedFailure,
                                            restart_backoff,
                                            run_with_restarts)

        mgr = CheckpointManager(str(tmp_path / "a"), async_save=False)
        fail_at = {2, 5}
        restarts, legacy, slept = [], [], []

        def make_state_for(mgr):
            def make_state(restore_step):
                if restore_step is None:
                    return {"acc": jnp.zeros(())}, 0
                state, meta = mgr.restore({"acc": jnp.zeros(())})
                return state, meta["step"]
            return make_state

        make_state = make_state_for(mgr)

        def step_fn(state, step):
            if step in fail_at:
                fail_at.discard(step)
                raise SimulatedFailure(f"preempted at {step}")
            return {"acc": state["acc"] + step}

        _, step, failures = run_with_restarts(
            make_state, step_fn, mgr, total_steps=8, checkpoint_every=2,
            max_failures=3, backoff_base=0.001, backoff_max=0.004,
            backoff_jitter=0.5, seed=11,
            on_restart=lambda s, f, w: restarts.append((s, f, w)),
            sleep=slept.append)
        assert failures == 2 and step == 8
        # each restart surfaced the wait it actually slept, and the waits
        # follow the seeded schedule exactly
        want = [restart_backoff(k, base=0.001, cap=0.004, jitter=0.5,
                                seed=11) for k in (1, 2)]
        assert slept == want
        assert [w for (_, _, w) in restarts] == want
        assert [f for (_, f, _) in restarts] == [1, 2]

        # a legacy two-argument callback keeps working
        fail_at.add(2)
        mgr2 = CheckpointManager(str(tmp_path / "b"), async_save=False)
        run_with_restarts(
            make_state_for(mgr2), step_fn, mgr2, total_steps=8,
            checkpoint_every=2, max_failures=3,
            on_restart=lambda s, f: legacy.append((s, f)))
        assert legacy == [(2, 1)]

    def test_watchdog_flags_stragglers(self):
        from repro.runtime.failover import StepWatchdog

        wd = StepWatchdog(threshold=2.0)
        for _ in range(10):
            wd.observe(0, 1.0)
        assert wd.observe(11, 5.0) is True
        assert not wd.observe(12, 1.1)
        assert len(wd.stragglers) == 1


class TestShardingRules:
    def test_rule_resolution_and_elastic_drop(self):
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import compat_make_mesh
        from repro.runtime.sharding import ShardingRules

        mesh = compat_make_mesh((1,), ("data",))
        rules = ShardingRules(mesh=mesh)
        # "model" axis absent from this mesh -> dropped
        assert rules.param_spec("embed", "heads") == P("data", None)
        assert rules.act_spec("batch", "seq", "ffn") == P(("data",), None, None)

    def test_duplicate_axis_suppressed(self):
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import compat_make_mesh
        from repro.runtime.sharding import ShardingRules

        mesh = compat_make_mesh((1, 1), ("data", "model"))
        rules = ShardingRules(mesh=mesh)
        # vocab and heads both map to "model": second use must drop
        spec = rules.param_spec("vocab", "heads")
        assert spec == P("model", None)

    def test_constrain_noop_without_context(self):
        from repro.runtime.sharding import constrain

        x = jnp.ones((4, 4))
        np.testing.assert_array_equal(np.asarray(constrain(x, "batch", None)),
                                      np.ones((4, 4)))


class TestCompensatedPsum:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
    def test_exact_and_finite_across_the_range(self, dtype):
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.runtime.collectives import compensated_psum

        fi = jnp.finfo(dtype)
        x = jnp.asarray([1.0 / 3, -2.5e-30, float(fi.max) / 2,
                         -float(fi.max) / 7, 7.0], dtype)
        mesh = Mesh(np.array(jax.devices()[:1]), ("d",))
        out = jax.shard_map(lambda v: compensated_psum(v, "d", slices=3),
                            mesh=mesh, in_specs=P("d"), out_specs=P())(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
