"""End-to-end system tests: training converges, failover recovers mid-run,
serving generates, the precision policy engages, HLO cost parsing is sane."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_train_loss_decreases_and_failover_recovers(tmp_path):
    from repro.launch.train import train

    out = train("qwen3-0.6b", steps=90, batch=4, seq=64,
                ckpt_dir=str(tmp_path), inject_failure_at=45,
                verbose=False)
    losses = out["losses"]
    assert out["failures"] == 1  # injected failure was recovered
    # synthetic-markov LM at 90 short steps: modest but monotone progress
    assert np.mean(losses[-10:]) < 0.97 * np.mean(losses[:10]), (
        losses[:10], losses[-10:])


def test_train_ssm_family(tmp_path):
    from repro.configs.base import RunConfig
    from repro.launch.train import train

    # 40 short steps: the default warmup (10 steps) burns a quarter of the
    # run at reduced LR and leaves the loss drop marginal — configure the
    # short run explicitly so the test checks learning, not the schedule
    rc = RunConfig(learning_rate=2e-3, warmup_steps=5, total_steps=40,
                   param_dtype="float32", microbatches=1)
    out = train("xlstm-350m", steps=40, batch=4, seq=64, run_cfg=rc,
                verbose=False)
    losses = out["losses"]
    assert np.mean(losses[-5:]) < 0.95 * np.mean(losses[:5])


def test_serve_batched_generates():
    from repro.configs import get_config
    from repro.launch.serve import BatchedServer, Request
    from repro.launch.train import reduce_cfg
    from repro.models import model as M

    cfg = reduce_cfg(get_config("qwen3-0.6b"), d_model=64)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    server = BatchedServer(cfg, params, batch_slots=2, max_len=64)
    for rid in range(4):
        server.submit(Request(rid=rid, prompt=[1, 2, 3], max_new=5))
    done = server.run()
    assert len(done) == 4
    assert all(len(r.generated) == 5 for r in done)
    assert all(0 <= t < cfg.vocab_size for r in done for t in r.generated)


def test_precision_policy_dd_head():
    from repro.configs import get_config
    from repro.launch.train import reduce_cfg
    from repro.models import model as M

    cfg = reduce_cfg(get_config("qwen3-0.6b"), d_model=64)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32),
    }
    l_native, _ = M.train_loss(params, cfg, batch, policy={})
    l_dd, _ = M.train_loss(params, cfg, batch, policy={"lm_head": "dd"})
    # dd logits agree with native at f32 level but are not bitwise equal
    assert abs(float(l_native) - float(l_dd)) < 1e-3
    # grads flow through the dd head (straight-through vjp)
    g = jax.grad(lambda p: M.train_loss(p, cfg, batch,
                                        policy={"lm_head": "dd"})[0])(params)
    assert float(jnp.abs(g["embed"]).sum()) > 0


def test_hlo_cost_trip_count_accounting():
    from repro.launch.hlo_cost import analyze_hlo

    n, L, MB = 128, 4, 3

    def f(x, ws):
        def body(c, _):
            y, _ = jax.lax.scan(lambda cc, w: (cc @ w, None), c, ws)
            return y, None
        out, _ = jax.lax.scan(body, x, None, length=MB)
        return out

    hlo = jax.jit(f).lower(
        jax.ShapeDtypeStruct((n, n), jnp.float32),
        jax.ShapeDtypeStruct((L, n, n), jnp.float32)).compile().as_text()
    c = analyze_hlo(hlo)
    assert c.flops == pytest.approx(2 * n**3 * L * MB, rel=0.01)
    assert sorted(c.while_trip_counts.values()) == [MB, L]


def test_roofline_report_terms():
    from repro.configs import SHAPES, get_config
    from repro.launch.roofline import model_flops, roofline_report

    cfg = get_config("qwen3-0.6b")
    shape = SHAPES["train_4k"]
    mf = model_flops(cfg, shape)
    assert 3e15 < mf < 1e16  # ~6*N*D + attention
    rep = roofline_report(cfg, shape, flops_per_dev=mf / 256 * 1.5,
                          bytes_per_dev=1e12,
                          coll={"total": 1e11}, n_devices=256)
    assert rep["bottleneck"] in ("compute", "memory", "collective")
    assert 0 < rep["roofline_fraction"] <= 1.0
    assert 0 < rep["useful_ratio"] <= 1.0


def test_validate_spec():
    from jax.sharding import PartitionSpec as P

    from repro.launch.steps import validate_spec

    class FakeMesh:
        shape = {"model": 16, "data": 4}

    assert validate_spec(FakeMesh, P("model", None), (32, 7)) == P("model", None)
    assert validate_spec(FakeMesh, P("model",), (8,)) == P(None)
    assert validate_spec(FakeMesh, P(("data", "model"),), (64,)) == P(("data", "model"))
    assert validate_spec(FakeMesh, P(("data", "model"),), (32,)) == P(None)
