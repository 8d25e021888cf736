"""Training substrate: checkpoint atomicity/elasticity, trainer fault
recovery, UTP step-ops equivalence (eager == fused == direct jit)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro import optim
from repro.configs import ARCHS
from repro.configs.base import ShapeConfig
from repro.data.pipeline import DataConfig, SyntheticLMDataset
from repro.models import build_model
from repro.train import Checkpointer, Trainer, TrainerConfig, UTPTrainStep


def tiny_cfg():
    return ARCHS["qwen3-32b"].reduced()


# --------------------------------------------------------------------------
# checkpoint
# --------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    state = {
        "a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "b": {"c": jnp.ones((2,), jnp.int32)},
    }
    ck.save(5, state)
    out, step = ck.restore(state)
    assert step == 5
    np.testing.assert_array_equal(out["a"], state["a"])
    np.testing.assert_array_equal(out["b"]["c"], state["b"]["c"])


def test_checkpoint_gc_and_latest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    state = {"x": jnp.zeros((2,))}
    for s in (1, 2, 3, 4):
        ck.save(s, state)
    assert ck.all_steps() == [3, 4]
    assert ck.latest_step() == 4


def test_checkpoint_crc_detects_corruption(tmp_path):
    import json

    ck = Checkpointer(str(tmp_path), keep=3)
    ck.save(1, {"x": jnp.arange(8.0)})
    # tamper: stored CRC no longer matches the array bytes
    d = tmp_path / "step_00000001"
    meta = json.loads((d / "meta.json").read_text())
    meta["crc"]["x"] ^= 0xDEADBEEF
    (d / "meta.json").write_text(json.dumps(meta))
    with pytest.raises(IOError):
        ck.restore({"x": jnp.zeros(8)})


def test_checkpoint_async(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save_async(7, {"x": jnp.ones((4,))})
    ck.wait()
    assert ck.latest_step() == 7


def test_checkpoint_elastic_resharding(tmp_path):
    """Save, then restore with an explicit (trivial) sharding tree — the
    elastic path used when the mesh changes between runs."""
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    ck = Checkpointer(str(tmp_path))
    state = {"w": jnp.arange(16.0).reshape(4, 4)}
    ck.save(1, state)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    out, _ = ck.restore(state, shardings=sh)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(state["w"]))
    assert out["w"].sharding == sh["w"]


# --------------------------------------------------------------------------
# trainer: loss falls, resume works, failures recover
# --------------------------------------------------------------------------
def small_trainer(tmp_path, steps=12, ckpt_every=4):
    cfg = tiny_cfg()
    shape = ShapeConfig("t", seq_len=32, global_batch=4, kind="train")
    mesh = make_mesh((1, 1), ("data", "model"))
    t = Trainer(
        cfg, shape, mesh,
        TrainerConfig(
            steps=steps, ckpt_every=ckpt_every, ckpt_dir=str(tmp_path),
            log_every=100, seed=0,
        ),
        opt_cfg=optim.AdamWConfig(lr=3e-3),
    )
    return t


def test_trainer_loss_decreases(tmp_path):
    t = small_trainer(tmp_path, steps=30)
    out = t.train()
    losses = [m["loss"] for m in out["metrics"]]
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1
    assert out["step"] == 30


def test_trainer_resume(tmp_path):
    t1 = small_trainer(tmp_path, steps=8, ckpt_every=4)
    out1 = t1.train()
    # new trainer, same dir -> resumes at 8 and continues to 12
    t2 = small_trainer(tmp_path, steps=12, ckpt_every=4)
    out2 = t2.train()
    assert out2["step"] == 12
    assert out2["metrics"][0]["step"] == 9  # continued, not restarted


def test_trainer_failure_recovery(tmp_path):
    t = small_trainer(tmp_path, steps=10, ckpt_every=2)
    fail_at = {6}

    def inject(step):
        if step in fail_at:
            fail_at.discard(step)  # fail once
            return True
        return False

    out = t.train(inject_failure=inject)
    assert out["step"] == 10
    assert out["failures"] == 1


def test_trainer_too_many_failures_raises(tmp_path):
    t = small_trainer(tmp_path, steps=10, ckpt_every=2)
    t.tcfg.max_failures = 1
    with pytest.raises(RuntimeError):
        t.train(inject_failure=lambda s: True)


# --------------------------------------------------------------------------
# UTP step ops: the task-tree step == the direct jit step
# --------------------------------------------------------------------------
@pytest.mark.parametrize("executor", ["eager", "fused"])
@pytest.mark.parametrize("m", [1, 2])
def test_utp_train_step_matches_direct(executor, m):
    cfg = tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ocfg = optim.AdamWConfig(lr=1e-3)
    opt = optim.init(params, ocfg)
    B, S = 4, 16
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab),
    }

    def loss_fn(p, b):
        return model.loss(p, b)

    utp = UTPTrainStep(loss_fn, ocfg, microbatches=m, executor=executor)
    p_utp, o_utp, metrics = utp(params, opt, batch)

    # direct reference: microbatched grad accumulation
    def direct(p, o, b):
        mb = jax.tree.map(lambda x: x.reshape((m, B // m) + x.shape[1:]), b)
        gs = [
            jax.grad(lambda pp: loss_fn(pp, jax.tree.map(lambda x: x[i], mb))[0])(p)
            for i in range(m)
        ]
        g = jax.tree.map(lambda *xs: sum(xs) / m, *gs)
        return optim.update(g, o, p, ocfg)

    p_ref, o_ref, _ = direct(params, opt, batch)
    for a, b in zip(jax.tree.leaves(p_utp), jax.tree.leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)
    assert "loss" in metrics or metrics  # metrics aggregated


def test_utp_fused_compiles_once():
    cfg = tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ocfg = optim.AdamWConfig(lr=1e-3)
    opt = optim.init(params, ocfg)
    batch = {
        "tokens": jnp.ones((2, 16), jnp.int32),
        "labels": jnp.ones((2, 16), jnp.int32),
    }
    utp = UTPTrainStep(lambda p, b: model.loss(p, b), ocfg, executor="fused")
    p1, o1, _ = utp(params, opt, batch)
    p2, o2, _ = utp(p1, o1, batch)  # second call reuses cached jit
    assert np.isfinite(float(jax.tree.leaves(p2)[0].sum()))


# --------------------------------------------------------------------------
# data pipeline
# --------------------------------------------------------------------------
def test_data_deterministic_and_learnable():
    dc = DataConfig(vocab=64, seq_len=32, global_batch=4, seed=1)
    ds1 = SyntheticLMDataset(dc)
    ds2 = SyntheticLMDataset(dc)
    b1, b2 = ds1.batch(5), ds2.batch(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])
    # structure: top-1 bigram prediction from the table beats chance by a lot
    table = ds1.table
    toks, labels = b1["tokens"], b1["labels"]
    any_hit = (table[toks] == labels[..., None]).any(-1).mean()
    assert any_hit > 0.9
