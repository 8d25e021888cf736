"""MoE dispatch equivalence (local gather vs dense vs shard_map EP) and the
serving engine end-to-end."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.configs import ARCHS
from repro.models import build_model
from repro.models.layers import init_params
from repro.models.moe import MoeCtx, moe_apply, moe_template
from repro.serving import EngineConfig, Request, ServeEngine


def moe_cfg(**kw):
    cfg = ARCHS["granite-moe-1b-a400m"].reduced()
    return dataclasses.replace(cfg, **kw)


def make_params(cfg):
    return init_params(moe_template(cfg), jax.random.PRNGKey(0), jnp.float32)


def test_gather_vs_dense_dispatch():
    cfg = moe_cfg(capacity_factor=8.0)  # no drops -> exact equality
    p = make_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model)) * 0.5
    out_g, aux_g = moe_apply(dataclasses.replace(cfg, moe_dispatch="gather"), p, x)
    out_d, aux_d = moe_apply(dataclasses.replace(cfg, moe_dispatch="dense"), p, x)
    np.testing.assert_allclose(out_g, out_d, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(aux_g, aux_d, rtol=1e-5, atol=1e-6)


def test_ep_matches_local():
    """shard_map EP on a 1x1 mesh == the local gather path."""
    cfg = moe_cfg(capacity_factor=8.0)
    p = make_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model)) * 0.5
    out_local, aux_local = moe_apply(cfg, p, x)
    mesh = make_mesh((1, 1), ("data", "model"))
    ctx = MoeCtx(mesh=mesh, batch_axes=("data",), model_axis="model")
    with mesh:
        out_ep, aux_ep = jax.jit(lambda pp, xx: moe_apply(cfg, pp, xx, ctx=ctx))(p, x)
    np.testing.assert_allclose(out_ep, out_local, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(aux_ep, aux_local, rtol=1e-4, atol=1e-6)


def test_ep_grads_flow():
    cfg = moe_cfg()
    p = make_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, cfg.d_model)) * 0.5
    mesh = make_mesh((1, 1), ("data", "model"))
    ctx = MoeCtx(mesh=mesh, batch_axes=("data",), model_axis="model")

    def loss(pp):
        out, aux = moe_apply(cfg, pp, x, ctx=ctx)
        return (out**2).mean() + 0.01 * aux

    with mesh:
        g = jax.jit(jax.grad(loss))(p)
    gn = sum(float(jnp.abs(v).sum()) for v in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0


def test_capacity_drops_tokens():
    cfg = moe_cfg(capacity_factor=0.05)  # force drops
    p = make_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, cfg.d_model)) * 0.5
    out, aux = moe_apply(cfg, p, x)
    assert np.isfinite(np.asarray(out)).all()


# --------------------------------------------------------------------------
# serving engine
# --------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["starcoder2-7b", "rwkv6-3b"])
def test_engine_generates(arch):
    cfg = ARCHS[arch].reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, EngineConfig(slots=2, max_seq=64))
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=5 + i),
                max_new_tokens=4)
        for i in range(4)
    ]
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_drained(max_steps=200)
    assert len(done) == 4
    for r in done:
        assert len(r.out_tokens) == 4
        assert all(0 <= t < cfg.vocab for t in r.out_tokens)


def test_engine_greedy_matches_model():
    """Engine output == argmax decoding straight through the model."""
    cfg = ARCHS["starcoder2-7b"].reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    prompt = np.array([1, 2, 3, 4, 5], dtype=np.int32)
    new = 4

    # reference: naive full-forward argmax loop
    toks = list(prompt)
    from repro.models.model import lm_logits

    for _ in range(new):
        h, _, _ = m.forward(params, {"tokens": jnp.asarray([toks])})
        nxt = int(jnp.argmax(lm_logits(cfg, params, h[:, -1]), axis=-1)[0])
        toks.append(nxt)
    want = toks[len(prompt):]

    eng = ServeEngine(cfg, params, EngineConfig(slots=2, max_seq=32))
    r = Request(rid=0, prompt=prompt, max_new_tokens=new)
    eng.submit(r)
    eng.run_until_drained(max_steps=50)
    assert r.out_tokens == want


def test_engine_continuous_batching_slot_reuse():
    cfg = ARCHS["starcoder2-7b"].reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, EngineConfig(slots=1, max_seq=32))
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=3),
                    max_new_tokens=3) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_drained(max_steps=100)
    assert len(done) == 3  # one slot served all three sequentially
