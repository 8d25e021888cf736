"""Launch layer: sharding resolver, step plans on a local mesh, hlo_cost."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import make_mesh
from repro.configs import ARCHS, SHAPES, get_arch
from repro.configs.base import ShapeConfig
from repro.launch import sharding as sh
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.roofline import model_flops
from repro.launch.steps import make_decode_step, make_prefill_step, make_train_step


def mesh2():
    return make_mesh((1, 1), ("data", "model"))


# --------------------------------------------------------------------------
# resolver
# --------------------------------------------------------------------------
def test_resolver_divisibility_fallback():
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = sh.Rules(table={"heads": ("model",), "embed": ("data",), None: ()})
    # divisible -> sharded (axis size 1 divides everything)
    spec = sh.resolve_pspec(("embed", "heads", None), (64, 8, 16), mesh, rules)
    assert spec == P("data", "model", None)


def test_resolver_nondivisible_replicates():
    # fake a larger mesh via the production mesh helper is not possible on
    # 1 device; test the pure logic with a mock mesh object instead.
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    rules = sh.Rules(table={"kv_heads": ("model",), "embed": ("data",), None: ()})
    spec = sh.resolve_pspec(("embed", "kv_heads"), (64, 8), FakeMesh(), rules)
    assert spec == P("data", None)  # kv=8 not divisible by 16 -> replicated
    spec = sh.resolve_pspec(("embed", "kv_heads"), (60, 32), FakeMesh(), rules)
    assert spec == P(None, "model")  # 60 % 16 != 0 -> embed replicated


def test_resolver_multi_axis_dim():
    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}

    rules = sh.Rules(table={"embed": ("pod", "data"), None: ()})
    spec = sh.resolve_pspec(("embed", None), (18432, 8), FakeMesh(), rules)
    assert spec == P(("pod", "data"), None)


def test_resolver_axis_used_once_per_leaf():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 4}

    rules = sh.Rules(
        table={"batch": ("data", "model"), "seq": ("data", "model"), None: ()}
    )
    spec = sh.resolve_pspec(("batch", "seq"), (16, 64), FakeMesh(), rules)
    # batch takes data+model; seq gets nothing (both consumed)
    assert spec == P(("data", "model"), None)


def test_vector_params_replicated():
    mesh = mesh2()
    rules = sh.train_rules(get_arch("qwen3-32b"))
    assert sh.resolve_pspec(("embed",), (5120,), mesh, rules) == P()


# --------------------------------------------------------------------------
# step plans lower + run on the local 1x1 mesh (real execution!)
# --------------------------------------------------------------------------
def tiny_shape(kind):
    return ShapeConfig(f"tiny_{kind}", seq_len=32, global_batch=2, kind=kind)


@pytest.mark.parametrize("arch", ["qwen3-32b", "granite-moe-1b-a400m", "rwkv6-3b",
                                  "zamba2-2.7b", "gemma3-12b"])
def test_train_plan_executes(arch):
    cfg = ARCHS[arch].reduced()
    mesh = mesh2()
    plan = make_train_step(cfg, mesh, tiny_shape("train"))
    fn = plan.jitted()
    from repro.models import build_model
    from repro import optim

    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    opt = optim.init(params, optim.AdamWConfig(state_dtype=cfg.optim_state_dtype))
    batch = (
        {"embeds": jnp.ones((2, 32, cfg.d_model), cfg.compute_dtype) * 0.01}
        if cfg.frontend
        else {"tokens": jnp.ones((2, 32), jnp.int32)}
    )
    batch["labels"] = jnp.zeros((2, 32), jnp.int32)
    with mesh:
        p2, o2, metrics = fn(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("arch", ["starcoder2-7b", "zamba2-2.7b"])
def test_decode_plan_executes(arch):
    cfg = ARCHS[arch].reduced()
    mesh = mesh2()
    plan = make_decode_step(cfg, mesh, tiny_shape("decode"))
    fn = plan.jitted()
    from repro.models import build_model

    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    cache = m.init_cache(2, 32)
    batch = (
        {"embeds": jnp.ones((2, 1, cfg.d_model), cfg.compute_dtype) * 0.01}
        if cfg.frontend
        else {"tokens": jnp.ones((2, 1), jnp.int32)}
    )
    with mesh:
        logits, cache2 = fn(params, cache, batch, jnp.asarray(3, jnp.int32))
    assert logits.shape == (2, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()


# --------------------------------------------------------------------------
# hlo_cost: white-box validation against known programs
# --------------------------------------------------------------------------
def test_hlo_cost_scan_flops_exact():
    def f(x, ws):
        def body(c, w):
            return c @ w, ()
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jnp.zeros((64, 64), jnp.float32)
    ws = jnp.zeros((7, 64, 64), jnp.float32)
    c = jax.jit(f).lower(x, ws).compile()
    cost = analyze_hlo(c.as_text())
    assert cost.flops == pytest.approx(7 * 2 * 64**3, rel=1e-6)
    assert cost.n_while == 1


def test_hlo_cost_nested_scan():
    def f(x, ws):
        def outer(c, w):
            def inner(ci, _):
                return ci @ w, ()
            c2, _ = jax.lax.scan(inner, c, jnp.arange(3))
            return c2, ()
        y, _ = jax.lax.scan(outer, x, ws)
        return y

    x = jnp.zeros((32, 32), jnp.float32)
    ws = jnp.zeros((5, 32, 32), jnp.float32)
    c = jax.jit(f).lower(x, ws).compile()
    cost = analyze_hlo(c.as_text())
    assert cost.flops == pytest.approx(5 * 3 * 2 * 32**3, rel=1e-6)


def test_model_flops_sane():
    for arch in ("qwen3-32b", "granite-moe-1b-a400m", "rwkv6-3b"):
        cfg = get_arch(arch)
        for s in SHAPES.values():
            f = model_flops(cfg, s)
            assert f > 0
    # train >= prefill >= decode per token
    cfg = get_arch("qwen3-32b")
    tr = model_flops(cfg, SHAPES["train_4k"])
    pf = model_flops(cfg, SHAPES["prefill_32k"])
    assert tr > 0 and pf > 0
