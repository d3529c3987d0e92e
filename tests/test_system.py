"""End-to-end behaviour: training reduces loss; optimizer; schedules; specs."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import REGISTRY, get_config, reduced
from repro.data.tokens import TokenStream
from repro.launch.train import init_train_state, make_train_step
from repro.models import input_specs, supported_shapes
from repro.models.api import LM_SHAPES
from repro.optim import AdamWConfig, cosine_schedule


def test_train_loop_reduces_loss():
    cfg = reduced(get_config("smollm-360m"))
    opt = AdamWConfig(lr=2e-3)
    state = init_train_state(cfg, jax.random.PRNGKey(0), opt)
    step = jax.jit(make_train_step(cfg, opt, total_steps=60))
    data = TokenStream(cfg, batch=4, seq=64)
    losses = []
    for i in range(50):
        state, metrics = step(state, {k: jnp.asarray(v) for k, v in data(i).items()})
        losses.append(float(metrics["loss"]))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.5, (
        losses[:5], losses[-5:])
    assert int(state.step) == 50


def test_train_loop_moe_reduces_loss():
    cfg = reduced(get_config("granite-moe-3b-a800m"))
    opt = AdamWConfig(lr=2e-3)
    state = init_train_state(cfg, jax.random.PRNGKey(0), opt)
    step = jax.jit(make_train_step(cfg, opt, total_steps=40))
    data = TokenStream(cfg, batch=4, seq=64)
    losses = []
    for i in range(30):
        state, metrics = step(state, {k: jnp.asarray(v) for k, v in data(i).items()})
        losses.append(float(metrics["loss"]))
    # MoE routing stabilizes slower than dense at tiny scale; require a
    # clear monotone improvement rather than a large drop
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.15


def test_adamw_converges_quadratic():
    from repro.optim import adamw_init, adamw_update

    opt = AdamWConfig(lr=0.1, weight_decay=0.0, clip_norm=0.0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    st = adamw_init(params, opt)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, st, _ = adamw_update(grads, st, params, opt)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_grad_clip_bounds_norm():
    from repro.optim import clip_by_global_norm

    g = {"a": jnp.full((10,), 100.0), "b": jnp.full((5,), -100.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    total = jnp.sqrt(sum(jnp.sum(x**2) for x in jax.tree.leaves(clipped)))
    assert abs(float(total) - 1.0) < 1e-4
    assert float(norm) > 100


def test_cosine_schedule_shape():
    lr0 = float(cosine_schedule(0, peak_lr=1.0, warmup_steps=10, total_steps=100))
    lr_peak = float(cosine_schedule(10, peak_lr=1.0, warmup_steps=10, total_steps=100))
    lr_end = float(cosine_schedule(100, peak_lr=1.0, warmup_steps=10, total_steps=100))
    assert lr0 == 0.0 and abs(lr_peak - 1.0) < 1e-6 and abs(lr_end - 0.1) < 1e-6


def test_input_specs_cover_all_cells():
    """Every (arch x supported shape) has well-formed ShapeDtypeStruct specs."""
    n_cells = 0
    for arch, cfg in REGISTRY.items():
        shapes = supported_shapes(cfg)
        if cfg.family in ("ssm", "hybrid"):
            assert any(s.name == "long_500k" for s in shapes), arch
        else:
            assert not any(s.name == "long_500k" for s in shapes), arch
        for shape in shapes:
            specs = input_specs(cfg, shape)
            assert "tokens" in specs
            for k, sd in specs.items():
                assert all(d > 0 for d in sd.shape), (arch, shape.name, k)
            n_cells += 1
    assert n_cells == 32  # 10 train + 10 prefill + 10 decode + 2 long_500k


def test_assigned_shape_table():
    names = [(s.name, s.seq_len, s.global_batch) for s in LM_SHAPES]
    assert names == [
        ("train_4k", 4096, 256),
        ("prefill_32k", 32768, 32),
        ("decode_32k", 32768, 128),
        ("long_500k", 524288, 1),
    ]


def test_compile_cache_dir_env_or_checkout(monkeypatch):
    """The entry points' compile cache: `JAX_COMPILATION_CACHE_DIR` when set
    (JAX reads it; nothing else is configured), else the fixed `.jax_cache/`
    at the checkout root. The config update is captured, not applied, so
    this test leaves the cache off."""
    from pathlib import Path

    from repro.launch import compile_cache

    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/cache")
    assert compile_cache.enable_compile_cache() == "/some/cache"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = Path(__file__).resolve().parents[1]
    assert compile_cache.enable_compile_cache() == str(root / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", str(root / ".jax_cache"))]
