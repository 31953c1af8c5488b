"""Per-architecture smoke tests (assignment requirement): a REDUCED config
of the same family runs one forward/train step on CPU with correct output
shapes and no NaNs; decode-vs-prefill consistency is checked for
representative families."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPE_BY_NAME, shape_applicable
from repro.configs.reduced import reduced
from repro.configs.registry import ARCH_IDS, get_config
from repro.models import transformer as T
from repro.optim import adamw
from repro.train import step as S

BATCH, SEQ = 2, 24


def _fp32(cfg):
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_smoke(arch):
    cfg = reduced(get_config(arch))
    key = jax.random.PRNGKey(0)
    params = T.init(key, cfg)
    batch = S.demo_batch(key, cfg, BATCH, SEQ)
    ts = S.make_train_step(cfg, adamw.AdamWConfig(lr=1e-3))
    opt = adamw.init_state(params, adamw.AdamWConfig())
    p2, o2, m = jax.jit(ts)(params, opt, batch)
    for k, v in m.items():
        assert np.isfinite(float(v)), (arch, k, v)
    # optimizer actually moved the params (some leaf must change; bf16
    # leaves can be below update resolution when the grad clip is active)
    changed = any(
        not np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert changed


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_decode(arch):
    cfg = reduced(get_config(arch))
    key = jax.random.PRNGKey(1)
    params = T.init(key, cfg)
    batch = S.demo_batch(key, cfg, BATCH, SEQ)
    logits, aux = T.forward_train(params, batch, cfg, T.Ctx(mode="train"))
    assert logits.shape == (BATCH, SEQ, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()

    caches = T.init_cache(cfg, BATCH, SEQ + 4)
    lg, caches = jax.jit(S.make_prefill_step(cfg))(params, batch, caches)
    assert lg.shape == (BATCH, 1, cfg.vocab)
    tok = jnp.zeros((BATCH,), jnp.int32)
    lg2, caches = jax.jit(S.make_decode_step(cfg))(params, tok, caches,
                                                   jnp.int32(SEQ))
    assert lg2.shape == (BATCH, 1, cfg.vocab)
    assert np.isfinite(np.asarray(lg2, np.float32)).all()


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "gemma3-1b", "rwkv6-7b",
                                  "recurrentgemma-9b", "qwen3-moe-30b-a3b"])
def test_decode_matches_forward(arch):
    """prefill(x[:P]) + decode(x[P]) must equal forward(x[:P+1])[-1]."""
    cfg = _fp32(reduced(get_config(arch)))
    P = 12
    key = jax.random.PRNGKey(2)
    params = T.init(key, cfg)
    full = S.demo_batch(key, cfg, BATCH, P + 1)
    logits_full, _ = T.forward_train(params, full, cfg, T.Ctx(mode="train"))

    pre = {k: (v[:, :P] if v.ndim >= 2 and v.shape[1] == P + 1 else v)
           for k, v in full.items()}
    caches = T.init_cache(cfg, BATCH, P + 1)
    _, caches = T.prefill(params, pre, cfg, T.Ctx(mode="prefill"), caches)
    lg, _ = T.decode_step(params, full["tokens"][:, P], caches,
                          jnp.int32(P), cfg, T.Ctx(mode="decode"))
    a = np.asarray(logits_full[:, P], np.float32)
    b = np.asarray(lg[:, 0], np.float32)
    np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)


def test_shape_applicability_rules():
    skips = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        ok, why = shape_applicable(cfg, SHAPE_BY_NAME["long_500k"])
        if not ok:
            skips.append(arch)
    assert "rwkv6_7b" not in skips
    assert "recurrentgemma_9b" not in skips
    assert "gemma3_1b" not in skips
    assert len(skips) == 7, skips


def test_param_counts_match_scale():
    """Analytic n_params sanity: within 2x of the advertised scale."""
    expect = {"tinyllama_1_1b": 1.1e9, "llama3_2_3b": 3.2e9,
              "qwen2_5_32b": 32e9, "rwkv6_7b": 7e9,
              "qwen3_moe_30b_a3b": 30e9}
    for arch, n in expect.items():
        got = get_config(arch).n_params
        assert 0.5 * n < got < 2.0 * n, (arch, got, n)


def test_stacked_layers_draw_at_the_per_layer_scale():
    """A weight stacked for the scan over layers draws at 1/sqrt(fan_in)
    of one layer: the stacking axis is not a fan-in (at 4 stacked cycles
    it would make every scanned layer's weights sqrt(d/4) times too big)."""
    from repro.models.params import ParamSpec, init_params, stack_specs
    spec = {"w": ParamSpec((256, 64), ("embed", "mlp"), "float32")}
    w = init_params(jax.random.PRNGKey(0), stack_specs(spec, 4))["w"]
    assert w.shape == (4, 256, 64)
    np.testing.assert_allclose(float(jnp.std(w)), 1 / 16, rtol=0.05)
