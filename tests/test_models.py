"""Model zoo: every arch trains a step; decode == prefill; chunked == exact."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config, make_smoke_batch
from repro.distributed.sharding import single_device_ctx
from repro.models.lm import LM
from repro.models import layers as L
from repro.models.attention import HeadLayout, flash_attention
from repro.models.mamba import MambaConfig, init_mamba, mamba_init_state, mamba_mix
from repro.models.xlstm import (XLSTMConfig, init_mlstm, init_slstm, mlstm_init_state,
                                mlstm_mix, slstm_mix)


def build(arch):
    cfg = get_smoke_config(arch)
    model = LM(cfg, single_device_ctx())
    params, axes = model.init(jax.random.key(0))
    return cfg, model, params


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_backward_finite(arch):
    cfg, model, params = build(arch)
    batch = make_smoke_batch(cfg, 2, 32)
    loss, metrics = jax.jit(model.loss_fn)(params, batch)
    assert jnp.isfinite(loss), (arch, loss)
    grads = jax.grad(lambda p: model.loss_fn(p, batch)[0])(params)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert jnp.all(jnp.isfinite(g)), (arch, path)


@pytest.mark.parametrize("arch", ["internlm2_1_8b", "jamba_1_5_large_398b",
                                  "xlstm_350m", "moonshot_v1_16b_a3b"])
def test_decode_matches_prefill(arch):
    """Greedy next-token from token-by-token decode == prefill's."""
    cfg, model, params = build(arch)
    B, S = 2, 16
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(1, cfg.vocab, (B, S)), jnp.int32)

    lgts_prefill, _ = jax.jit(model.prefill)(params, {"tokens": toks})

    caches = model.init_caches(B, S + 4)
    step = jax.jit(lambda c, t, p: model.decode_step(params, c, t, p, return_logits=True))
    for i in range(S):
        nxt, caches, lgts = step(caches, toks[:, i:i + 1], jnp.int32(i))
    np.testing.assert_allclose(np.asarray(lgts, np.float32),
                               np.asarray(lgts_prefill, np.float32),
                               rtol=0.08, atol=0.08)


def test_flash_attention_matches_naive():
    B, S, Ke, Gq, hd = 2, 64, 2, 2, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, Ke, Gq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Ke, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Ke, hd)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, q_chunk=16, kv_chunk=16)
    # naive reference
    s = jnp.einsum("bskgh,btkh->bkgst", q, k) / np.sqrt(hd)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bkgst,btkh->bskgh", p, v)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref), atol=2e-2)


def test_head_layout_padding_math():
    """deepseek-style: 56 q / 8 kv -> (16, 4) padded grid, 56 real heads."""
    lo = HeadLayout(56, 8, 128, 16)
    assert lo.repl == 2 and lo.eff_kv == 16 and lo.q_per_kv == 4
    assert lo.padded_heads == 64
    assert int(lo.head_mask().sum()) == 56
    lo2 = HeadLayout(24, 8, 96, 16)
    assert lo2.padded_heads == 32 and int(lo2.head_mask().sum()) == 24


def test_mamba_chunked_equals_whole():
    cfg = MambaConfig(d_model=32, d_state=4, d_conv=4, expand=2)
    pb = L.ParamBuilder(jax.random.key(0))
    init_mamba(pb, cfg)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 32, 32)), jnp.float32)
    ctx = single_device_ctx()
    y_chunked, st1 = mamba_mix(pb.params, x, ctx, chunk=8)
    y_whole, st2 = mamba_mix(pb.params, x, ctx, chunk=32)
    np.testing.assert_allclose(np.asarray(y_chunked, np.float32),
                               np.asarray(y_whole, np.float32), atol=3e-2)
    np.testing.assert_allclose(np.asarray(st1["h"]), np.asarray(st2["h"]), atol=3e-2)


def test_mamba_decode_continues_train_state():
    """Running seq then one decode step == running seq+1 at once."""
    cfg = MambaConfig(d_model=16, d_state=4, d_conv=4, expand=2)
    pb = L.ParamBuilder(jax.random.key(1))
    init_mamba(pb, cfg)
    ctx = single_device_ctx()
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 9, 16)), jnp.float32)
    y_all, _ = mamba_mix(pb.params, x, ctx, chunk=9)
    y_pre, st = mamba_mix(pb.params, x[:, :8], ctx, chunk=8)
    y_last, _ = mamba_mix(pb.params, x[:, 8:9], ctx, state=st)
    np.testing.assert_allclose(np.asarray(y_last[:, 0]), np.asarray(y_all[:, 8]), atol=3e-2)


def test_mlstm_chunked_equals_sequential():
    cfg = XLSTMConfig(d_model=32, n_heads=2)
    pb = L.ParamBuilder(jax.random.key(2))
    init_mlstm(pb, cfg)
    ctx = single_device_ctx()
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 16, 32)), jnp.float32)
    y_par, st_par = mlstm_mix(pb.params, x, ctx, chunk=8)
    # sequential: feed one token at a time
    st = mlstm_init_state(2, cfg)
    outs = []
    for i in range(16):
        y, st = mlstm_mix(pb.params, x[:, i:i + 1], ctx, state=st)
        outs.append(y)
    y_seq = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_par, np.float32),
                               np.asarray(y_seq, np.float32), atol=5e-2)


def test_slstm_gradients_finite_over_long_sequence():
    """At xlstm_350m width (4 heads of 256) the recurrent weights' init must
    not make gradients explode through 1024 time steps of the sLSTM scan."""
    cfg = XLSTMConfig(d_model=1024, n_heads=4)
    pb = L.ParamBuilder(jax.random.key(0))
    init_slstm(pb, cfg)
    ctx = single_device_ctx()
    x = jax.random.normal(jax.random.key(1), (1, 1024, 1024), jnp.float32).astype(jnp.bfloat16)

    def loss(p, x):
        out, _ = slstm_mix(p, x, ctx)
        return jnp.mean(jnp.square(out.astype(jnp.float32)))

    grads = jax.jit(jax.grad(loss, argnums=(0, 1)))(pb.params, x)
    assert all(np.all(np.isfinite(np.asarray(g, np.float32))) for g in jax.tree.leaves(grads))


def test_param_count_matches_actual():
    for arch in ("internlm2_1_8b", "moonshot_v1_16b_a3b", "xlstm_350m"):
        cfg, model, params = build(arch)
        actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        analytic = cfg.param_count()
        # analytic counts true (unpadded) heads and no norm weights: within 5%
        assert abs(actual - analytic) / actual < 0.05, (arch, actual, analytic)


def test_vlm_patches_change_output():
    cfg, model, params = build("phi_3_vision_4_2b")
    batch = make_smoke_batch(cfg, 2, 32)
    l1, _ = model.loss_fn(params, batch)
    batch2 = dict(batch)
    batch2["patches"] = batch["patches"] + 1.0
    l2, _ = model.loss_fn(params, batch2)
    assert not np.isclose(float(l1), float(l2))


def test_audio_mask_limits_loss_positions():
    cfg, model, params = build("hubert_xlarge")
    batch = make_smoke_batch(cfg, 2, 32)
    batch["mask"] = np.zeros_like(batch["mask"])
    l0, _ = model.loss_fn(params, batch)
    assert float(l0) == 0.0  # no masked positions -> zero loss
