"""Per-architecture smoke tests (deliverable f): a REDUCED variant of each
assigned family runs one forward + one train step on CPU, asserting output
shapes and finiteness, plus decode-consistency checks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config, param_count
from repro.models import transformer as T
from repro.optim import apply_updates, sgd

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=16):
    b = {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "audio":
        b["audio_embeds"] = jax.random.normal(
            KEY, (B, cfg.encoder.n_ctx, cfg.d_model))
    if cfg.family == "vlm":
        b["vision_embeds"] = jax.random.normal(
            KEY, (B, cfg.vision.n_tokens, cfg.vision.embed_dim))
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_shapes_and_finite(arch):
    cfg = get_smoke_config(arch)
    assert cfg.n_layers <= 2 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    params = T.init_params(KEY, cfg)
    batch = _batch(cfg)
    if cfg.family == "audio":
        from repro.models.encdec import decode, encode
        enc = encode(params, cfg, batch["audio_embeds"])
        logits = decode(params, cfg, batch["tokens"], enc)
        expect_s = batch["tokens"].shape[1]
    else:
        logits, _ = T.forward(params, cfg, batch)
        expect_s = batch["tokens"].shape[1] + (
            cfg.vision.n_tokens if cfg.family == "vlm" else 0)
    assert logits.shape == (2, expect_s, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch)
    params = T.init_params(KEY, cfg)
    batch = _batch(cfg)
    opt = sgd(0.1, momentum=0.9)
    state = opt.init(params)

    def loss(p):
        return T.loss_fn(p, cfg, batch)[0]

    l0, grads = jax.value_and_grad(loss)(params)
    upd, state = opt.update(grads, state, params)
    params = apply_updates(params, upd)
    l1 = loss(params)
    assert np.isfinite(float(l0)) and np.isfinite(float(l1))
    assert float(l1) < float(l0)   # one SGD step reduces loss on same batch


@pytest.mark.parametrize("arch", ["stablelm-3b", "gemma2-27b", "mamba2-780m",
                                  "zamba2-2.7b", "deepseek-v3-671b",
                                  "whisper-tiny", "paligemma-3b"])
def test_decode_matches_forward(arch):
    """Prefill+decode at the last position == full forward (high capacity
    MoE so routing is drop-free and deterministic)."""
    cfg = get_smoke_config(arch)
    if cfg.moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = T.init_params(KEY, cfg)
    B, S = 2, 12
    batch = _batch(cfg, B, S)
    prefix = cfg.vision.n_tokens if cfg.family == "vlm" else 0
    if cfg.family == "audio":
        from repro.models.encdec import decode, encode
        enc = encode(params, cfg, batch["audio_embeds"])
        full = decode(params, cfg, batch["tokens"], enc)[:, -1]
    else:
        full = T.forward(params, cfg, batch)[0][:, -1]
    caches = T.make_caches(cfg, B, 32, jnp.float32)
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :-1]
    _, caches = T.prefill(params, cfg, pre, caches)
    pos = jnp.full((B,), prefix + S - 1, jnp.int32)
    d, _ = T.decode_step(params, cfg, batch["tokens"][:, -1:], caches, pos)
    np.testing.assert_allclose(np.asarray(d[:, 0]), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_windowed_ring_prefill_matches_full_cache():
    """Prefill longer than a sliding-window ring (gemma2 local layers under
    prefill_32k — regression for the S > cache_len bug): prefill logits and
    3 subsequent decode steps must match a full-length-cache oracle."""
    from repro.models.attention import init_cache
    cfg = get_smoke_config("gemma2-27b")
    assert cfg.attn_window(0) == 64 and cfg.attn_window(1) is None
    params = T.init_params(KEY, cfg)
    B, S = 2, 96                       # S > window=64 -> ring truncation
    batch = _batch(cfg, B, S)
    caches = T.make_caches(cfg, B, S + 4, jnp.float32)   # local layer -> 64
    assert caches[0].pos.shape[1] == 64
    logits, caches = T.prefill(params, cfg, batch, caches)
    oracle = [init_cache(cfg, B, S + 4, jnp.float32)
              for _ in range(cfg.n_layers)]
    logits_f, oracle = T.prefill(params, cfg, batch, oracle)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_f),
                               rtol=2e-4, atol=2e-4)
    tok = batch["tokens"][:, -1:]
    for step in range(3):
        pos = jnp.full((B,), S + step, jnp.int32)
        a, caches = T.decode_step(params, cfg, tok, caches, pos)
        b, oracle = T.decode_step(params, cfg, tok, oracle, pos)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_full_configs_match_assignment():
    """The exact assigned hyperparameters (spot checks per arch)."""
    c = get_config("olmoe-1b-7b")
    assert (c.n_layers, c.d_model, c.attn.n_heads) == (16, 2048, 16)
    assert (c.moe.n_experts, c.moe.top_k) == (64, 8)
    c = get_config("gemma-2b")
    assert (c.n_layers, c.d_ff, c.attn.n_kv_heads, c.attn.head_dim) == \
        (18, 16384, 1, 256)
    c = get_config("mamba2-780m")
    assert (c.n_layers, c.d_model, c.ssm.d_state) == (48, 1536, 128)
    assert c.attn is None
    c = get_config("zamba2-2.7b")
    assert (c.n_layers, c.d_model, c.ssm.d_state) == (54, 2560, 64)
    c = get_config("stablelm-3b")
    assert (c.n_layers, c.d_model, c.d_ff, c.vocab_size) == \
        (32, 2560, 6912, 50304)
    c = get_config("deepseek-v3-671b")
    assert (c.n_layers, c.d_model, c.moe.n_experts) == (61, 7168, 256)
    assert c.attn.mla is not None and c.moe.n_shared_experts == 1
    c = get_config("gemma2-27b")
    assert (c.n_layers, c.d_model, c.d_ff) == (46, 4608, 36864)
    assert c.attn.attn_logit_softcap == 50.0
    assert c.attn.layer_pattern == ("local", "global")
    c = get_config("nemotron-4-340b")
    assert (c.n_layers, c.d_model, c.d_ff, c.attn.n_kv_heads) == \
        (96, 18432, 73728, 8)
    assert c.activation == "relu2"
    c = get_config("whisper-tiny")
    assert (c.n_layers, c.d_model, c.encoder.n_layers) == (4, 384, 4)
    c = get_config("paligemma-3b")
    assert (c.vocab_size, c.vision.n_tokens) == (257216, 256)


def test_param_counts_in_expected_range():
    """Analytic param counts land near the named model sizes."""
    expected = {
        "olmoe-1b-7b": (6e9, 8.5e9),
        "gemma-2b": (2.0e9, 3.0e9),
        "mamba2-780m": (0.6e9, 1.0e9),
        # single shared attn block (vs the real model's two + LoRA
        # per-invocation adapters) undersizes zamba2 slightly
        "zamba2-2.7b": (1.8e9, 3.4e9),
        "stablelm-3b": (2.4e9, 3.4e9),
        "deepseek-v3-671b": (6.0e11, 7.4e11),
        "gemma2-27b": (2.3e10, 3.1e10),
        "nemotron-4-340b": (3.0e11, 3.8e11),
        "whisper-tiny": (2e7, 6e7),
        "paligemma-3b": (2.0e9, 3.2e9),
    }
    for arch, (lo, hi) in expected.items():
        n = param_count(get_config(arch))
        assert lo <= n <= hi, (arch, n)


def test_ssd_scan_gradient_finite_over_long_chunk():
    """mamba2-780m's 256-position chunk with its fastest-decaying head
    (A = -16, dt = 0.1): the cumulative decay spans e^-400, beyond float32,
    and the gradient must stay finite through the masked intra-chunk
    decay matrix."""
    from repro.models.ssm import ssd_scan
    ks = jax.random.split(KEY, 3)
    b, s, h, p, n = 1, 256, 4, 8, 16
    x = jax.random.normal(ks[0], (b, s, h, p))
    B = jax.random.normal(ks[1], (b, s, 1, n))
    C = jax.random.normal(ks[2], (b, s, 1, n))
    A = -jnp.array([1.0, 4.0, 8.0, 16.0])

    def loss(x, dt):
        return jnp.sum(ssd_scan(x, dt, A, B, C, chunk=256)[0] ** 2)

    grads = jax.grad(loss, argnums=(0, 1))(x, jnp.full((b, s, h), 0.1))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)
