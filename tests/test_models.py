"""Per-arch smoke tests (reduced configs) + prefill/decode equivalence."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, reduced
from repro.configs.base import ShapeConfig
from repro.models import transformer, zoo

SMOKE = ShapeConfig("smoke", 64, 2, "train")


def _smoke_cfg(arch_id):
    cfg = reduced(get_config(arch_id))
    if cfg.moe:   # ample capacity -> deterministic routing for equivalence
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    return cfg


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_train_step(arch_id, rng):
    cfg = _smoke_cfg(arch_id)
    params, axes = zoo.init(cfg, jax.random.key(0))
    batch = zoo.make_batch(cfg, SMOKE, rng)
    loss, parts = jax.jit(lambda p, b: zoo.loss_fn(cfg, p, b))(params, batch)
    assert jnp.isfinite(loss), f"{arch_id} loss not finite"
    assert 0.0 < float(loss) < 20.0
    # gradients flow and are finite
    g = jax.grad(lambda p: zoo.loss_fn(cfg, p, batch)[0])(params)
    flat = jax.tree.leaves(g)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in flat)
    assert any(float(jnp.max(jnp.abs(x))) > 0 for x in flat), "all-zero grads"


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_init_params_matches_init(arch_id):
    """The one-program device init builds exactly init's params."""
    cfg = _smoke_cfg(arch_id)
    want, _ = zoo.init(cfg, jax.random.key(3))
    got = zoo.init_params(cfg, jax.random.key(3))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def test_attention_init_scales_by_fan_in():
    """Projections are scaled by their true fan-in, so random-weight
    attention scores stay O(1) and the softmax is not one-hot."""
    cfg = dataclasses.replace(get_config("yi-6b"), num_layers=1,
                              d_model=512, num_heads=4, num_kv_heads=2,
                              d_ff=1024, vocab_size=256)
    params = zoo.init_params(cfg, jax.random.key(0))
    attn = jax.tree.map(lambda t: t[0], params["groups"][0]["attn"])
    d, hd = cfg.d_model, cfg.resolved_head_dim
    for name, fan_in in (("wq", d), ("wk", d), ("wv", d),
                         ("wo", cfg.num_heads * hd)):
        bound = float(jnp.max(jnp.abs(attn[name].astype(jnp.float32))))
        assert 0.9 / np.sqrt(fan_in) < bound <= 1.0 / np.sqrt(fan_in) + 1e-3


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_forward_shapes(arch_id, rng):
    cfg = _smoke_cfg(arch_id)
    params, _ = zoo.init(cfg, jax.random.key(0))
    batch = zoo.make_batch(cfg, SMOKE, rng)
    x, aux = transformer.forward(cfg, params, batch)
    assert x.shape[0] == SMOKE.global_batch
    assert x.shape[-1] == cfg.d_model
    assert bool(jnp.all(jnp.isfinite(x)))


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_prefill_decode_equals_forward(arch_id, rng):
    """The decode path (ring caches, recurrent states, cross-attn caches)
    must agree with the full-sequence forward at the last position."""
    cfg = _smoke_cfg(arch_id)
    params, _ = zoo.init(cfg, jax.random.key(1))
    B, S = 2, 33   # odd length exercises ring wrap (window 32)
    st = S - (cfg.num_patches if cfg.frontend == "vision" else 0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, st)), jnp.int32)}
    if cfg.frontend == "vision":
        batch["patches"] = jnp.asarray(
            rng.standard_normal((B, cfg.num_patches, cfg.frontend_dim)),
            jnp.float32)
    if cfg.encoder_decoder:
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, cfg.enc_seq, cfg.d_model)), jnp.float32)

    x, _ = transformer.forward(cfg, params, batch)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    full_logits = (x[:, -1] @ head).astype(jnp.float32)

    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :-1]
    _, caches = transformer.prefill(cfg, params, pre, cache_len=2 * S)
    npatch = cfg.num_patches if cfg.frontend == "vision" else 0
    pos = jnp.full((B,), st - 1 + npatch, jnp.int32)
    dec_logits, _ = transformer.decode_step(cfg, params, caches,
                                            batch["tokens"][:, -1], pos)
    scale = float(jnp.max(jnp.abs(full_logits))) + 1e-9
    err = float(jnp.max(jnp.abs(full_logits - dec_logits))) / scale
    assert err < 5e-3, f"{arch_id}: prefill/decode mismatch rel={err:.2e}"


@pytest.mark.parametrize("arch_id,chunk", [
    ("yi-6b", 8),                # full attention, multi-chunk
    ("h2o-danube-3-4b", 8),      # swa ring cache, chunk < window
    ("h2o-danube-3-4b", 48),     # chunk > ring size (write-back tail)
    ("gemma3-4b", 16),           # local/global mixed pattern
])
def test_chunked_prefill_matches_one_shot(arch_id, chunk, rng):
    """Padded, bucketed, chunk-at-a-time prefill into a live fused cache must
    reproduce the one-shot prefill logits and leave an equivalent cache."""
    cfg = _smoke_cfg(arch_id)
    assert transformer.supports_chunked_prefill(cfg)
    params, _ = zoo.init(cfg, jax.random.key(1))
    L, cache_len = 50, 64
    prompt = rng.integers(0, cfg.vocab_size, L).astype(np.int32)
    ref_logits, ref_caches = transformer.prefill(
        cfg, params, {"tokens": jnp.asarray(prompt[None])},
        cache_len=cache_len)

    # two fused rows: row 0 carries the prompt, row 1 stays inactive
    caches = zoo.init_cache(cfg, 2, cache_len)
    logits = None
    for s in range(0, L, chunk):
        n = min(chunk, L - s)
        tok = np.zeros((2, chunk), np.int32)
        tok[0, :n] = prompt[s:s + n]
        logits, caches = transformer.prefill_chunk(
            cfg, params, caches, jnp.asarray(tok),
            jnp.asarray([s, 0], jnp.int32), jnp.asarray([n, 0], jnp.int32))
    scale = float(jnp.max(jnp.abs(ref_logits))) + 1e-9
    err = float(jnp.max(jnp.abs(logits[0] - ref_logits[0]))) / scale
    assert err < 5e-3, f"{arch_id} chunk={chunk}: prefill rel={err:.2e}"

    # decode one step from both caches; the inactive row must not interfere
    tok = jnp.asarray([int(jnp.argmax(ref_logits[0]))] * 2, jnp.int32)
    d_ref, _ = transformer.decode_step(cfg, params, ref_caches, tok[:1],
                                       jnp.asarray([L], jnp.int32))
    d_chk, _ = transformer.decode_step(cfg, params, caches, tok,
                                       jnp.asarray([L, 0], jnp.int32),
                                       active=jnp.asarray([True, False]))
    scale = float(jnp.max(jnp.abs(d_ref))) + 1e-9
    err = float(jnp.max(jnp.abs(d_chk[0] - d_ref[0]))) / scale
    assert err < 5e-3, f"{arch_id} chunk={chunk}: decode rel={err:.2e}"


def test_chunked_prefill_gates_unsupported():
    # universal chunked prefill: only the modality frontends stay one-shot
    for arch_id in ("whisper-tiny", "internvl2-1b"):
        assert not transformer.supports_chunked_prefill(
            reduced(get_config(arch_id))), arch_id
    # recurrent / hybrid / MoE families joined the fast path
    for arch_id in ("rwkv6-7b", "recurrentgemma-9b", "deepseek-moe-16b",
                    "llama4-maverick-400b-a17b"):
        assert transformer.supports_chunked_prefill(
            reduced(get_config(arch_id))), arch_id
    # paged KV needs attention-only blocks: MoE yes, recurrent no
    for arch_id, expect in (("deepseek-moe-16b", True),
                            ("llama4-maverick-400b-a17b", True),
                            ("rwkv6-7b", False),
                            ("recurrentgemma-9b", False),
                            ("whisper-tiny", False)):
        assert transformer.supports_paged_kv(
            reduced(get_config(arch_id))) is expect, arch_id


def test_moe_matches_reference(rng):
    from repro.models import moe as moe_lib
    cfg = _smoke_cfg("deepseek-moe-16b")
    params, _ = moe_lib.moe_init(jax.random.key(0), cfg)
    x = jnp.asarray(rng.standard_normal((2, 32, cfg.d_model)) * 0.5, jnp.float32)
    out = moe_lib.moe_apply(params, x, cfg)
    ref = moe_lib.moe_reference(params, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-4)


def test_moe_capacity_drops_are_bounded(rng):
    import dataclasses as dc
    from repro.models import moe as moe_lib
    cfg = dc.replace(_smoke_cfg("deepseek-moe-16b"), capacity_factor=1.0)
    params, _ = moe_lib.moe_init(jax.random.key(0), cfg)
    x = jnp.asarray(rng.standard_normal((1, 64, cfg.d_model)), jnp.float32)
    out, aux = moe_lib.moe_apply(params, x, cfg, return_aux=True)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert float(aux) > 0.0   # load-balance loss reported


def test_param_count_analytic_close(rng):
    """Analytic param_count tracks the real tree within 10%."""
    for arch_id in ("yi-6b", "rwkv6-7b", "deepseek-moe-16b"):
        cfg = _smoke_cfg(arch_id)
        params, _ = zoo.init(cfg, jax.random.key(0))
        real = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        approx = cfg.param_count()
        assert abs(real - approx) / real < 0.15, (arch_id, real, approx)


def test_long_context_gate():
    from repro.configs import cells
    for aid in ARCH_IDS:
        names = [s for s, _ in cells(aid)]
        cfg = get_config(aid)
        assert ("long_500k" in names) == cfg.supports_long_context
