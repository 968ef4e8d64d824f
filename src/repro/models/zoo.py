"""Model zoo: build / batch / input-spec helpers over ArchConfig."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig
from . import transformer

__all__ = ["init", "init_params", "loss_fn", "forward", "prefill", "prefill_chunk",
           "prefill_packed", "step_packed", "step_spec",
           "supports_chunked_prefill",
           "supports_paged_kv", "decode_step", "init_cache",
           "init_paged_cache", "map_paged_caches", "copy_paged_blocks",
           "make_batch", "input_specs"]

init = transformer.init
init_params = transformer.init_params
loss_fn = transformer.loss_fn
forward = transformer.forward
prefill = transformer.prefill
prefill_chunk = transformer.prefill_chunk
prefill_packed = transformer.prefill_packed
step_packed = transformer.step_packed
step_spec = transformer.step_spec
supports_chunked_prefill = transformer.supports_chunked_prefill
supports_paged_kv = transformer.supports_paged_kv
decode_step = transformer.decode_step
init_cache = transformer.init_cache
init_paged_cache = transformer.init_paged_cache
map_paged_caches = transformer.map_paged_caches
copy_paged_blocks = transformer.copy_paged_blocks


def token_seq_len(cfg: ArchConfig, seq_len: int) -> int:
    """Backbone sequence is seq_len; VLM prefixes patches inside it."""
    if cfg.frontend == "vision":
        return seq_len - cfg.num_patches
    return seq_len


def make_batch(cfg: ArchConfig, shape: ShapeConfig, rng: np.random.Generator):
    """Concrete small batch for CPU smoke tests / examples."""
    b, s = shape.global_batch, shape.seq_len
    st = token_seq_len(cfg, s)
    batch = {}
    if shape.kind in ("train", "prefill"):
        batch["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (b, st)), jnp.int32)
        if shape.kind == "train":
            batch["labels"] = jnp.asarray(
                rng.integers(0, cfg.vocab_size, (b, st)), jnp.int32)
    if cfg.frontend == "vision":
        batch["patches"] = jnp.asarray(
            rng.standard_normal((b, cfg.num_patches, cfg.frontend_dim)),
            jnp.float32)
    if cfg.encoder_decoder:
        batch["frames"] = jnp.asarray(
            rng.standard_normal((b, cfg.enc_seq, cfg.d_model)), jnp.float32)
    return batch


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell — the
    dry-run lowers against these (no allocation)."""
    b, s = shape.global_batch, shape.seq_len
    st = token_seq_len(cfg, s)
    sds = jax.ShapeDtypeStruct
    specs: dict = {}
    if shape.kind in ("train", "prefill"):
        specs["tokens"] = sds((b, st), jnp.int32)
        if shape.kind == "train":
            specs["labels"] = sds((b, st), jnp.int32)
    else:  # decode
        specs["token"] = sds((b,), jnp.int32)
        specs["pos"] = sds((b,), jnp.int32)
        specs["caches"] = jax.eval_shape(
            lambda: init_cache(cfg, b, s))
    if cfg.frontend == "vision" and shape.kind != "decode":
        specs["patches"] = sds((b, cfg.num_patches, cfg.frontend_dim), jnp.float32)
    if cfg.encoder_decoder and shape.kind != "decode":
        specs["frames"] = sds((b, cfg.enc_seq, cfg.d_model), jnp.float32)
    return specs


def abstract_params(cfg: ArchConfig):
    """(ShapeDtypeStruct params tree, axes tree) without allocating.  The
    axes tree is plain Python built during tracing, captured via side box."""
    box = {}

    def f(k):
        p, a = init(cfg, k)
        box["axes"] = a
        return p

    params = jax.eval_shape(f, jax.random.key(0))
    return params, box["axes"]
