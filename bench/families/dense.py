"""The dense family: decoder-only transformers whose every layer is the same
pre-norm block of grouped-query attention with rotary positions and one
MLP (SwiGLU, or GELU with the tanh approximation), with an untied head.
yi-6b and starcoder2-15b are of this family.  Departures from the published
models, shared with the program and listed in each configuration file: no
linear biases, and full attention in place of a sliding window that no
sequence of the cell reaches.

What the harness reads of a family (``cells.load_family``): the shape of a
configuration, the weights from a seed, the plain reference's gaps, the
agreement with the program's own configuration, and, on the shape, the
work counts of ``bench/work.py``.  The shared pieces (``seed_key`` and the
uniform matrices of ``bench/weights.py``; the fp8 control, norms, rotary
positions, blocked causal attention and the gap arithmetic of
``bench/reference.py``) are imported, not copied."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench import reference as ref
from bench.weights import dense as _dense, norm as _norm, seed_key


# ------------------------------------------------------------------ shape
@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of a configuration, read from its published ``config.json``
    keys as the configuration file holds them.  Where the program cannot
    serve a published value, the file states the served one beside it
    (``served_dtype``, ``served_rms_norm_eps``)."""
    d_model: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str            # "rms" | "layer"
    gated: bool          # SwiGLU (silu gate) or a plain GELU MLP
    norm_eps: float
    rope_theta: float
    dtype: str = "bfloat16"

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * 2

    def matmul_flops_per_token(self) -> int:
        """Projections and MLP of every layer, per token (2 FLOPs a MAC)."""
        attn = self.d_model * (self.heads + 2 * self.kv_heads) \
            * self.head_dim + self.heads * self.head_dim * self.d_model
        mlp = (3 if self.gated else 2) * self.d_model * self.d_ff
        return 2 * self.layers * (attn + mlp)


def shape_from_config(cfg: dict) -> Shape:
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    rms = "rms_norm_eps" in cfg
    return Shape(
        d_model=d, layers=int(cfg["num_hidden_layers"]), heads=heads,
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg.get("head_dim") or d // heads),
        d_ff=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        norm="rms" if rms else "layer",
        gated=cfg["hidden_act"] == "silu",
        norm_eps=float(cfg.get("served_rms_norm_eps", cfg["rms_norm_eps"])
                       if rms else cfg["norm_epsilon"]),
        rope_theta=float(cfg["rope_theta"]),
        dtype=cfg.get("served_dtype", "bfloat16"))


def program_config(config: dict, shape: Shape):
    """The program's own configuration of this architecture, cut to the
    configuration file's depth; every width must agree with the file."""
    from repro.configs import get_config
    prog = config["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]),
                              num_layers=shape.layers,
                              **prog.get("replace", {}))
    got = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.resolved_head_dim, cfg.d_ff, cfg.vocab_size, cfg.norm,
           cfg.mlp == "swiglu", cfg.rope_theta, cfg.dtype,
           cfg.tie_embeddings, tuple(cfg.block_pattern))
    want = (shape.d_model, shape.heads, shape.kv_heads, shape.head_dim,
            shape.d_ff, shape.vocab, shape.norm, shape.gated,
            shape.rope_theta, shape.dtype, False, ("full",))
    if got != want:
        raise ValueError(f"program config {prog['arch']} {got} differs from "
                         f"the configuration file {want}")
    return cfg


# ---------------------------------------------------------------- weights
def _layer(s: Shape, key) -> dict:
    d, h, kv, hd, f = s.d_model, s.heads, s.kv_heads, s.head_dim, s.d_ff
    k = jax.random.split(key, 7)
    mlp = {"w_up": _dense(k[4], (d, f), d, s.dtype),
           "w_down": _dense(k[5], (f, d), f, s.dtype)}
    if s.gated:
        mlp["w_gate"] = _dense(k[6], (d, f), d, s.dtype)
    return {
        "ln1": _norm(s),
        "attn": {"wq": _dense(k[0], (d, h, hd), d, s.dtype),
                 "wk": _dense(k[1], (d, kv, hd), d, s.dtype),
                 "wv": _dense(k[2], (d, kv, hd), d, s.dtype),
                 "wo": _dense(k[3], (h, hd, d), h * hd, s.dtype)},
        "ln2": _norm(s),
        "mlp": mlp,
    }


def init_weights(s: Shape, seed: int) -> dict:
    """The whole tree, built by one jitted program on the device in the
    served type, laid out as the serving engine takes it: ``embed [V, d]``,
    ``head [d, V]``, ``ln_f``, and one ``groups`` entry whose leaves carry
    a leading layer axis.  Layers are made one at a time inside a scan, so
    no more than one layer's float32 temporaries are live."""

    @jax.jit
    def build(key):
        k_embed, k_head, k_layers = jax.random.split(key, 3)
        _, layers = jax.lax.scan(lambda c, k: (c, _layer(s, k)), None,
                                 jax.random.split(k_layers, s.layers))
        return {"embed": _dense(k_embed, (s.vocab, s.d_model), s.vocab,
                                s.dtype),
                "head": _dense(k_head, (s.d_model, s.vocab), s.d_model,
                               s.dtype),
                "ln_f": _norm(s),
                "groups": [layers]}

    return build(seed_key(seed))


# -------------------------------------------------------------- reference
def _mlp(s: Shape, p, x, control):
    up = ref.linear(x, p["w_up"], "sd,df->sf", 0, control)
    if s.gated:
        gate = ref.linear(x, p["w_gate"], "sd,df->sf", 0, control)
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up, approximate=True)
    return ref.linear(h, p["w_down"], "sf,fd->sd", 0, control)


@functools.partial(jax.jit, static_argnames=("s", "control"))
def _logits(s: Shape, weights, tokens, idx, *, control: bool):
    """The published architecture at float32: token embedding; per layer a
    pre-norm, causal GQA with rotary positions, a residual, a second
    pre-norm and the MLP, a residual; a final norm and the untied head,
    at the rows ``idx``.  Layers run one at a time inside a scan."""
    pos = jnp.arange(tokens.shape[0])
    x = weights["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        h = ref.norm(s, p["ln1"], x)
        a = p["attn"]
        q = ref.rope(ref.linear(h, a["wq"], "sd,dhk->shk", 0, control), pos,
                     s.rope_theta)
        k = ref.rope(ref.linear(h, a["wk"], "sd,dhk->shk", 0, control), pos,
                     s.rope_theta)
        v = ref.linear(h, a["wv"], "sd,dhk->shk", 0, control)
        o = ref.attention(s, q, k, v).reshape(tokens.shape[0], -1)
        wo = a["wo"].reshape(s.heads * s.head_dim, s.d_model)
        x = x + ref.linear(o, wo, "sk,kd->sd", 0, control)
        x = x + _mlp(s, p["mlp"], ref.norm(s, p["ln2"], x), control)
        return x, None

    x, _ = jax.lax.scan(layer, x, weights["groups"][0])
    x = ref.norm(s, weights["ln_f"], x[idx])
    return ref.linear(x, weights["head"], "nd,dv->nv", 0, control)


def served_gaps(s: Shape, weights, prompt, served, *, length: int,
                rows: int, control: bool = False):
    """``reference.served_gaps`` with this family's forward."""
    return ref.served_gaps(_logits, s, weights, prompt, served,
                           length=length, rows=rows, control=control)
