"""The sizes of a configuration, read from its published ``config.json``
keys as the configuration file holds them.  Where the program cannot serve
a published value, the file states the served one beside it
(``served_dtype``, ``served_rms_norm_eps``)."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Shape:
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

    @classmethod
    def from_config(cls, cfg: dict) -> "Shape":
        d = int(cfg["hidden_size"])
        heads = int(cfg["num_attention_heads"])
        rms = "rms_norm_eps" in cfg
        return cls(
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

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * 2
