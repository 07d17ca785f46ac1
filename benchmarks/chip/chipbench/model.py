"""A model's shapes as the benchmark reads them from a configuration file.

Configuration files use the published `config.json` key names; this is
the one place that reads them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict


@dataclasses.dataclass(frozen=True)
class Shape:
    name: str
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm_eps: float
    rope_theta: float
    dtype: str

    @classmethod
    def from_config(cls, name: str, c: Dict[str, Any]) -> "Shape":
        if c.get("hidden_act", "silu") != "silu":
            raise ValueError(f"{name}: only SwiGLU (silu) models are served")
        if c.get("tie_word_embeddings", False):
            raise ValueError(f"{name}: tied embeddings are not supported")
        return cls(
            name=name, d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim",
                           c["hidden_size"] // c["num_attention_heads"]),
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"],
            dtype=c["torch_dtype"])

    @property
    def bytes_per_param(self) -> int:
        return {"bfloat16": 2, "float16": 2, "float32": 4}[self.dtype]
