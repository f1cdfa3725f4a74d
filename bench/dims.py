"""Shapes of a dense GQA decoder, read from a configuration file that
uses the key names of the model's published ``config.json``."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    qk_norm: bool
    tie_embeddings: bool

    @property
    def kv_bytes_per_token(self) -> int:
        """bf16 K and V of one token over every layer."""
        return self.n_layers * 2 * self.n_kv_heads * self.head_dim * 2


def load_config(name: str) -> dict:
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


def dims_of(spec: dict) -> Dims:
    """Dense GQA decoder shapes from a configuration file.  Rejects a
    configuration whose block this reference does not describe."""
    if spec.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{spec['name']}: only SwiGLU MLPs are described")
    if spec.get("sliding_window") or spec.get("use_sliding_window"):
        raise ValueError(f"{spec['name']}: sliding windows not described")
    if spec.get("attention_bias"):
        raise ValueError(f"{spec['name']}: attention biases not described")
    return Dims(
        name=spec["name"],
        n_layers=int(spec["num_hidden_layers"]),
        d_model=int(spec["hidden_size"]),
        n_heads=int(spec["num_attention_heads"]),
        n_kv_heads=int(spec["num_key_value_heads"]),
        head_dim=int(spec["head_dim"]),
        d_ff=int(spec["intermediate_size"]),
        vocab=int(spec["vocab_size"]),
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]),
        # RMSNorm of q and k per head (over head_dim) before RoPE, as
        # Qwen3's attention has; config.json has no key for it, so the
        # file states it under ``assumed``
        qk_norm=bool(spec["assumed"]["qk_norm"]),
        tie_embeddings=bool(spec.get("tie_word_embeddings", False)),
    )
