"""Model FLOPs of one trained token of a dense decoder-only transformer.

The PaLM convention (Chowdhery et al. 2022, appendix B):

    flops_per_token = 6 * N + 12 * L * d_attn * seq_len

- ``N`` counts every parameter that a token's forward pass multiplies by
  or adds: the layers' matrices, biases and norm scales, and the embedding
  once (tied to the output head, it is the head's matrix product).
- ``6 N`` is the forward pass (2 N) and the backward pass (4 N).
- ``12 L d_attn s`` is attention's score and value products, forward and
  backward, over the full ``s x s`` scores, with ``d_attn`` the heads times
  the head size.
- Recomputation (remat) is not counted: it is work the chip does that the
  model does not need.
"""
from __future__ import annotations

from typing import Any, Dict


def params(model: Dict[str, Any]) -> int:
    d, ff, n = model["d_model"], model["d_ff"], model["n_layers"]
    d_attn = model["n_heads"] * model["head_dim"]
    d_kv = model["n_kv_heads"] * model["head_dim"]
    ffn_mats = 3 if model["ffn_act"] == "swiglu" else 2
    bias = d_attn + 2 * d_kv if model.get("qkv_bias") else 0
    per_layer = (d * d_attn + 2 * d * d_kv + d_attn * d + bias
                 + ffn_mats * d * ff + 2 * d)
    head = 0 if model["tie_embeddings"] else model["vocab_size"] * d
    return n * per_layer + model["vocab_size"] * d + head + d


def flops_per_token(model: Dict[str, Any], seq_len: int) -> float:
    d_attn = model["n_heads"] * model["head_dim"]
    return 6.0 * params(model) + 12.0 * model["n_layers"] * d_attn * seq_len
