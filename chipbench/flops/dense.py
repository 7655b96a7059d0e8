"""Model FLOPs of one trained token of a dense decoder-only transformer,
and the work of one call of each flash-attention kernel.

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


def attention_kernels(model: Dict[str, Any], traffic: Dict[str, Any]
                      ) -> Dict[str, Dict[str, float]]:
    """FLOPs and bytes of one call of each causal flash-attention kernel
    (``fwd``, the backward's ``dq`` and ``dkv``) at the traffic's
    microbatch: ``rows = global_batch / num_micro`` rows of ``n_heads``
    heads (key and value heads repeated to as many), each of ``seq_len``
    positions and ``head_dim`` wide.

    The work is that of the algorithm, over half the ``S x S`` scores (the
    causal triangle), per row and head:

    - ``fwd``: Q K^T and P V, ``4 S^2 d / 2``; full remat's recompute of
      the forward is a call of its own;
    - ``dq``: Q K^T again (for P), dO V^T and dS K, ``6 S^2 d / 2``;
    - ``dkv``: Q K^T, dO V^T, P^T dO and dS^T Q, ``8 S^2 d / 2``.

    Bytes are what each kernel reads and writes once: Q, K, V, O, dO, dQ,
    dK, dV in the activations' dtype, and the float32 log-sum-exp ``lse``
    and ``di = rowsum(dO * O)``, one number per row, head and position.
    ``fwd`` reads Q, K, V and writes O and ``lse``; ``dq`` reads Q, K, V,
    dO, ``lse`` and ``di`` and writes dQ; ``dkv`` reads the same and writes
    dK and dV."""
    s, d = traffic["seq_len"], model["head_dim"]
    bh = traffic["global_batch"] // traffic["num_micro"] * model["n_heads"]
    half = bh * s * s * d / 2
    act = bh * s * d * (2 if model["dtype"] in ("bfloat16", "float16") else 4)
    row = bh * s * 4
    return {"fwd": {"flops": 4 * half, "bytes": 4 * act + row},
            "dq": {"flops": 6 * half, "bytes": 5 * act + 2 * row},
            "dkv": {"flops": 8 * half, "bytes": 6 * act + 2 * row}}
