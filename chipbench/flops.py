"""Operations and bytes that the work needs, reckoned from shapes.

Kept with the benchmark so that no PR can change how its own gain is
counted.  Counts are of the algorithm, not of what a program happens to
execute: padding, recomputation and masked attention rows do not count.
"""

from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def matmul_params(cfg: dict) -> int:
    """Weights that every token multiplies in the decoder stack (attention
    projections and the gated MLP), from a configuration in Hugging Face
    key names."""
    d = cfg["hidden_size"]
    hd = head_dim(cfg)
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    ff = cfg["intermediate_size"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer


def token_flops(cfg: dict, context: int, *, logits: bool) -> float:
    """Model FLOPs of one token that attends over ``context`` positions
    (itself included): 2 per weight of the stack, 4·heads·head_dim per
    attended position and layer (scores and values), and the output head
    where the token's logits are used."""
    attn = (4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * head_dim(cfg) * context)
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"] if logits else 0
    return 2.0 * matmul_params(cfg) + attn + head


def prefill_flops(cfg: dict, start: int, stop: int) -> float:
    """Prompt positions [start, stop): position p attends over p + 1."""
    n = stop - start
    if n <= 0:
        return 0.0
    ctx_sum = (start + 1 + stop) * n / 2.0          # sum of p + 1
    attn = (4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * head_dim(cfg) * ctx_sum)
    return 2.0 * matmul_params(cfg) * n + attn


def stencil_bytes_per_cell(itemsize: int = 4) -> int:
    """Least HBM traffic of one leapfrog update of one cell: u, u_prev and
    the velocity model read once, u_next written once."""
    return 4 * itemsize
