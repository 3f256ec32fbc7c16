"""Operations and bytes of the dense decoder's work, from its shapes.

``m`` is a configuration file's ``model`` section. Positions count from
0, so a token at position ``pos`` attends to ``pos + 1`` keys. Weights
and the key/value cache are bfloat16 (2 bytes), the served type.
"""
from __future__ import annotations

BYTES = 2


def layer_params(m: dict) -> int:
    """Matrix parameters of all layers (norm weights are negligible)."""
    D, F = m["hidden_size"], m["intermediate_size"]
    H, K, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    return m["num_hidden_layers"] * (D * hd * (2 * H + 2 * K) + 3 * D * F)


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def kv_bytes_per_position(m: dict) -> int:
    """Key and value bytes one lane stores per position, all layers."""
    return (2 * m["num_hidden_layers"] * m["num_key_value_heads"]
            * m["head_dim"] * BYTES)


def flops_per_token(m: dict, pos: float) -> float:
    """Model FLOPs to score one token at position ``pos``: the matrix
    products, the head, and attention's two products over pos + 1 keys."""
    attn = (4 * m["num_hidden_layers"] * m["num_attention_heads"]
            * m["head_dim"] * (pos + 1))
    return 2.0 * (layer_params(m) + head_params(m)) + attn


def decode_step_bytes(m: dict, lanes: float, pos: float) -> float:
    """Least bytes one decode step over ``lanes`` coding lanes at mean
    position ``pos`` must move: every weight once (the input embedding
    only its lanes' rows), the cache read up to each lane's position and
    written at it, and the lanes' logits."""
    D, V = m["hidden_size"], m["vocab_size"]
    weights = (layer_params(m) + head_params(m) + lanes * D) * BYTES
    kv = lanes * (pos + 2) * kv_bytes_per_position(m)
    return weights + kv + lanes * V * BYTES


def decode_step_seconds(m: dict, peaks: dict, lanes: float,
                        pos: float) -> float:
    """Least time of one decode step on a chip with ``peaks``: the larger
    of its operations over peak FLOP/s and its bytes over peak bandwidth."""
    return max(lanes * flops_per_token(m, pos) / peaks["bf16_flops"],
               decode_step_bytes(m, lanes, pos) / peaks["hbm_bytes_per_s"])
