"""FLOPs of a GQA model's prefill and decode step, from shapes.

Per layer and token: the q/k/v/o projections and the MLP, two FLOPs per
weight.  Attention: QK^T and PV over the positions each query may see,
2 * heads * head_dim FLOPs per (query, key) pair for each; a causal
prefill of S tokens has S (S + 1) / 2 pairs.  A prefill computes logits for
its last position only; a decode step for each slot.
"""

from __future__ import annotations

from bench.lib.modelflops import mlp_per_token, unembed_per_row


def _hd(c: dict) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def _proj_per_token(c: dict) -> int:
    d, h, kv, hd = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], _hd(c)
    return 2 * d * hd * (2 * h + 2 * kv)


def _pair(c: dict) -> int:
    return 2 * 2 * c["num_attention_heads"] * _hd(c)


def prefill(c: dict, s: int) -> int:
    per_layer = s * (_proj_per_token(c) + mlp_per_token(c)) + _pair(c) * s * (s + 1) // 2
    return c["num_hidden_layers"] * per_layer + unembed_per_row(c)


def decode(c: dict, context_lens: list[int]) -> int:
    """One step over live slots whose caches hold ``context_lens`` entries
    (the new token's included)."""
    per_tok = c["num_hidden_layers"] * (_proj_per_token(c) + mlp_per_token(c)) + unembed_per_row(c)
    attn = c["num_hidden_layers"] * _pair(c) * sum(context_lens)
    return len(context_lens) * per_tok + attn
