"""FLOPs of an MLA model's prefill (expanded form) and decode step
(absorbed form, as the engine serves it), from shapes.

Prefill, per layer and token: W_dq, W_uq, W_dkv, W_kr, W_uk, W_uv, W_o and
the MLP; attention 2 * heads * (nope + rope) FLOPs per causal pair for
QK^T and 2 * heads * v_head_dim for PV.

Decode, per layer and slot: W_dq, W_uq, W_dkv, W_kr, W_o and the MLP; the
absorption q_nope W_uk^T (2 * heads * nope * kv_lora) and W_uv on the
latent context (2 * heads * kv_lora * v_head_dim); per cached position the
latent and rope scores (2 * heads * (kv_lora + rope)) and the latent
context (2 * heads * kv_lora).
"""

from __future__ import annotations

from bench.lib.modelflops import mlp_per_token, unembed_per_row


def _shared_proj(c: dict) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    qlr, kvlr = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rd, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return 2 * (d * qlr + qlr * h * (nope + rd) + d * kvlr + d * rd + h * vd * d)


def prefill(c: dict, s: int) -> int:
    h, kvlr = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rd, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    per_tok = _shared_proj(c) + 2 * kvlr * h * (nope + vd) + mlp_per_token(c)
    pairs = s * (s + 1) // 2
    per_layer = s * per_tok + pairs * 2 * h * (nope + rd + vd)
    return c["num_hidden_layers"] * per_layer + unembed_per_row(c)


def decode(c: dict, context_lens: list[int]) -> int:
    h, kvlr = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rd, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    per_tok = _shared_proj(c) + 2 * h * kvlr * (nope + vd) + mlp_per_token(c)
    per_pos = 2 * h * (kvlr + rd) + 2 * h * kvlr
    n_l = c["num_hidden_layers"]
    return len(context_lens) * (n_l * per_tok + unembed_per_row(c)) + n_l * per_pos * sum(context_lens)
