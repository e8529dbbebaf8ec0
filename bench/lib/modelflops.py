"""FLOPs that every attention kind shares: the SwiGLU MLP and the unembed.
Useful work only: the true vocabulary, not the program's padded one."""

from __future__ import annotations


def mlp_per_token(c: dict) -> int:
    return 2 * 3 * c["hidden_size"] * c["intermediate_size"]


def unembed_per_row(c: dict) -> int:
    return 2 * c["hidden_size"] * c["vocab_size"]
