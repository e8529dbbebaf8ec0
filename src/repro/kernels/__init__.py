"""Pallas TPU kernels for the serving hot spots (+ ops wrappers + oracles).

The paper's serving stack leans on FlashInfer GPU kernels (§6 "all our GPU
kernels for LLM come from FlashInfer"); the TPU-native equivalents live here:

  flash_attention.py  — blocked causal GQA prefill attention
  decode_attention.py — flash-decoding single-token GQA over the KV cache
  rmsnorm.py          — fused RMSNorm
  ops.py              — jit'd wrappers (impl='pallas' | 'ref'; no fallback)
  ref.py              — pure-jnp oracles (shared with the model code)
"""

from repro.kernels import ops, ref
