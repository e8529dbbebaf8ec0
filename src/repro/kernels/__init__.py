"""Pallas TPU kernels for the serving hot spots, their wrappers, and the
pure-jnp attention and RMSNorm the models run.

The paper's serving stack leans on FlashInfer GPU kernels (§6 "all our GPU
kernels for LLM come from FlashInfer"); the TPU-native equivalents live here:

  flash_attention.py  — blocked causal GQA prefill attention
  decode_attention.py — flash-decoding single-token GQA over the KV cache
  rmsnorm.py          — fused RMSNorm
  ops.py              — jit'd wrappers (impl='pallas' | 'ref'; no fallback)
  ref.py              — pure-jnp attention and RMSNorm: what ``repro.models``
                        imports and runs, and what each kernel is tested
                        against

The package imports none of its modules on its own, so importing
``repro.kernels.ref`` (as ``repro.models`` does) loads no Pallas code.
"""
