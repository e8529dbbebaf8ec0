"""bench/flops against FLOPs worked out by hand for two tiny models."""

from bench.lib import spec
from bench.tests import tiny


def _flops(attn):
    return spec.load_module(f"{spec.BENCH_DIR}/flops/{attn}.py")


def test_gqa_prefill_and_decode():
    f = _flops("gqa")
    # per token and layer: projections 2*64*16*(2*4 + 2*2) = 24576,
    # MLP 2*3*64*128 = 49152; a (query, key) pair costs 2*2*4*16 = 256
    # prefill of 8: 2 layers * (8*73728 + 256*36) + unembed 2*64*256
    assert f.prefill(tiny.GQA, 8) == 2 * (8 * 73728 + 256 * 36) + 32768 == 1230848
    # decode of two slots holding 5 and 9 entries
    assert f.decode(tiny.GQA, [5, 9]) == 2 * (2 * 73728 + 32768) + 2 * 256 * 14 == 367616


def test_mla_prefill_and_decode():
    f = _flops("mla")
    # projections shared by both forms: 2*(64*32 + 32*4*24 + 64*16 + 64*8 + 4*16*64)
    shared = 2 * (2048 + 3072 + 1024 + 512 + 4096)
    assert shared == 21504
    # prefill (expanded): + W_uk, W_uv 2*16*4*(16+16) + MLP 49152 per token;
    # a pair costs 2*4*(16+8) for QK and 2*4*16 for PV
    per_tok = shared + 4096 + 49152
    assert f.prefill(tiny.MLA, 8) == 2 * (8 * per_tok + 36 * (192 + 128)) + 2 * 64 * 300 == 1257472
    # decode (absorbed): q_nope W_uk^T and W_uv on the latent, 2*4*16*(16+16);
    # per cached position 2*4*(16+8) scores + 2*4*16 context
    dec_tok = shared + 4096 + 49152
    assert f.decode(tiny.MLA, [5, 9]) == 2 * (2 * dec_tok + 38400) + 2 * 320 * 14 == 384768
