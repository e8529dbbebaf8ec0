"""Kernel microbenchmarks: Pallas (interpret) correctness + analytic
VMEM/roofline characteristics per BlockSpec configuration.

On this CPU container wall-clock numbers reflect the interpreter, not the
MXU; the meaningful outputs are (a) max |err| vs the oracle per shape and
(b) the analytic VMEM working set + arithmetic intensity per block config,
which determine TPU performance."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import markdown_table, smoke, write_csv
from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import fused_rmsnorm


def flash_rows():
    rows = []
    key = jax.random.PRNGKey(0)
    cases = [
        ((1, 256, 4, 2, 64), (64, 128)),
        ((1, 256, 4, 2, 64), (128, 256)),
        ((2, 128, 8, 8, 128), (64, 64)),
    ]
    for (b, s, h, kv, d), (bq, bk) in (cases[:1] if smoke() else cases):
        q = jax.random.normal(key, (b, s, h, d), jnp.float32)
        k = jax.random.normal(key, (b, s, kv, d), jnp.float32)
        v = jax.random.normal(key, (b, s, kv, d), jnp.float32)
        t0 = time.perf_counter()
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True)
        dt = time.perf_counter() - t0
        err = float(jnp.max(jnp.abs(out - ref.attention_reference(q, k, v, causal=True))))
        vmem = (bq * d + 2 * bk * d) * 2 + bq * bk * 4 + bq * d * 4  # bytes
        flops = 4.0 * s * s * h * d / 2  # causal half
        rows.append([f"{b}x{s}x{h}x{d}", f"{bq}/{bk}", round(vmem / 1024, 1),
                     f"{err:.2e}", round(dt * 1e3, 1), f"{flops/1e6:.1f}M"])
    return rows


def decode_rows():
    rows = []
    key = jax.random.PRNGKey(1)
    dec_cases = [((4, 1024, 8, 2, 64), 256), ((4, 1024, 8, 2, 64), 512)]
    for (b, s, h, kv, d), bs in (dec_cases[:1] if smoke() else dec_cases):
        q = jax.random.normal(key, (b, h, d), jnp.float32)
        kc = jax.random.normal(key, (b, kv, s, d), jnp.float32)
        vc = jax.random.normal(key, (b, kv, s, d), jnp.float32)
        lengths = jnp.full((b,), s, jnp.int32)
        t0 = time.perf_counter()
        out = decode_attention(q, kc, vc, lengths, block_s=bs, interpret=True)
        dt = time.perf_counter() - t0
        err = float(jnp.max(jnp.abs(out - ref.decode_attention_reference(q, kc, vc, lengths))))
        vmem = 2 * bs * d * 2 + (h // kv) * bs * 4
        ai = (2.0 * h * d) / (2 * d * 2)  # flops per cache byte ~ n_rep/1
        rows.append([f"{b}x{s}x{h}x{d}", bs, round(vmem / 1024, 1),
                     f"{err:.2e}", round(dt * 1e3, 1), round(ai, 2)])
    return rows


def rmsnorm_rows():
    rows = []
    key = jax.random.PRNGKey(2)
    rn_cases = [((512, 1024), 128), ((512, 1024), 256)]
    for shape, bn in (rn_cases[:1] if smoke() else rn_cases):
        x = jax.random.normal(key, shape, jnp.float32)
        w = jax.random.normal(key, (shape[-1],), jnp.float32)
        t0 = time.perf_counter()
        out = fused_rmsnorm(x, w, block_n=bn, interpret=True)
        dt = time.perf_counter() - t0
        err = float(jnp.max(jnp.abs(out - ref.rmsnorm(x, w))))
        rows.append([f"{shape}", bn, f"{err:.2e}", round(dt * 1e3, 1)])
    return rows


def main():
    fr = flash_rows()
    write_csv("kernel_flash.csv",
              ["shape", "block_q/k", "vmem_kib", "max_err", "interp_ms", "flops"], fr)
    print(markdown_table(["flash shape", "blocks", "VMEM KiB", "max|err|", "ms", "flops"], fr))
    dr = decode_rows()
    write_csv("kernel_decode.csv",
              ["shape", "block_s", "vmem_kib", "max_err", "interp_ms", "arith_int"], dr)
    print(markdown_table(["decode shape", "block_s", "VMEM KiB", "max|err|", "ms", "AI"], dr))
    rr = rmsnorm_rows()
    write_csv("kernel_rmsnorm.csv", ["shape", "block_n", "max_err", "interp_ms"], rr)
    print(markdown_table(["rmsnorm shape", "block_n", "max|err|", "ms"], rr))
    assert all(float(r[3]) < 3e-5 for r in fr)
    assert all(float(r[3]) < 3e-5 for r in dr)
    assert all(float(r[2]) < 1e-5 for r in rr)


if __name__ == "__main__":
    main()
