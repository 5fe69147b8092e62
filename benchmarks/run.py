"""Benchmark entry point: one function per paper table/figure plus kernel
micro-benchmarks. Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --fast     # cost models only
"""
from __future__ import annotations

import argparse
import sys


def kernel_microbench():
    import jax
    import jax.numpy as jnp
    from benchmarks.common import emit, time_fn
    from repro.kernels import ops

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 256, 8, 64), jnp.float32)
    k = jax.random.normal(key, (2, 256, 4, 64), jnp.float32)
    v = jax.random.normal(key, (2, 256, 4, 64), jnp.float32)
    p = jnp.broadcast_to(jnp.arange(256)[None], (2, 256)).astype(jnp.int32)
    f = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, p, p))
    us = time_fn(f, q, k, v)
    emit("kernel/flash_attention_256", us, "interpret=True")

    x = jax.random.normal(key, (1, 128, 64)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(key, (1, 128, 64))) * 0.1
    b = jax.random.normal(key, (1, 128, 8))
    c = jax.random.normal(key, (1, 128, 8))
    al = jnp.log(jnp.abs(jax.random.normal(key, (64, 8))) + 0.5)
    g = jax.jit(lambda *a: ops.selective_scan(*a, None, 32))
    us = time_fn(g, x, dt, b, c, al)
    emit("kernel/selective_scan_128", us, "interpret=True")

    z = jax.random.normal(key, (1024, 512))
    h = jax.jit(ops.quant_dequant)
    us = time_fn(h, z)
    emit("kernel/quant8_1024x512", us, "interpret=True")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--fast", action="store_true",
                   help="cost models + kernels only (no training runs)")
    args = p.parse_args()

    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    from benchmarks import paper_tables as T
    T.table1_client_cost()
    T.fig3_comm_overhead()
    T.fig6_encoder_depth_cost()
    kernel_microbench()
    from benchmarks import kernel_bench
    # --fast keeps the interpret-mode sweep short; the full cap is the
    # default standalone invocation (python -m benchmarks.kernel_bench)
    kernel_bench.run(cap=512 if args.fast else 4096)
    if not args.fast:
        from benchmarks import pipeline_bench
        # end-to-end step pipeline: sync vs prefetch vs overlapped
        pipeline_bench.run(steps=20)
        T.table1_accuracy()
        T.table2_retrieval()
        T.table3_batch_size()
        T.table4_blocks()
        T.table5_fusion()
    print("benchmarks: done")


if __name__ == '__main__':
    main()
