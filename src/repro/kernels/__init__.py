"""Pallas TPU kernels for the perf-critical hot spots, with pure-jnp
oracles (ref.py) and jit'd custom-VJP wrappers (ops.py). Validated in
interpret mode on CPU; ``interpret=False`` on real TPU.

Kernel coverage (fused forward / fused backward):

  flash_attention — fwd + bwd. HBM->VMEM blocked online-softmax attention
                    (the body's dominant matmul pair at 4k-32k sequence
                    lengths; attention's "auto" runs it on one TPU device
                    for multi-token calls over at most 2048 keys). The
                    forward emits a per-row LSE residual; the backward's
                    dq and dk/dv kernels recompute probabilities
                    blockwise from (q, k, v, lse), so no [Sq, Sk]
                    intermediate exists in either direction.
                    Non-block-multiple sequence lengths are padded onto
                    the block grid with masked keys / zero-cotangent
                    query rows.
  softmax_xent    — fwd + bwd. Fused LM-head cross-entropy: online
                    softmax over vocab tiles with an in-tile one-hot
                    label gather; backward reconstructs
                    g * (softmax - onehot) tile-by-tile from the LSE
                    residual ([T, V] logits never materialized).
  quant8          — fwd (bwd is straight-through by construction). Fused
                    int-k quant-dequant for the MPSL smashed-data uplink
                    / cut-layer-gradient downlink: one read + one write
                    per element. Stochastic rounding uses the TPU
                    hardware PRNG when compiled and a threaded
                    jax.random key in interpret mode (the pltpu PRNG
                    primitives have no CPU lowering).
  selective_scan  — fwd + bwd. Mamba recurrence with VMEM-resident state,
                    chunked along the sequential grid axis. The forward
                    emits per-chunk-boundary state checkpoints
                    [B, nchunks, di, ds]; the backward sweeps chunks in
                    reverse, recomputes the in-chunk states from each
                    checkpoint into VMEM scratch and runs the adjoint
                    recurrence, so no [B, S, di, ds] state history exists
                    in either direction (run.impls["ssm_bwd"] falls back
                    to the recompute-through-reference VJP).

Interpret-mode caveats: grids execute sequentially in Python (orders of
magnitude slower than compiled — benchmark numbers from CPU measure
dispatch overhead, not kernel quality; ``benchmarks/kernel_bench.py``
therefore also reports analytic bytes-moved per lowering), and the
TPU-only PRNG path above is swapped for precomputed uniforms.
"""
