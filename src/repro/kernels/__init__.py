"""Pallas TPU kernels for the perf-critical paths.

  paged_attention/  multi-size paged flash-decoding with per-page heat stats
                    (the paper's translated-read hot path)
  flash_attention/  causal/windowed GQA prefill-training attention
  block_copy/       page migration (compaction / khugepaged collapse)

Each directory: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper), ref.py (pure-jnp oracle used by the allclose test sweeps).
All kernels target TPU (VMEM tiling, MXU-aligned blocks).  The tests run
them on the CPU with interpret=True and compile them for a described TPU
v5e at Phi-3-mini widths (tests/test_chip_compile.py); none of them is on
the served path yet.
"""
