"""Pallas TPU kernels for the perf-critical paths.

  paged_attention/  decode attention over a batch's live KV pages, one
                    contiguous DMA per buddy-aligned page, with per-block
                    heat (the paper's translated-read hot path)
  flash_attention/  causal/windowed GQA prefill-training attention
  block_copy/       page migration (compaction / khugepaged collapse)

Each directory: kernel.py (pl.pallas_call + BlockSpec), ops.py (wrapper),
ref.py (oracle for the test sweeps; paged_attention's attention oracle is
the gather path in models/decode.py).  All kernels target TPU (VMEM tiling,
MXU-aligned blocks).  The tests run them on the CPU in interpret mode and
compile them for a described TPU v5e at the benchmark's widths
(tests/test_chip_compile.py).  paged_attention is the engine's decode
attention on a TPU; the other two are not on the served path.
"""
