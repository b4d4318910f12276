"""Numeric ops: TPU kernels (Pallas) and their dense JAX references.

Layout:
- ``norms``           — RMSNorm (f32 accumulation)
- ``rope``            — rotary position embeddings with offset support
- ``attention``       — dense reference attention (GQA, causal, cached) +
                        backend dispatch
- ``flash_attention`` — Pallas flash attention (prefill)
- ``ragged_attention`` — Pallas ragged attention over the block pool
                        (decode, spec verify and prefill in one kernel)
- ``ring_attention``  — sequence-parallel ring attention over a mesh axis
- ``quant``           — int8 quantized matmul kernels
"""
