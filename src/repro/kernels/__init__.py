"""Pallas TPU kernels for the performance-critical GEMM paths.

- dense_gemm:   the optimized dense baseline (blocked MXU matmul).
- griffin_spmm: the paper's sparse technique, TPU-adapted — offline
  block-compaction of weights with scalar-prefetch metadata (Sparse.B),
  optional on-the-fly A-block skipping (dual), and column balancing
  (shuffle).  See DESIGN.md Section 3 for the granularity adaptation.
- sparse_a:     the Sparse.A analogue — runtime compaction of the A-block
  iteration space with scalar-prefetch metadata against dense weights
  (DESIGN.md Section 3; jit static-shape fallback in Section 5).
- decode_attention: one decode token per row against the layer-stacked
  fixed KV arena, in place: writes the new K/V and reads only each live
  row's blocks (scalar-prefetched lengths), the serving engine's decode
  attention on one device (DESIGN.md Section 9).
- batch_eval:   jax.vmap twin of the batched cycle-model scheduler, the
  accelerator path behind ``schedule_batched(..., backend="jax")``.

``auto_matmul`` dispatches every ``core.spec.Mode`` to one of these kernels;
the framework layer reaches it per GEMM via ``models.common.griffin_linear``.
Kernels are validated against their ref.py oracles (decode_attention:
``models.attention.decode_attention``, the path it replaces) in interpret
mode on CPU and target TPU v5e block shapes (128-aligned) for real runs.
"""
from .batch_eval.ops import schedule_cycles
from .dense_gemm.ops import dense_matmul
from .griffin_spmm.ops import (GriffinWeights, auto_matmul, balance_columns,
                               griffin_matmul, preprocess_weights,
                               stack_weights)
from .sparse_a.ops import ActivationMeta, compact_activations, sparse_a_matmul

__all__ = ["dense_matmul", "GriffinWeights", "auto_matmul",
           "balance_columns", "griffin_matmul", "preprocess_weights",
           "stack_weights", "ActivationMeta", "compact_activations",
           "sparse_a_matmul", "schedule_cycles"]
