"""Pallas TPU kernels for the performance-critical compute hot-spots.

Each kernel directory contains:
- ``<name>.py``: the pl.pallas_call kernel with explicit BlockSpec VMEM
  tiling (TPU is the *target*; correctness is validated in interpret mode),
- ``ops.py``: the jit'd public wrapper (dispatches interpret/compiled),
- ``ref.py``: the pure-jnp oracle the tests assert against.

Kernels:
- ``dram_timing``: the DRAM bank state-machine engine, re-designed for TPU
  lane-major: one trace a lane, requests streamed HBM->VMEM in blocks, the
  bank state a [banks, 128] tile carried across sequential grid steps.  It
  is what ``repro.core.engine._scan_engine_batch`` lowers to on a TPU.
- ``spmv``: ELL-blocked sparse matrix-vector multiply (the SpMV graph
  workload, and the compute core of PR).
- ``edge_update``: edge-centric gather-apply-scatter step (BFS/WCC/SSSP
  min-propagation) over edge blocks.
- ``attention``: blocked causal flash-attention forward (LM serving
  hot-spot; the dry-run model code keeps XLA einsum attention so
  cost_analysis stays interpretable — see DESIGN.md).
"""
