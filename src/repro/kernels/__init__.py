"""Pallas TPU kernels for EC-DNN's compute hot-spots.

  flash_attention  tiled online-softmax attention (causal/SWA/GQA)
  distill_loss     fused dual-CE of paper Eqn 9 (+ custom VJP)
  wkv6             RWKV6 chunked recurrence (data-dependent decay)
  ssm_scan         Mamba selective scan, chunk-sequential

Each kernel has a pure-jnp oracle in ref.py; ops.py is the dispatch layer
model code imports.  Every kernel defaults to interpret=False (compiled
for the TPU); CPU tests pass interpret=True explicitly, and
tests/test_tpu_compile.py compiles the main-path kernels for a described
v5e chip at real widths.
"""
