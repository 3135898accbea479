"""Pallas TPU kernels for the IVFPQ hot path (+ jnp oracles in ref.py).

  adc_scan.py  -- ADC distance scan (gather + one-hot-GEMM paths)
  adc_topk.py  -- fused scan + running top-k with §4.4 early pruning
                  (shared-codes and per-pair-window variants)
  lut_build.py -- fused [LUT | combo-sums | 0] tables
  ops.py       -- public jit'd wrappers (padding, dtypes, dispatch)
  ref.py       -- pure-jnp oracles, one per kernel
"""

from repro.kernels import ops, ref
