"""Shared constants for the Pallas TPU kernels.

The package runs with jax_enable_x64=True (paddle exposes float64/int64
dtypes), which makes bare Python literals trace as i64/f64 — types Mosaic
cannot legalize inside kernels or index maps. Kernels therefore use these
pre-typed constants (and wrap every float closure scalar in jnp.float32).
"""
import numpy as np

# i32 index constants (x64 mode would make a literal 0 trace as i64,
# which Mosaic refuses in an index map or a DMA slice)
I0 = np.int32(0)
I1 = np.int32(1)
I2 = np.int32(2)

# additive mask value; finite so exp() underflows cleanly instead of NaN
NEG_INF = -1e30
