"""Device kernels for the perf-critical compute layers.

* ``flash_attention`` — the serve/train attention hot path (Pallas).
* ``checksum``        — end-to-end transfer integrity, overlappable with
  the weight transfer (paper 4.6; Pallas).
* ``quant``           — int8 compression for cross-DC seeding and gradient
  transfer (beyond-paper optimization; Pallas), and the fused int8
  dequant + gather of resharded units (``quant.fused``; jitted XLA).
* ``repack``          — the byte gather of resharded units (jitted XLA).

The Pallas kernels ship ``kernel.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jitted wrapper) and ``ref.py`` (pure-jnp oracle); tests sweep
shapes and dtypes against the oracle in interpret mode on the CPU, and
``tests/test_tpu_compile.py`` compiles the transfer path's kernels for a
described TPU v5e. The gathers are XLA programs because Mosaic lowers
only gathers within one 2D block; they run compiled on every backend.
"""
