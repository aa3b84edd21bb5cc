"""Pallas TPU kernels for the compute hot spots, each with a pure-jnp
oracle in ref.py and a jit wrapper in ops.py.

  mixup_kernel    — two-way Mixup / inverse-Mixup batch transform (eq. 6/7)
  distill_loss    — fused softmax CE + KD regularizer (eq. 3/5)
  flash_attention — block-tiled online-softmax attention (prefill path)
  ssd_scan        — Mamba2 SSD chunked scan (state-space duality)

Each kernel picks its mode from ``runtime.default_interpret``: on the CPU
(the test suite sets ``JAX_PLATFORMS=cpu``) it runs in the Pallas
interpreter; on a TPU the same pallas_call compiles to a Mosaic kernel
(``tpu_custom_call`` in the HLO).  ``python chip_smoke.py`` at the repo
root checks the compiled path on one chip.
"""
