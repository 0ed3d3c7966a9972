"""Pallas TPU kernel pack.

TPU-native replacement for the reference's fused CUDA kernels
(paddle/phi/kernels/gpu/flash_attn_kernel.cu, fused_*_op.cu — see SURVEY §2.4).
Each module exposes `available()` (True when running on a TPU backend) and
falls back to an equivalent XLA composition elsewhere, so the same model code
runs in CPU tests and on hardware.
"""
import functools

import jax


@functools.lru_cache(maxsize=1)
def on_tpu() -> bool:
    """Whether the default backend is a TPU. A backend that fails to
    initialise RAISES (and is not cached): answering False there would
    send every kernel to its XLA reference on a machine that was meant
    to have a chip."""
    return jax.default_backend() == "tpu"
