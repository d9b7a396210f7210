"""Pallas TPU kernels for the two hot spots: histogram accumulation
(``histogram``) and whole-ensemble traversal (``ensemble_predict``)."""

import jax


def interpret_mode() -> bool:
    """Pallas kernels compile for the TPU and run in interpret mode on every
    other backend (the CPU test path); never interpreted on a TPU."""
    return jax.default_backend() != "tpu"
