"""Pallas kernels for the paper's compute hot spots: split histograms,
dense proximity blocks and leaf routing (each ``<name>.py`` + ``ops.py`` +
``ref.py``).

All three are 32-bit kernels.  One rule decides how they run: interpret
mode on the CPU backend, compiled everywhere else — and a kernel that the
accelerator's compiler refuses raises there, it never falls back to the
interpreter or to the jnp reference.
"""
from __future__ import annotations


def interpret_mode() -> bool:
    """True iff Pallas kernels run in interpret mode: on the CPU backend."""
    import jax
    return jax.default_backend() == "cpu"
