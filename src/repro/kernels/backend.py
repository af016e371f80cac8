"""Which backend runs a Pallas kernel.

``"pallas"`` (the default of every measuring entry point) compiles the
kernel with Mosaic for the TPU and refuses to run when JAX finds no TPU: a
measurement that quietly fell back to the Pallas interpreter would time the
interpreter on the host CPU and label it with the kernel's name.
``"interpret"`` runs the kernel body in the Pallas interpreter on any
backend — for tests and docs, and only when asked for by name.
"""
from __future__ import annotations

import jax

BACKENDS = ("pallas", "interpret")


class NoChipError(RuntimeError):
    """The chip backend was requested and JAX finds no TPU."""


def use_interpreter(backend: str) -> bool:
    """The ``interpret=`` flag of ``pallas_call`` for ``backend``; raises
    ``NoChipError`` for the chip backend off the TPU."""
    if backend == "interpret":
        return True
    if backend not in BACKENDS:
        raise ValueError(f"unknown pallas backend {backend!r}; one of "
                         f"{BACKENDS}")
    platform = jax.default_backend()
    if platform != "tpu":
        raise NoChipError(
            f"pallas backend {backend!r} compiles kernels for a TPU, but JAX "
            f"finds none (platform {platform!r}); pass backend='interpret' "
            "to run the kernels in the Pallas interpreter instead")
    return False
