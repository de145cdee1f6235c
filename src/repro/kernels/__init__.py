"""Pallas TPU kernels for the tiered-KV read path, with pure-jnp oracles.

Every kernel is written for the TPU and compiles there; on the CPU backend
the same kernels run in Pallas interpret mode (the test suite's mode).
``interpret_mode`` is the one place that choice is made.
"""

import jax


def interpret_mode() -> bool:
    """True on the CPU backend (kernels run in the Pallas interpreter),
    False on a TPU (kernels compile).  Any other backend is an error: a
    TPU kernel has no lowering there, and silently interpreting it would
    hide that the program is not on the chip."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas TPU kernels cannot run on the {backend!r} "
                       f"backend")
