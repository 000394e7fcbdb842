"""Pallas kernels of the routing and serving hot paths.

Each kernel package holds ``kernel.py`` (the ``pallas_call``), ``ops.py``
(the jitted padding wrapper callers use) and ``ref.py`` (a jnp oracle).
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter on this platform.

    The one place the choice is made: on the CPU the kernels run in
    interpret mode (the tests' path), on a TPU they are compiled, and
    any other platform raises instead of silently interpreting.
    """
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {platform!r}")
