"""Pallas kernels of the device path.

Every kernel wrapper takes ``interpret=None`` and resolves it here, so
the choice between Mosaic (the TPU kernel compiler) and the Pallas
interpreter depends on the platform alone: compiled wherever JAX runs
on an accelerator, interpreted only on a CPU backend (the test suite).
"""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The ``interpret`` flag for a ``pallas_call``.

    ``None`` follows the platform: ``True`` on a CPU backend, ``False``
    on a TPU.  An explicit ``False`` is how a CPU host compiles kernels
    for a described (not attached) TPU; an explicit ``True`` on a TPU is
    refused, since it would run the interpreter on the chip.
    """
    platform = jax.default_backend()
    if interpret is None:
        return platform == "cpu"
    if interpret and platform == "tpu":
        raise ValueError("interpret=True on a TPU backend would run the "
                         "Pallas interpreter on the chip")
    return interpret
