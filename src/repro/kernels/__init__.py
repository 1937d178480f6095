"""Pallas TPU kernels: flash attention, decode attention, RWKV6 and SSD scans.

Every kernel entry takes ``interpret=None``: the kernel then compiles
for the TPU when JAX runs on one and runs in the Pallas interpreter on
any other platform. :func:`interpret_default` is the one place that
decides; pass ``interpret=False`` explicitly only to compile for a TPU
that is described but not attached.
"""

from __future__ import annotations

from typing import Optional

import jax


def interpret_default(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else True unless JAX's backend is a TPU."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
