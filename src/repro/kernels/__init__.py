"""Pallas TPU kernels for the compute hot-spots, each with a pure-jnp
oracle (ref.py) and a jit'd wrapper (ops.py). On CPU hosts the kernels run
in interpret mode (the kernel body executes in Python) — numerically
validated against the oracles in tests/test_kernels.py."""
from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode(interpret: Optional[bool]) -> bool:
    """A kernel's ``interpret`` flag: as given, or by default the
    interpreter exactly when JAX's backend is not a TPU."""
    return (not on_tpu()) if interpret is None else interpret
