"""Pallas kernels for the paper's hot spots, their jnp oracles (ref.py)
and the jitted public wrappers (ops.py)."""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """A kernel's ``interpret`` flag: an explicit bool wins; ``None``
    follows the backend — compiled on a TPU, interpreted elsewhere."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
