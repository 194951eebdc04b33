"""Kernel mode from the platform, never from a flag.

On a TPU the Pallas kernels compile through Mosaic and the apps run their
Pallas stages; on the CPU the kernels run under the Pallas interpreter
(tests) and the apps run their jnp stages. Any other backend is an error:
there is no third mode to fall back to.

Both functions are read at trace time. Tests that need the other branch
monkeypatch them (and clear JAX's trace caches); the program has no option
for it.
"""

from __future__ import annotations

import jax


def interpret() -> bool:
    """True on the CPU (Pallas interpreter), False on a TPU (compiled)."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas kernel mode for backend {backend!r}: kernels compile "
        "on 'tpu' and run under the interpreter on 'cpu'")


def use_kernels() -> bool:
    """Whether the apps run their Pallas stages (TPU) or jnp stages (CPU)."""
    return not interpret()
