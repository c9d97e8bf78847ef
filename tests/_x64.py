"""The one 64-bit-mode scope the tests use: ``with x64(): ...``.

f64 runs are the engine's bit-exact reference configurations; JAX keeps
32-bit mode outside the scope.
"""

import jax


def x64():
    """Context manager that enables 64-bit mode for its body."""
    return jax.enable_x64(True)
