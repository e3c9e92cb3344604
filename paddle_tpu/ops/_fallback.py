"""What happens when a Pallas kernel cannot be built.

On the CPU backend (tests, interpret mode) kernel dispatch falls back to the
XLA reference path and each (kernel, reason) pair warns once. On any other
backend there is no fallback: a run on the chip that quietly took the
O(L^2)-HBM reference path would report that path's speed and memory under
the kernel's name, so the kernel's own error is raised.
"""
import warnings

import jax

_warned = set()

__all__ = ["kernel_fallback"]


def kernel_fallback(name, err):
    """Called from an `except` that caught `err` building kernel `name`:
    re-raise it unless the backend is the CPU, where it warns once."""
    if jax.default_backend() != "cpu":
        raise err
    key = (name, type(err).__name__)
    if key in _warned:
        return
    _warned.add(key)
    warnings.warn(
        f"Pallas kernel '{name}' unavailable ({type(err).__name__}: {err}); "
        "falling back to the XLA reference path (slower / more HBM)",
        RuntimeWarning, stacklevel=3)
