"""Pallas kernels, each behind an ``ops.py`` dispatcher.

Every op takes ``backend="pallas" | "interpret" | "xla"``. Left at
``None`` the backend comes from the platform (:func:`resolve_backend`):
the Pallas kernel on a TPU, the XLA reference elsewhere, since Pallas TPU
kernels do not lower for the CPU. An op that cannot serve a shape on the
backend it resolved to raises; it never falls back to another backend.
"""

from __future__ import annotations

from typing import Optional


def platform_backend() -> str:
    """The kernel backend of the default JAX platform."""
    import jax

    return "pallas" if jax.default_backend() == "tpu" else "xla"


def resolve_backend(backend: Optional[str]) -> str:
    """An explicit ``backend`` as given, ``None`` from the platform."""
    return platform_backend() if backend is None else backend
