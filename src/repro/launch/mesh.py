"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never module-level state) so
importing this module touches no jax device machinery — critical because
the dry-run must set XLA_FLAGS before any jax initialization.
"""

from __future__ import annotations

import math

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    if len(jax.devices()) == n:
        # shardings here are placed by hand (NamedSharding + shard_map),
        # so the axes are Auto, not make_mesh's Explicit default
        return jax.make_mesh(shape, axes, axis_types=(
            jax.sharding.AxisType.Auto,) * len(axes))
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"need {n} devices for {shape} mesh, have {len(jax.devices())} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512")
    devs = np.asarray(jax.devices()[:n]).reshape(shape)
    return jax.sharding.Mesh(devs, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests/examples on CPU)."""
    import jax

    n = len(jax.devices())
    data = n // model
    devs = np.asarray(jax.devices()[: data * model]).reshape(data, model)
    return jax.sharding.Mesh(devs, ("data", "model"))


def make_serve_mesh(model: int = 1):
    """1-axis ``("model",)`` mesh over the first ``model`` devices — the
    shape ``LLMEngine`` shards the paged KV pool over. Unlike
    ``make_host_mesh`` it takes a device *count*, so benchmarks can build
    1/2/4/8-device meshes out of one forced-host-device pool."""
    import jax

    devs = jax.devices()
    if len(devs) < model:
        raise RuntimeError(
            f"need {model} devices, have {len(devs)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={model}")
    return jax.sharding.Mesh(
        np.asarray(devs[:model]).reshape(model), ("model",))
