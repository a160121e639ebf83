"""Seeded random weights in the layout the program serves, made on the
device by one jitted call.

The layout is the dense family's parameter tree (``repro.models.
transformer.schema``): ``embed`` and ``unembed`` [vocab, d_model],
``final_norm`` [d_model], and one ``stacks`` entry whose leaves carry the
layer axis first. A norm leaf holds the gain's offset from 1, so zeros are
the published initial gain of ones. Scales follow the usual random-weight
convention (a projection's entries have standard deviation
1/sqrt(fan_in); the embedding's 1), which puts a logit's standard
deviation near 0.3 at phi3's widths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from loadgen import seed_sequence


def key_for(seed: int) -> jax.Array:
    """A PRNG key that differs for every whole-number seed (a plain
    ``jax.random.key`` folds seeds past 32 bits onto each other)."""
    data = seed_sequence(seed, 5).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data), impl="threefry2x32")


def shapes(m: dict) -> dict:
    """Leaf shapes of the dense tree for model sizes ``m``."""
    d, L, f, v = m["d_model"], m["n_layers"], m["d_ff"], m["vocab"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    layer = {"ln1": (L, d), "wq": (L, d, q), "wk": (L, d, kv),
             "wv": (L, d, kv), "wo": (L, q, d), "ln2": (L, d),
             "wg": (L, d, f), "wu": (L, d, f), "wd": (L, f, d)}
    return {"embed": (v, d), "final_norm": (d,), "stacks": [layer],
            "unembed": (v, d)}


def _std(name: str, shape: tuple) -> float:
    if name == "embed":
        return 1.0
    return shape[-2] ** -0.5


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, frozen_shapes, dtype):
    flat = dict(frozen_shapes)
    keys = jax.random.split(key, len(flat))
    out = {}
    for k, (path, shape) in zip(keys, sorted(flat.items())):
        name = path.split("/")[-1]
        if name.startswith("ln") or name == "final_norm":
            out[path] = jnp.zeros(shape, dtype)
        else:
            out[path] = (jax.random.normal(k, shape, jnp.float32)
                         * _std(name, shape)).astype(dtype)
    return out


def make(m: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole tree for sizes ``m`` from ``seed``, in one compiled call."""
    tree = shapes(m)
    flat = {k: v for k, v in tree.items() if k != "stacks"}
    flat.update({f"stacks/{k}": v for k, v in tree["stacks"][0].items()})
    out = _make(key_for(seed), tuple(sorted(flat.items())), dtype)
    params = {k: v for k, v in out.items() if "/" not in k}
    params["stacks"] = [{k.split("/")[1]: v for k, v in out.items()
                         if k.startswith("stacks/")}]
    return params
