"""Random serving weights made on the device from the seed, in one jitted
call, in the dtype they are served in.

The tree has the layout the served model takes (``repro.models.model_spec``
gives its shapes, and the embedding's scale); the draw is the benchmark's
own, so the reference can be handed the same weights without taking
anything the program made.  A matrix is drawn with standard deviation
1/sqrt(its input width), per layer, so that attention is far from uniform
and every layer moves the logits: a wrong key or value then shows in them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def jax_seed(seed: int) -> int:
    """A 31-bit key seed for JAX from a seed of any size."""
    return int(np.random.default_rng(seed).integers(0, 2**31 - 1))


def make(cfg, seed: int):
    from repro.models import model_spec
    from repro.models.common import ParamSpec

    dtype = jnp.bfloat16

    leaves, treedef = jax.tree.flatten(
        model_spec(cfg), is_leaf=lambda x: isinstance(x, ParamSpec))
    specs = [(p.shape, p.init,
              p.scale if p.scale is not None else
              1.0 / math.sqrt(p.shape[-2] if len(p.shape) > 1 else 1))
             for p in leaves]

    def draw(key):
        out = []
        for k, (shape, init, std) in zip(jax.random.split(key, len(specs)),
                                         specs):
            if init == "ones":
                out.append(jnp.ones(shape, dtype))
            elif init == "zeros":
                out.append(jnp.zeros(shape, dtype))
            else:
                out.append(jax.random.normal(k, shape, dtype)
                           * jnp.asarray(std, dtype))
        return out

    key = jax.random.PRNGKey(jax_seed(seed))
    return jax.tree.unflatten(treedef, jax.jit(draw)(key))
