"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights itself, from ``--seed``, in the layout the
program's initialiser defines for the configuration and in its parameter
dtype (bf16): the served path quantises them to Q8_0 as it would a
checkpoint, and the float32 reference reads the same values back. A family
module (``families/<name>.py``) gives the tree, leaf by leaf, through
``draw``; the program's own initialiser is never called for values. Its
abstract shapes are only compared against (``check_layout``), so a layout
change in the program fails loudly here instead of feeding the reference a
tree it misreads.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

#: the standard deviation of each kind of leaf; ``w`` (an (out, in)
#: matrix) is drawn at 1/sqrt(fan-in), and ``scale`` (a norm gain) about 1
SCALES = {"bias": 0.02, "scale": 0.1, "table": 0.02}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed, also ones past 32 bits."""
    words = np.random.SeedSequence(seed % 2**64).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def make(cfg, seed: int, tree: Callable, scales: Optional[dict] = None,
         fixed: Optional[Dict[str, np.ndarray]] = None) -> dict:
    """The weights ``tree(cfg, draw)`` lays out, for ``seed``, on the
    default device, in ``cfg.param_dtype``, from one jitted call.
    ``draw(shape, kind)`` gives one leaf: a normal draw at
    ``SCALES[kind]`` (or ``scales[kind]``), or for a kind in ``fixed`` that
    table, cast. Each draw folds the next counter value into the seed's
    key, so the order of the draws fixes every value."""
    dtype = jnp.dtype(cfg.param_dtype)
    std = dict(SCALES, **(scales or {}))
    tables = {k: jnp.asarray(v, jnp.float32) for k, v in (fixed or {}).items()}

    def build(key):
        counter = iter(range(1 << 20))

        def draw(shape, kind):
            if kind in tables:
                return tables[kind].astype(dtype)
            k = jax.random.fold_in(key, next(counter))
            z = jax.random.normal(k, shape, jnp.float32)
            scale = shape[-1] ** -0.5 if kind == "w" else std[kind]
            return (1.0 + scale * z if kind == "scale"
                    else scale * z).astype(dtype)
        return tree(cfg, draw)

    return jax.jit(build)(seed_key(seed))


def check_layout(cfg, params, max_positions: int = 0) -> None:
    """Raise if ``params`` is not laid out as the program's initialiser
    lays out this configuration (shapes and dtypes, leaf by leaf)."""
    from repro.models import model as model_lib
    want = jax.eval_shape(
        lambda k: model_lib.init_params(k, cfg, max_positions=max_positions),
        jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise ValueError("benchmark weight layout differs from the "
                         f"program's: {got} != {want}")
