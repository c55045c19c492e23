"""Seeded weights, made on the device in one jitted call.

The tree (names, shapes, dtypes) is the one ``init_params`` would build,
read with ``jax.eval_shape`` so nothing is allocated for it. Every leaf is
then drawn inside one jitted program from the run's seed, by its role:
embeddings and the LM head are N(0, 1/d_model), every other matrix is
N(0, 1/fan_in) with the fan-in on its second-to-last axis — the scales
``init_params`` uses. A leaf's role is read from its last path
component, whatever its leading layer or expert axes: a norm scale
(``ln*``, ``final_norm``, ``scale``) is drawn as ones, a bias (``BIASES``)
N(0, 0.02²): the ``initializer_range`` of Hugging Face configurations,
not zero, so a program that drops a bias moves the logits. Any other
leaf that is a vector (under ``scan/`` once its layer axis is set aside)
is refused. Tables with a leading layer or expert axis are drawn
one [fan_in, fan_out] slice at a time (``lax.map``), so no float32 copy of
a whole expert table is ever live: set-up stays under serving's own
high-water mark of HBM.

The reference regenerates the same tree from the same seed after the
program's state is freed; it never reads the program's copy.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed32(seed: int, salt: str = "") -> int:
    """A 32-bit seed from any whole number (seeds may exceed 32
    bits, and ``jax.random.PRNGKey`` keeps only the low 32)."""
    words = [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF,
             zlib.crc32(salt.encode())]
    return int(np.random.SeedSequence(words).generate_state(1)[0])


def _leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


BIAS_STD = 0.02
BIASES = ("bq", "bk", "bv", "bo", "bias")
NORMS = ("final_norm", "scale")


def _draw(key, shape, dtype, name: str):
    last = name.rsplit("/", 1)[-1]
    if last.startswith("ln") or last in NORMS:
        return jnp.ones(shape, dtype)
    if last in BIASES:
        return (jax.random.normal(key, shape, jnp.float32) * BIAS_STD
                ).astype(dtype)
    if len(shape) == 1 + name.startswith("scan/"):
        raise ValueError(f"weight leaf {name} {tuple(shape)} is a vector "
                         f"but neither a norm scale nor a bias")
    if last in ("embed", "lm_head"):
        scale = shape[-1] ** -0.5
    else:
        scale = shape[-2] ** -0.5
    if len(shape) <= 2:
        return (jax.random.normal(key, shape, jnp.float32) * scale
                ).astype(dtype)
    lead = math.prod(shape[:-2])
    keys = jax.random.split(key, lead)

    def one(k):
        return (jax.random.normal(k, shape[-2:], jnp.float32) * scale
                ).astype(dtype)

    return jax.lax.map(one, keys).reshape(shape)


def param_shapes(model_cfg):
    from repro.models import init_params
    return jax.eval_shape(lambda k: init_params(model_cfg, k),
                          jax.random.PRNGKey(0))


def make_params(model_cfg, seed: int):
    """The seeded weight tree on the default device, in served dtypes."""
    shapes = param_shapes(model_cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_leaf_name(p) for p, _ in flat]
    specs = [(s.shape, s.dtype) for _, s in flat]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(specs))
        leaves = [_draw(k, shape, dtype, name)
                  for k, (shape, dtype), name in zip(keys, specs, names)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    params = build(jax.random.PRNGKey(seed32(seed, "weights")))
    return jax.block_until_ready(params)


def nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
