"""Weights from the seed: one jitted call, on the device, float32 (the type
the program keeps its parameters in under `--amp`; the bfloat16 casts happen
inside the program's own step and predict, as in training).

The list of arrays comes from the reference's walker
(`reference.model.param_spec`), not from the program; `to_program_tree` lays the
same arrays out as the program's checkpoint tree and `check_tree` proves, on
shapes alone, that the program asks for exactly these paths.

Draws: conv kernels normal with std 1/sqrt(fan_in) (the LeCun scale the
program's own init uses), conv biases N(0, 0.05), the head's bias at a
trained detector's operating point (see `_draw`),
BatchNorm scale U(0.8, 1.2), bias N(0, 0.1), running mean N(0, 0.1), running
variance U(0.8, 1.2): nothing sits at a value (0 or 1) that would hide a
skipped BatchNorm term. A serving cell then replaces the running statistics
by the network's own over a few of its frames (`with_running_statistics`).

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _draw(key, shape, kind):
    if kind == "kernel":
        fan_in = math.prod(shape[:-1])
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    if kind == "bias":
        return 0.05 * jax.random.normal(key, shape, jnp.float32)
    if kind == "head_bias":
        # (heatmap per class, offset x y, size w h), as a detector's head
        # sits after training: CenterNet's -2.19 heatmap prior, offsets
        # mid-cell, boxes some 8 cells (32 px) a side. With boxes a few
        # pixels wide at random, no two overlapped and NMS had nothing to do.
        centre = jnp.concatenate([jnp.full((shape[0] - 4,), -2.19),
                                  jnp.array([0.5, 0.5, 8.0, 8.0])])
        return centre + 0.05 * jax.random.normal(key, shape, jnp.float32)
    if kind in ("bn_scale", "bn_var"):
        return jax.random.uniform(key, shape, jnp.float32, 0.8, 1.2)
    if kind in ("bn_bias", "bn_mean"):
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    raise ValueError("unknown parameter kind %r" % (kind,))


def make_weights(spec: Dict[str, tuple], seed: int) -> Dict[str, jax.Array]:
    """{path: float32 array} for every entry of `spec`, in one program."""
    paths = sorted(spec)

    @jax.jit
    def draw(key):
        return {p: _draw(jax.random.fold_in(key, i), *spec[p])
                for i, p in enumerate(paths)}

    return draw(seed_key(seed))


def with_running_statistics(cfg: dict, weights: Dict[str, jax.Array],
                            frames_u8) -> Dict[str, jax.Array]:
    """`weights` with every BatchNorm's running mean and variance replaced by
    the network's own batch statistics over `frames_u8` (raw uint8 frames of
    the cell's pool), computed by the plain reference in one jitted call.
    Serving needs it: see `reference.model.batch_statistics`."""
    from .reference import model as ref
    stats = jax.jit(lambda w, x: ref.batch_statistics(
        cfg, w, ref.normalize_pixels(x)))(weights, jnp.asarray(frames_u8))
    return {**weights, **stats}


def to_program_tree(weights: Dict[str, jax.Array], spec) -> dict:
    """The program's checkpoint layout: {'params': nested, 'batch_stats':
    nested}, split by kind."""
    tree = {"params": {}, "batch_stats": {}}
    for path, array in weights.items():
        kind = spec[path][1]
        node = tree["batch_stats" if kind in ("bn_mean", "bn_var")
                    else "params"]
        *parents, leaf = path.split("/")
        for name in parents:
            node = node.setdefault(name, {})
        node[leaf] = array
    return tree


def flatten_tree(tree) -> Dict[str, jax.Array]:
    """{'A/B/leaf': array} of a nested dict (one collection of the program's
    tree, e.g. its params, its gradients or Adam's mu)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in flat}


def check_tree(program_shapes, spec) -> None:
    """Raise unless the program's variables (as `jax.eval_shape` of its init
    gives them) are exactly the reference's list: same paths, same shapes."""
    got = {}
    for coll in ("params", "batch_stats"):
        for path, leaf in flatten_tree(program_shapes.get(coll, {})).items():
            got[path] = tuple(leaf.shape)
    want = {p: s for p, (s, _) in spec.items()}
    if got != want:
        only_p = sorted(set(got) - set(want))[:5]
        only_r = sorted(set(want) - set(got))[:5]
        diff = [p for p in got if p in want and got[p] != want[p]][:5]
        raise ValueError("program and reference disagree on the parameters: "
                         "program only %r, reference only %r, shapes differ "
                         "%r" % (only_p, only_r, diff))
