"""Seeded decoder weights, made on the device in one jitted call, in the
layout and dtype the program serves them in.

The program's own parameter tree gives only the shapes (`jax.eval_shape`
of its initializer); every value is drawn here: each leaf from its own key
folded from the seed, normal with std 1/sqrt(fan_in), where fan_in is the
leaf's input width (the model width for the embedding and for the q, k, v,
gate and up projections, the heads x head size for the attention output,
the MLP width for the down projection).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def fan_in(path: str, shape: tuple) -> int:
    if path.endswith("attn/wo"):
        return shape[-3] * shape[-2]
    if path.endswith("mlp/wo"):
        return shape[-2]
    if path.endswith("embed/tok"):
        return shape[-1]
    if path.endswith(("attn/wq", "attn/wk", "attn/wv")):
        return shape[-3]
    if path.endswith(("mlp/wi", "mlp/wg")):
        return shape[-2]
    raise KeyError(f"no fan-in rule for parameter {path} {shape}")


def _path(kp) -> str:
    parts = []
    for k in kp:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def make(shapes, seed: int):
    """A tree like `shapes` (ShapeDtypeStructs) filled from `seed`."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    meta = tuple((_path(kp), tuple(s.shape), jnp.dtype(s.dtype).name)
                 for kp, s in leaves)
    vals = _draw(jax.random.key(seed % 2 ** 32), meta)
    return jax.tree_util.tree_unflatten(treedef, list(vals))


@partial(jax.jit, static_argnames=("meta",))
def _draw(key, meta):
    out = []
    for i, (path, shape, dtype) in enumerate(meta):
        std = fan_in(path, shape) ** -0.5
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out.append((x * std).astype(dtype))
    return tuple(out)


def reference_view(params) -> dict:
    """The same arrays in the reference's naming: the embedding and the
    stacked layers of the model's one repeating block."""
    (block,) = params["blocks"]
    assert not params["tail"], "the reference expects one stacked block"
    return {"embed": params["embed"],
            "layers": {"attn": block["attn"], "mlp": block["mlp"]}}
