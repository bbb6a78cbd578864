"""Weights from the seed, made on the device in one jitted call.

The program and the reference both start from these: each leaf is drawn
from its own key, folded from the seed and the leaf's path, so a leaf's
values depend on nothing but the seed, its path, its shape and its rule.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A key from a seed of any size (seeds may exceed 32 bits)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _draw(key, path: str, shape, init: str, scale: float):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    if init == "sign":
        return jax.random.rademacher(k, shape, jnp.float32) * scale
    if init == "normal":
        return jax.random.normal(k, shape, jnp.float32) * scale
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    raise ValueError(init)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, spec_items: tuple):
    return {path: _draw(key, path, *rule) for path, rule in spec_items}


@functools.partial(jax.jit, static_argnums=(1,))
def _distances(key, spec_items: tuple, flat: dict):
    return {path: jnp.sqrt(jnp.sum(jnp.square(flat[path]
                                              - _draw(key, path, *rule))))
            for path, rule in spec_items}


def _items(spec: dict) -> tuple:
    return tuple((p, (tuple(s), i, float(c))) for p, (s, i, c) in
                 sorted(spec.items()))


def make(seed: int, spec: dict) -> dict:
    """{path: float32 array} for ``spec`` = {path: (shape, init, scale)}."""
    return _make(base_key(seed), _items(spec))


def distances(seed: int, spec: dict, flat: dict) -> dict:
    """{path: ||flat[path] - the seed's weights||}, the weights drawn inside
    the reduction so that no second copy of them is held."""
    out = _distances(base_key(seed), _items(spec), flat)
    return {k: float(v) for k, v in out.items()}


def as_tree(flat: dict, like) -> object:
    """Arrange ``flat`` {path: array} in the tree structure of ``like`` (the
    program's parameter shapes); paths and shapes have to agree exactly."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(like)
    paths = ["/".join(str(getattr(k, "key", k)) for k in kp)
             for kp, _ in leaves]
    if sorted(paths) != sorted(flat):
        raise ValueError(f"parameter paths differ: program {sorted(paths)} "
                         f"vs configuration {sorted(flat)}")
    for path, (_, leaf) in zip(paths, leaves):
        if tuple(leaf.shape) != tuple(flat[path].shape):
            raise ValueError(f"{path}: program shape {leaf.shape} vs "
                             f"configuration {flat[path].shape}")
    return jax.tree_util.tree_unflatten(treedef, [flat[p] for p in paths])


def tree_paths(tree) -> list[str]:
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in kp) for kp, _ in leaves]
