"""Seeded weights, made on the device in one jitted call.

The benchmark owns the weights: their names, shapes and distribution come
from the configuration file's sizes alone, the values from ``--seed``. The
same flat ``{name: array}`` dict feeds the plain reference directly and the
program through :func:`as_variables`, which only re-shapes it into the
nested tree the program's model expects (and refuses a tree whose names or
shapes differ — the configuration file would then not be what is run).

Which tensors a model has, and how each is spread, is its family's answer
(``families/<name>.py``: ``param_spec`` lists them in a fixed order,
``spread`` gives each kind its mean and standard deviation). Every tensor
is ``mean + std * z`` of one normal draw.
"""

from __future__ import annotations

import functools

import numpy as np

from . import loader


def seed_key(seed: int, salt: int = 0):
    """A threefry key from any whole number (seeds run past 2**31)."""
    import jax
    import jax.numpy as jnp

    seed = int(seed)
    data = jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)
    return jax.random.fold_in(jax.random.wrap_key_data(data), salt)


@functools.lru_cache(maxsize=None)
def _generator(tensors: tuple):
    """``tensors``: ((name, shape, mean, std), ...) in the draw's order."""
    import jax
    import jax.numpy as jnp

    sizes = [int(np.prod(shape)) for _, shape, _, _ in tensors]

    def gen(key):
        # one draw for the whole model, cut into the tensors: a draw per
        # tensor makes ~150 generator kernels, and that executable takes
        # ~3 s to load from the compile cache in every run
        z_all = jax.random.normal(key, (sum(sizes),), jnp.float32)
        flat, at = {}, 0
        for (name, shape, mean, std), n in zip(tensors, sizes):
            z = z_all[at:at + n].reshape(shape) * np.float32(std)
            at += n
            # no add where the mean is 0: 0.0 + z would turn a -0.0 into 0.0
            flat[name] = mean + z if mean else z
        return flat

    return jax.jit(gen)


def generate(seed: int, family: str, cfg: dict, salt: int = 0) -> dict:
    """Flat float32 weights on the default device, one jitted call."""
    fam = loader.family(family)
    tensors = tuple((name, tuple(shape)) + tuple(fam.spread(kind, fan_in, cfg))
                    for name, shape, kind, fan_in in fam.param_spec(cfg))
    return _generator(tensors)(seed_key(seed, salt))


def as_variables(flat: dict, template) -> dict:
    """Nest ``flat`` into the program's ``{"params": ...}`` tree.

    ``template`` is ``jax.eval_shape`` of the program's ``model.init``: only
    its names, shapes and sharding boxes are read."""
    import flax.linen as nn
    import jax

    is_box = lambda x: isinstance(x, nn.meta.AxisMetadata)  # noqa: E731
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        template, is_leaf=is_box)
    seen, vals = set(), []
    for path, leaf in leaves:
        keys = [getattr(k, "key", str(k)) for k in path]
        if keys[0] != "params":
            raise ValueError(f"unexpected collection {keys[0]!r}")
        name = "/".join(keys[1:])
        shape = tuple((leaf.unbox() if is_box(leaf) else leaf).shape)
        if name not in flat:
            raise ValueError(f"the program has a weight the configuration "
                             f"file does not describe: {name} {shape}")
        if tuple(flat[name].shape) != shape:
            raise ValueError(f"{name}: configuration gives "
                             f"{tuple(flat[name].shape)}, program {shape}")
        seen.add(name)
        vals.append(leaf.replace_boxed(flat[name]) if is_box(leaf)
                    else flat[name])
    extra = set(flat) - seen
    if extra:
        raise ValueError(f"weights the program does not have: "
                         f"{sorted(extra)[:5]}")
    return jax.tree_util.tree_unflatten(treedef, vals)
