"""Seeded weights, made on the device in one jitted call.

The benchmark owns the weights: their names, shapes and distribution come
from the configuration file's sizes alone, the values from ``--seed``. The
same flat ``{name: array}`` dict feeds the plain reference directly and the
program through :func:`as_variables`, which only re-shapes it into the
nested tree the program's model expects (and refuses a tree whose names or
shapes differ — the configuration file would then not be what is run).

Distribution (the benchmark's choice; the program's own initialiser leaves
LayerNorm at identity and every bias at zero, which would let a dropped
bias or scale pass): matrices N(0, 1/fan_in), biases N(0, 0.05²),
LayerNorm scale 1 + N(0, 0.1²), position table and class token N(0, 0.1²),
classifier N(0, head_std²/fan_in) so that logits spread by about
``head_std`` and the top classes are distinct.
"""

from __future__ import annotations

import functools

import numpy as np


def param_spec(family: str, cfg: dict) -> list:
    """[(name, shape, kind, fan_in)] in a fixed order."""
    d, m = cfg["hidden_size"], cfg["intermediate_size"]
    ps, g = cfg["patch_size"], cfg["image_size"] // cfg["patch_size"]
    out = []

    def dense(name, fan_in, fan_out, kind="matrix", shape=None):
        out.append((f"{name}/kernel", shape or (fan_in, fan_out), kind,
                    fan_in))
        out.append((f"{name}/bias", (fan_out,), "bias", 0))

    def norm(name):
        out.append((f"{name}/scale", (d,), "ln_scale", 0))
        out.append((f"{name}/bias", (d,), "bias", 0))

    if family == "vit":
        dense("patch_embed", ps * ps * 3, d, shape=(ps, ps, 3, d))
        out.append(("cls_token", (1, 1, d), "table", 0))
        out.append(("pos_embed", (1, g * g + 1, d), "table", 0))
        head = "classifier"
    elif family == "videomae":
        ts = cfg["tubelet_size"]
        dense("tubelet/proj", ts * ps * ps * 3, d, shape=(ts, ps, ps, 3, d))
        out.append(("pos_embed",
                    (1, (cfg["num_frames"] // ts) * g * g, d), "table", 0))
        head = "head"
    else:
        raise ValueError(f"unknown model family {family!r}")
    for i in range(cfg["num_hidden_layers"]):
        b = f"encoder/block{i}"
        norm(f"{b}/ln1")
        dense(f"{b}/attn/qkv", d, 3 * d)
        dense(f"{b}/attn/out", d, d)
        norm(f"{b}/ln2")
        dense(f"{b}/mlp/fc1", d, m)
        dense(f"{b}/mlp/fc2", m, d)
    norm("encoder/ln_final")
    dense(head, d, cfg["num_labels"], kind="head")
    return out


def seed_key(seed: int, salt: int = 0):
    """A threefry key from any whole number (seeds run past 2**31)."""
    import jax
    import jax.numpy as jnp

    seed = int(seed)
    data = jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)
    return jax.random.fold_in(jax.random.wrap_key_data(data), salt)


@functools.lru_cache(maxsize=None)
def _generator(spec: tuple, head_std: float):
    import jax
    import jax.numpy as jnp

    sizes = [int(np.prod(shape)) for _, shape, _, _ in spec]

    def gen(key):
        # one draw for the whole model, cut into the tensors: a draw per
        # tensor makes ~150 generator kernels, and that executable takes
        # ~3 s to load from the compile cache in every run
        z_all = jax.random.normal(key, (sum(sizes),), jnp.float32)
        flat, at = {}, 0
        for (name, shape, kind, fan_in), n in zip(spec, sizes):
            z = z_all[at:at + n].reshape(shape)
            at += n
            if kind == "matrix":
                flat[name] = z * np.float32(fan_in ** -0.5)
            elif kind == "head":
                flat[name] = z * np.float32(head_std * fan_in ** -0.5)
            elif kind == "bias":
                flat[name] = z * np.float32(0.05)
            elif kind == "ln_scale":
                flat[name] = 1.0 + z * np.float32(0.1)
            elif kind == "table":
                flat[name] = z * np.float32(0.1)
            else:
                raise ValueError(kind)
        return flat

    return jax.jit(gen)


def generate(seed: int, family: str, cfg: dict, salt: int = 0) -> dict:
    """Flat float32 weights on the default device, one jitted call."""
    spec = tuple(param_spec(family, cfg))
    return _generator(spec, float(cfg.get("head_std", 3.0)))(
        seed_key(seed, salt))


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
