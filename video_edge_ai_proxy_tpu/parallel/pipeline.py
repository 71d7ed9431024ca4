"""Pipeline parallelism over the ``pp`` mesh axis (GPipe schedule).

The sixth and last parallelism axis: layer *stages* live on different
chips, microbatches stream through the ring, and activations hop stage to
stage over ICI via `lax.ppermute`. Expressed entirely inside one
`shard_map` — stage s's params are simply shard s of a stacked param tree,
so there is no per-stage program, no RPC layer, and the whole schedule
jits and differentiates like any other function (grads flow back through
the ppermute chain automatically).

Schedule: the classic M + S - 1 tick loop. Every tick, every stage applies
its block to either a fresh microbatch (stage 0), its neighbor's activation
(inner stages), or garbage it discards (bubble ticks, predicated writes).
Bubble fraction (S-1)/(M+S-1) — pick M >= S for efficiency.

Scope: a pipeline stage must be shape-preserving ([B, T, D] -> [B, T, D]),
which transformer blocks are; embed/head stay replicated outside the
pipelined trunk (the standard megatron-style split).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .train import TrainState


def init_stages(rng: jax.Array, stage_module, example: jnp.ndarray, n_stages: int):
    """Init one param tree per stage and stack them on a leading axis
    (shard it over ``pp`` with `place_stages`)."""
    rngs = jax.random.split(rng, n_stages)
    jit_init = jax.jit(stage_module.init)   # one compile, n_stages calls
    trees = [jit_init(r, example) for r in rngs]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _check_stage_count(stacked_params, n_stages: int) -> None:
    got = jax.tree.leaves(stacked_params)[0].shape[0]
    if got != n_stages:
        # shard_map would otherwise split the stage axis silently and each
        # device would run the wrong (or only part of the) stage stack.
        raise ValueError(f"param tree has {got} stages but mesh pp={n_stages}")


def place_stages(mesh: Mesh, stacked_params):
    """Shard the stage axis over pp (stage s's weights live on pp=s)."""
    _check_stage_count(stacked_params, mesh.shape["pp"])

    def spec_for(a):
        return NamedSharding(mesh, P("pp", *([None] * (a.ndim - 1))))

    return jax.tree.map(lambda a: jax.device_put(a, spec_for(a)), stacked_params)


def pipeline_apply(
    mesh: Mesh,
    apply_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stacked_params,
    x: jnp.ndarray,
    n_microbatches: int,
):
    """Run the pipelined trunk: x [B, ...] -> [B, ...].

    ``apply_fn(stage_params, microbatch)`` applies ONE stage (e.g.
    ``stage_module.apply``); ``stacked_params`` has a leading stage axis
    sharded over pp. B must divide into ``n_microbatches``.
    """
    n_stages = mesh.shape["pp"]
    _check_stage_count(stacked_params, n_stages)
    m = n_microbatches
    b = x.shape[0]
    if b % m:
        raise ValueError(f"batch {b} not divisible into {m} microbatches")

    param_specs = jax.tree.map(
        lambda a: P("pp", *([None] * (a.ndim - 1))), stacked_params
    )

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(param_specs, P()), out_specs=P(),
        check_vma=False,
    )
    def run(stage_params, x):
        # local shard of the stacked tree: leading dim 1 == this stage
        params = jax.tree.map(lambda a: a[0], stage_params)
        idx = lax.axis_index("pp")
        mbs = x.reshape((m, b // m) + x.shape[1:])
        outs = jnp.zeros_like(mbs)
        recv0 = jnp.zeros_like(mbs[0])
        fwd = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(t, carry):
            recv, outs = carry
            feed_idx = jnp.clip(t, 0, m - 1)
            inp = jnp.where(idx == 0, mbs[feed_idx], recv)
            out = apply_fn(params, inp)
            # last stage owns microbatch t-(S-1) this tick (predicated write)
            out_idx = t - (n_stages - 1)
            j = jnp.clip(out_idx, 0, m - 1)
            write = (idx == n_stages - 1) & (out_idx >= 0)
            cur = lax.dynamic_index_in_dim(outs, j, 0, keepdims=False)
            outs = lax.dynamic_update_index_in_dim(
                outs, jnp.where(write, out, cur), j, 0
            )
            recv = lax.ppermute(out, "pp", fwd)
            return recv, outs

        _, outs = lax.fori_loop(0, m + n_stages - 1, tick, (recv0, outs))
        # broadcast the last stage's results to every device (out_specs P())
        outs = lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)), "pp"
        )
        return outs.reshape(x.shape)

    return run(stacked_params, x)


@dataclass
class PipelineTrainer:
    """Trains a pipelined trunk end to end: embed/head replicated closures
    around the staged middle, optimizer state sharded like the params
    (stage axis on pp), gradients flowing back through the ppermute chain.
    """

    mesh: Mesh
    apply_fn: Callable
    tx: optax.GradientTransformation
    n_microbatches: int

    def init_state(self, stacked_params) -> TrainState:
        placed = place_stages(self.mesh, stacked_params)
        opt_state = jax.jit(self.tx.init)(placed)
        return TrainState(step=jnp.zeros((), jnp.int32), params=placed,
                          opt_state=opt_state)

    def make_step(self, loss_of_output: Callable[[jnp.ndarray, Any], jnp.ndarray]):
        """Build the jitted train step. ``loss_of_output(trunk_out, labels)``
        maps the pipelined trunk's output (e.g. [B, T, D] tokens) plus
        labels to a scalar — pooling/head logic lives there, replicated."""

        def step(state: TrainState, x, labels):
            def loss_fn(params):
                out = pipeline_apply(
                    self.mesh, self.apply_fn, params, x, self.n_microbatches
                )
                return loss_of_output(out, labels)

            loss, grads = jax.value_and_grad(loss_fn)(state.params)
            updates, opt_state = self.tx.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
            return TrainState(step=state.step + 1, params=params,
                              opt_state=opt_state), loss

        return jax.jit(step, donate_argnums=(0,))


def make_pipeline_trainer(
    mesh: Mesh,
    apply_fn: Callable,
    n_microbatches: int,
    learning_rate: float = 1e-3,
    weight_decay: float = 0.0,
) -> PipelineTrainer:
    tx = optax.adamw(learning_rate, weight_decay=weight_decay)
    return PipelineTrainer(
        mesh=mesh, apply_fn=apply_fn, tx=tx, n_microbatches=n_microbatches
    )
