"""Sharded train state + jitted train step builders.

Equivalent capability to the reference's DDP wiring (reference:
python/ray/train/torch/train_loop_utils.py `prepare_model` wrapping
DistributedDataParallel) — except there is no wrapper: the step function is
jitted with NamedShardings derived from logical rules, and GSPMD inserts the
gradient reduce-scatters/all-gathers over ICI. One code path covers
DP / FSDP(ZeRO-3) / TP / SP by changing the mesh and rule table only.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu.parallel.mesh import use_mesh
from ray_tpu.utils.trees import path_name
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES,
    LogicalRules,
    logical_to_mesh_spec,
    logical_tree_to_shardings,
)


@jax.tree_util.register_pytree_node_class
class TrainState:
    """step / params / opt_state pytree (params are f32 masters)."""

    def __init__(self, step, params, opt_state):
        self.step = step
        self.params = params
        self.opt_state = opt_state

    def tree_flatten(self):
        return (self.step, self.params, self.opt_state), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def batch_sharding(mesh: Mesh, rules: LogicalRules = DEFAULT_RULES, *, ndim: int = 2):
    """Sharding for a [batch, seq, ...] batch array."""
    names = ("batch", "seq") + (None,) * (ndim - 2)
    return NamedSharding(mesh, logical_to_mesh_spec(names[:ndim], rules, mesh))


def _path_names(path) -> tuple[str, ...]:
    """Normalize a jax key path to a tuple of string names."""
    return tuple(path_name(path).split("/"))


def train_state_shardings(
    init_params_fn: Callable[[jax.Array], Any],
    param_axes,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rules: LogicalRules = DEFAULT_RULES,
    *,
    key=None,
):
    """(init_fn, abstract_state, state_shardings) for a TrainState on
    ``mesh`` — shapes only, nothing is placed. `init_train_state` runs
    ``init_fn``; a compile for a described (not attached) device hands
    the abstract state to ``step.lower`` instead."""
    if key is None:
        key = jax.random.PRNGKey(0)

    p_sh = logical_tree_to_shardings(param_axes, mesh, rules)
    scalar = NamedSharding(mesh, PartitionSpec())

    def _init(k):
        params = init_params_fn(k)
        opt_state = optimizer.init(params)
        return TrainState(jnp.zeros((), jnp.int32), params, opt_state)

    # Opt-state shardings: optimizer moments (adam mu/nu, etc.) mirror the
    # param tree structure, so match each opt leaf to the param whose key path
    # is a suffix of the opt leaf's path (e.g. (0,'mu','layers','wq') ends
    # with ('layers','wq')). Shape matching alone is wrong: wq/wo are both
    # [L, D, D] with transposed shardings. Unmatched leaves (counts, scalars)
    # replicate.
    abstract = jax.eval_shape(_init, key)
    param_by_path = {
        _path_names(path): sh
        for (path, _), sh in zip(
            jax.tree_util.tree_flatten_with_path(abstract.params)[0],
            jax.tree_util.tree_flatten(p_sh)[0],
        )
    }

    def match(path, leaf):
        names = _path_names(path)
        for start in range(len(names)):
            hit = param_by_path.get(names[start:])
            if hit is not None and len(hit.spec) <= leaf.ndim:
                return hit
        return scalar

    opt_sh = jax.tree_util.tree_map_with_path(match, abstract.opt_state)
    return _init, abstract, TrainState(scalar, p_sh, opt_sh)


def init_train_state(
    init_params_fn: Callable[[jax.Array], Any],
    param_axes,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rules: LogicalRules = DEFAULT_RULES,
    *,
    key=None,
) -> tuple[TrainState, Any]:
    """Create a fully-sharded TrainState directly on device.

    Init runs under jit with out_shardings so no replicated copy of the params
    ever materializes (critical for fsdp-sharded 7B+ states).

    Returns (state, state_shardings).
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    init_fn, _, state_sh = train_state_shardings(
        init_params_fn, param_axes, optimizer, mesh, rules, key=key)
    with use_mesh(mesh):
        state = jax.jit(init_fn, out_shardings=state_sh)(key)
    return state, state_sh


def make_train_step(
    loss_fn: Callable[[Any, Any], tuple[jax.Array, dict]],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    state_shardings,
    rules: LogicalRules = DEFAULT_RULES,
    *,
    donate_state: bool = True,
    compute_grad_norm: bool = True,
    grads_dtype=None,
):
    """Build the jitted SPMD train step: (state, batch) -> (state, metrics).

    loss_fn(params, batch) -> (scalar_loss, metrics_dict).
    compute_grad_norm=False drops the grad_norm metric — its global_norm is
    an extra full HBM pass over the gradient tree (~2 ms at 350M on v5e),
    real money in a tight step when the caller doesn't log it.
    grads_dtype=bfloat16 differentiates through a low-precision view of
    the params so the stored gradient tree is bf16 — halves the gradient
    HBM footprint (the fit-enabler for 1B-class states on one v5e chip);
    dot accumulation stays f32 inside XLA, and the fused optimizer
    upcasts per-leaf before the f32 master update.
    """
    scalar = NamedSharding(mesh, PartitionSpec())

    def step(state: TrainState, batch):
        if grads_dtype is not None:
            with jax.named_scope("optimizer"):  # (the masters' low view)
                p_low = jax.tree_util.tree_map(
                    lambda p: p.astype(grads_dtype)
                    if jnp.issubdtype(p.dtype, jnp.floating) else p,
                    state.params,
                )
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(p_low, batch)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        if compute_grad_norm:
            with jax.named_scope("optimizer"):
                metrics = dict(metrics, grad_norm=optax.global_norm(grads))
        return TrainState(state.step + 1, params, opt_state), metrics

    return jax.jit(
        step,
        out_shardings=(state_shardings, None),
        donate_argnums=(0,) if donate_state else (),
    )
