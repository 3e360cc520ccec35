"""The expert layer of a device that holds a part of its experts, and
the router in front of it (DeepSeek-V3's routing): what ``models/ling.py``
and ``models/exaone.py`` share, one copy.

Sigmoid scores in float32, a bias added for selection only,
``topk_group`` of ``n_group`` groups kept by the sum of their two best,
the ``top_k`` best of those chosen, their unbiased scores renormalised
and scaled; a shared expert beside them. ``held`` = (first, count) tells
the layer which experts live here: it routes over all of them and
computes the part of the result that its own give (:func:`moe`); what
the others would add is left out.

``cfg`` is any configuration with the fields read here: ``n_experts``,
``n_group``, ``topk_group``, ``top_k``, ``routed_scaling_factor``,
``held`` and ``compute_dtype``. :func:`draw` makes a leaf in its serving
type block by block: a model that holds billions of parameters as bf16
cannot make them as float32 masters first.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_BLOCK_ELEMS = 1 << 22  # a leaf is drawn in float32 blocks of this many


def draw(key, shape, scale: float, dtype):
    """Normal(0, scale) of ``shape`` in ``dtype``, drawn in float32
    blocks along the leading axis and rounded block by block: a 250 M
    leaf never exists in float32. The bits come from the device's own
    generator (an ``rbg`` key made of ``key``): threefry in XLA
    operations takes over a minute for 5 B numbers on the chip."""
    data = jax.random.key_data(key) if jnp.issubdtype(
        key.dtype, jax.dtypes.prng_key) else key
    key = jax.random.wrap_key_data(
        jnp.concatenate([data, data ^ jnp.uint32(0x9E3779B9)]), impl="rbg")
    n = shape[0]
    rows = max(1, _BLOCK_ELEMS // max(1, math.prod(shape[1:])))
    rows = max(r for r in range(1, min(rows, n) + 1) if n % r == 0)
    if rows == n:
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)
    blocks = jax.lax.map(
        lambda k: (jax.random.normal(k, (rows, *shape[1:]), jnp.float32)
                   * scale).astype(dtype),
        jax.random.split(key, n // rows))
    return blocks.reshape(shape)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(cfg, scores, bias):
    """The router's choice from its sigmoid ``scores`` [..., E] float32:
    -> (weights [..., top_k] float32, expert ids [..., top_k]). The bias
    moves the SELECTION only: groups are ranked by the sum of their two
    best biased scores, the ``top_k`` best biased scores of the kept
    groups are chosen (``lax.top_k``: exactly top_k, the lower index on
    a tie), and the weights are the chosen experts' unbiased scores,
    renormalised to 1 and scaled. With one group (``n_group`` 1,
    ``topk_group`` 1) it is kept and nothing is masked: the plain
    ``top_k`` of the biased scores."""
    e, ng = cfg.n_experts, cfg.n_group
    biased = scores + bias
    grouped = biased.reshape(*biased.shape[:-1], ng, e // ng)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, kept = jax.lax.top_k(group_score, cfg.topk_group)
    keep = jnp.any(jax.nn.one_hot(kept, ng, dtype=jnp.bool_), axis=-2)
    masked = jnp.where(keep[..., None], grouped, -jnp.inf)
    _, ids = jax.lax.top_k(masked.reshape(biased.shape), cfg.top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True) \
        * cfg.routed_scaling_factor
    return weights, ids


def moe(cfg, p, x, aux: dict | None = None):
    """The expert layer of a device that holds ``cfg.held`` = (first,
    count) of the experts. x [B, T, D]. Every token is routed over ALL
    experts; an assignment to an expert that is not held is left out:
    in the sort by expert it falls behind the held ones, into rows that
    belong to no group, which the grouped matmul never visits
    (``ops/grouped_matmul.py``: its grid covers the groups' rows only;
    off the TPU ``ragged_dot`` leaves such rows zero), its gather reads
    row 0 and its part of the sum is masked. The shared expert is
    computed in full. On one device the layer runs without an exchange:
    the other devices' partial sums are not here and nothing stands in
    for them. With ``aux`` the chosen ids [B, T, top_k] are left in
    ``aux["expert_ids"]``."""
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    cdt = cfg.compute_dtype
    b, t, d = x.shape
    kk = cfg.top_k
    first, count = cfg.held
    xf = x.reshape(b * t, d)
    with jax.named_scope("moe_router"):
        scores = jax.nn.sigmoid(jnp.dot(
            xf, p["router"], preferred_element_type=jnp.float32))
        weights, ids = route(cfg, scores, p["router_bias"])
        if aux is not None:
            aux["expert_ids"] = ids.reshape(b, t, kk)
    with jax.named_scope("moe_experts"):
        local = ids.reshape(-1) - first
        held = (local >= 0) & (local < count)
        key = jnp.where(held, local, count)  # the others sort last
        order = jnp.argsort(key, stable=True)
        group_sizes = jnp.sum(
            jax.nn.one_hot(key, count, dtype=jnp.int32), axis=0)
        rows = xf[jnp.where(held[order], order // kk, 0)]
        experts = functools.partial(grouped_matmul,
                                    group_sizes=group_sizes)
        gate = experts(rows, p["w_gate"])
        up = experts(rows, p["w_up"])
        y = experts(jax.nn.silu(gate) * up, p["w_down"])
        unsort = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        y = jnp.where(held[:, None], y[unsort], 0).astype(jnp.float32)
        out = jnp.sum(y.reshape(b * t, kk, d) * weights[..., None], axis=1)
    with jax.named_scope("moe_shared"):
        out = out.astype(cdt) + swiglu(
            xf, p["shared_gate"], p["shared_up"], p["shared_down"])
    return out.reshape(b, t, d)


@jax.named_scope("moe_router")
def routing_counts(cfg, ids, active) -> tuple:
    """ids [B, 1, top_k] of one expert layer's decode step -> (distinct
    held experts that got a row from an active slot, the active slots'
    assignments, those of them to held experts), int32 scalars."""
    first, count = cfg.held
    hit = jax.nn.one_hot(ids - first, count, dtype=jnp.bool_)
    hit = hit & active[:, None, None, None]
    return (jnp.sum(jnp.any(hit, axis=(0, 1, 2)), dtype=jnp.int32),
            jnp.sum(active, dtype=jnp.int32) * ids.shape[-1],
            jnp.sum(hit, dtype=jnp.int32))


@jax.named_scope("moe_router")
def prefill_loads(cfg, ids, true_lens):
    """ids [L_moe, F, P, top_k] of a prefill -> [L_moe, count] int32: the
    assignments each HELD expert got in each layer from the REAL
    positions of the prompts (``true_lens`` [F] masks the padding)."""
    first, count = cfg.held
    real = jnp.arange(ids.shape[2])[None, :] < true_lens[:, None]
    hit = jax.nn.one_hot(ids - first, count, dtype=jnp.int32)
    return jnp.sum(hit * real[None, :, :, None, None], axis=(1, 2, 3))
