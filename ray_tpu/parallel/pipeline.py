"""GPipe-style pipeline parallelism over the ``pp`` mesh axis.

The reference has no in-tree pipeline parallelism — it arrives only through
Alpa release tests (reference: release/release_tests.yaml:3347
`alpa_opt_2_7b_sanity_check`; SURVEY §2.7 TP/PP row) — so this is a from-first-
principles TPU design, not a port: the layer stack is sharded over the ``pp``
mesh axis (one contiguous block of layers per stage), microbatches stream
through the stages, and the only cross-stage communication is a single
`ppermute` of one microbatch's activations per tick. That maps PP onto the
slowest mesh dimension (DCN across slices) while dp/fsdp/sp/tp/ep keep riding
ICI *inside* each stage via GSPMD — the pipeline body is a partial-manual
`shard_map` (manual over ``pp`` only, every other axis stays auto).

Schedule: plain GPipe. With S stages and M microbatches the loop runs
M + S - 1 ticks; each tick every stage applies its local layer block and
hands its activation to the next stage. Bubble fraction (S-1)/(M+S-1) — pick
M >= 4*S to amortize. All control flow is a `lax.scan` over ticks, so the
whole schedule is one compiled program (XLA overlaps the ppermute with the
next tick's compute), and reverse-mode AD through scan+ppermute gives the
1F1B-equivalent backward for free.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_stages(mesh=None, axis: str = "pp") -> int:
    """Size of the pipeline axis in ``mesh`` (or the ambient mesh)."""
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    return dict(mesh.shape).get(axis, 1)


def pipeline_apply(
    layer_fn: Callable[[jax.Array, Any], jax.Array],
    stacked_params,
    h: jax.Array,
    *,
    num_microbatches: int,
    axis: str = "pp",
    mesh=None,
):
    """Run a stacked layer pytree over ``h`` as an S-stage GPipe pipeline.

    Args:
      layer_fn: ``(h, layer_params) -> h`` applying ONE layer (pre-wrapped in
        jax.checkpoint by the caller if remat is wanted).
      stacked_params: pytree whose leaves have a leading ``[L, ...]`` layers
        axis; must be sharded ``P(axis)`` on that axis (logical rule
        ``("layers", "pp")``). L must be divisible by the stage count.
      h: ``[B, ...]`` activations, replicated over ``axis`` (other mesh axes
        free to be GSPMD-sharded — they stay auto inside the pipeline).
      num_microbatches: M; B must be divisible by M.

    Returns ``[B, ...]`` activations, replicated over ``axis``.
    """
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    s_count = dict(mesh.shape).get(axis, 1)

    if s_count == 1:
        out, _ = jax.lax.scan(lambda c, p: (layer_fn(c, p), None), h, stacked_params)
        return out

    n_layers = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if n_layers % s_count:
        raise ValueError(f"n_layers={n_layers} not divisible by pp={s_count}")
    batch = h.shape[0]
    m = num_microbatches
    if batch % m:
        raise ValueError(f"batch={batch} not divisible by microbatches={m}")

    def stage_body(local_params, x):
        # Manual over `axis` only: local_params is this stage's [L/S, ...]
        # block, x is the full (auto-sharded) activation batch.
        s = jax.lax.axis_index(axis)
        mb = x.reshape((m, batch // m) + x.shape[1:])

        def block(h_):
            out, _ = jax.lax.scan(
                lambda c, p: (layer_fn(c, p), None), h_, local_params
            )
            return out

        def tick(carry, t):
            cur, out = carry
            # Stage 0 ingests microbatch t (clamped; bubbles recompute the
            # last microbatch, whose result is masked out downstream).
            fresh = jax.lax.dynamic_index_in_dim(
                mb, jnp.clip(t, 0, m - 1), keepdims=False
            )
            cur = jnp.where(s == 0, fresh, cur)
            y = block(cur)
            # The last stage finished microbatch t-(S-1) this tick.
            j = t - (s_count - 1)
            write = (s == s_count - 1) & (j >= 0)
            jc = jnp.clip(j, 0, m - 1)
            prev = jax.lax.dynamic_index_in_dim(out, jc, keepdims=False)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(write, y, prev), jc, 0
            )
            # Hand activations to the next stage (ring; stage 0's stale
            # input is overwritten by `fresh` next tick).
            y = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % s_count) for i in range(s_count)]
            )
            return (y, out), None

        # Initial carries are constants, but the tick body makes them vary
        # by stage; mark them pp-varying up front (scan carry types must
        # be loop-invariant under the vma type system).
        cur0 = jax.lax.pcast(
            jnp.zeros((batch // m,) + x.shape[1:], x.dtype), (axis,), to="varying"
        )
        out0 = jax.lax.pcast(jnp.zeros_like(mb), (axis,), to="varying")
        (_, out), _ = jax.lax.scan(
            tick, (cur0, out0), jnp.arange(m + s_count - 1)
        )
        # Only the last stage holds real outputs; psum broadcasts them so the
        # result is replicated over the pp axis (grads flow back the same
        # masked path in reverse).
        out = jax.lax.psum(
            jnp.where(s == s_count - 1, out, jnp.zeros_like(out)), axis
        )
        return out.reshape(x.shape)

    return jax.shard_map(
        stage_body,
        mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(axis), stacked_params), P()),
        out_specs=P(),
        axis_names=frozenset({axis}),
    )(stacked_params, h)
