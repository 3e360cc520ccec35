"""Attention ops: jnp reference + dispatch to the pallas TPU flash kernel.

Layout convention throughout: q [B, T, Hq, D], k/v [B, S, Hkv, D] with
Hq % Hkv == 0 (grouped-query attention; Hkv == Hq is vanilla MHA).

The reference framework has no attention op at all (torch supplies it); flash
attention here is the framework's flagship MXU kernel (see ops/flash_attention.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _repeat_kv(k, n_rep: int):
    """[B, S, Hkv, D] -> [B, S, Hkv*n_rep, D] by repeating each kv head."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def attention_reference(q, k, v, *, causal: bool = True, logits_dtype=jnp.float32):
    """O(T*S)-memory reference attention (also the autodiff oracle for flash).

    Softmax in f32 regardless of input dtype; returns q.dtype.
    """
    n_rep = q.shape[2] // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bthd,bshd->bhts", q, k, preferred_element_type=logits_dtype
    ) * scale
    empty_rows = None
    if causal:
        t, s = logits.shape[-2:]
        mask = jnp.tril(jnp.ones((t, s), dtype=bool), k=s - t)
        logits = jnp.where(mask, logits, jnp.finfo(logits_dtype).min)
        if s < t:
            # Rows attending no keys: softmax would be uniform garbage;
            # define the output as 0 (matches the flash kernel).
            empty_rows = ~mask.any(-1)  # [t]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if empty_rows is not None:
        probs = jnp.where(empty_rows[None, None, :, None], 0.0, probs)
    out = jnp.einsum(
        "bhts,bshd->bthd", probs.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    return out.astype(q.dtype)


def softmax_with_sink(logits, sink):
    """Softmax over the last axis with one more logit in the denominator
    that takes no value: ``sink`` broadcasts against ``logits[..., :1]``.
    float32 in, float32 out; the probabilities sum to less than 1."""
    m = jnp.maximum(jnp.max(logits, axis=-1, keepdims=True), sink)
    e = jnp.exp(logits - m)
    return e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - m))


def attend_rows(q, k, v, *, offset, window: int | None = None, sink=None,
                use_flash: bool | None = None):
    """Rows of a prompt over the rows written so far, FORWARD ONLY (what
    a serving prefill takes: :func:`attention` says which call is whose),
    in the flash kernel's layout: q [B, Hq, T, d_qk] at positions
    ``offset`` .. ``offset + T - 1`` (traced or not; 0: a whole bucket
    from its first row, :func:`attend_bucket`) over k [B, Hkv, S, d_qk],
    v [B, Hkv, S, d_v] of positions 0 .. S - 1 -> [B, Hq, T, d_v]. Row i
    sees keys <= i + offset, in a band (``window``) the last ``window``
    of them; ``sink`` [Hq] float32: a logit a head that joins its
    softmax's denominator and takes no value. ``use_flash`` as
    :func:`attention`'s: the kernel (``flash_attention.flash_fwd``, whose
    blocks come from the call's shapes; differentiated it raises) on a
    TPU, else the XLA body below, which forms the [T, S] scores whole.
    The kernel is NOT wrapped for a mesh (the serving engines run one
    device): under an ambient mesh of more it raises."""
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    if use_flash:
        from ray_tpu.ops.flash_attention import flash_fwd

        mesh = jax.sharding.get_abstract_mesh()
        if mesh.size > 1:
            raise NotImplementedError(
                f"attend_rows under a mesh of {mesh.size} devices: the "
                "forward-only kernel runs in no shard_map and GSPMD cannot "
                "partition a Mosaic call; serve on one device, or call "
                "attention (wrapped, differentiable) or use_flash=False")
        return flash_fwd(q, k, v, offset=offset, window=window, sink=sink)
    b, hq, t, d = q.shape
    hkv, s = k.shape[1:3]
    qg = q.reshape(b, hkv, hq // hkv, t, d)
    logits = jnp.einsum("bkgtd,bksd->bkgts", qg, k,
                        preferred_element_type=jnp.float32) * d ** -0.5
    at = jnp.arange(t)[:, None] + offset  # the query's position
    key = jnp.arange(s)[None, :]
    seen = key <= at
    if window is not None:
        seen &= key > at - window
    logits = jnp.where(seen, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1) if sink is None \
        else softmax_with_sink(logits, sink.astype(jnp.float32).reshape(
            hkv, hq // hkv)[None, :, :, None, None])
    o = jnp.einsum("bkgts,bksd->bkgtd", probs.astype(q.dtype), v,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    return o.reshape(b, hq, t, v.shape[-1])


@functools.partial(jax.jit, static_argnames=("use_flash",))
def attend_bucket(q, k, v, *, use_flash: bool | None = None):
    """:func:`attend_rows` over a whole bucket from position 0 in
    :func:`attention`'s layout ([B, T, H, D] in and out, causal): a
    SERVING prefill's call, never differentiated. The four transposes
    are the ones ``flash_attention`` makes round its own kernel. Jitted
    by itself, as ``ops.kda_chunk``'s call is: a block that calls it
    once a layer traces and lowers the kernel once a program (traced a
    layer, Instella-MoE's seven layers by four buckets cost a replica's
    start 9 s: ``PERF.md`` §6 PR 69)."""
    def heads_first(a):
        return a.transpose(0, 2, 1, 3)

    return heads_first(attend_rows(
        heads_first(q), heads_first(k), heads_first(v), offset=0,
        use_flash=use_flash))


def attention(q, k, v, *, causal: bool = True, use_flash: bool | None = None):
    """Dispatching attention entry point, DIFFERENTIABLE.

    Which call is whose. This one is a block's ``forward`` (what
    ``loss_fn`` differentiates, both train steps, the tests' references)
    and the Llama block's prefill: on a TPU ``flash_attention``, whose
    forward kernel ALWAYS makes the lse its backward reads (nothing there
    can see whether a call will be differentiated), at the blocks
    ``flash_block_q`` / ``_k`` give. A SERVING prefill, which is never
    differentiated, takes :func:`attend_rows` (a segment behind the rows
    so far: MiMo-V2.5, LFM2, the sparse blocks' window layers) or
    :func:`attend_bucket` (a whole bucket from position 0: Solar-Open2,
    Granite, Instella-MoE): ``flash_fwd``, one result, a body of its own
    (``ops/flash_attention.py``'s docstring), and a ``jax.grad`` through
    it raises instead of dropping gradients.

    use_flash=None → the backend's kernel: flash on a TPU backend, the
    reference elsewhere (the flash kernel is TPU-only: pltpu memory
    spaces). Once flash is chosen nothing falls back: sequence lengths
    that are not multiples of the blocks raise (pad to the block size),
    on a TPU backend as for an explicit use_flash=True, instead of
    silently taking the O(T*S) path. use_flash=False is the explicit
    request for the reference.

    Under an ambient mesh of more than one device the kernel runs inside
    a shard_map over that mesh (a Mosaic kernel cannot be partitioned by
    GSPMD): batch over the data axes, heads and kv heads over tp, as the
    logical rules place them; the sequence stays whole.
    """
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    if not use_flash:
        return attention_reference(q, k, v, causal=causal)

    from ray_tpu._private import config as _cfg
    from ray_tpu.ops.flash_attention import flash_attention

    t, s = q.shape[1], k.shape[1]
    # same config flags flash_attention resolves itself
    # (RAY_TPU_FLASH_BLOCK_Q/_K), so deployments retune in one place
    bq = min(_cfg.get("flash_block_q"), t)
    bk = min(_cfg.get("flash_block_k"), s)
    if t % bq or s % bk:
        raise ValueError(
            f"flash attention needs seq lengths (T={t}, S={s}) that are "
            f"multiples of the flash blocks ({bq}, {bk}); pad the "
            "sequence, or pass use_flash=False for the reference"
        )
    kernel = functools.partial(
        flash_attention, causal=causal, block_q=bq, block_k=bk)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.size <= 1:  # no ambient mesh (size 0), or one device
        return kernel(q, k, v)
    return _shard_over_mesh(kernel, mesh, q, k, v)


def _shard_over_mesh(kernel, mesh, q, k, v):
    """Run ``kernel(q, k, v)`` per shard of the ambient mesh, with the
    operand layout the logical rules give q/k/v (parallel/sharding.py)."""
    from ray_tpu.parallel.sharding import logical_to_mesh_spec

    q_spec = logical_to_mesh_spec(
        ("batch", "seq", "heads", "head_dim"), mesh=mesh)
    kv_spec = logical_to_mesh_spec(
        ("batch", "seq", "kv_heads", "head_dim"), mesh=mesh)
    # pad to rank 4: a trailing replicated dim is trimmed from the spec
    b_ax, seq_ax, h_ax, _ = (tuple(q_spec) + (None,) * 4)[:4]
    kv_h_ax = (tuple(kv_spec) + (None,) * 4)[2]

    def ways(ax):
        axes = (ax,) if isinstance(ax, str) else tuple(ax or ())
        return math.prod(mesh.shape[a] for a in axes)

    if seq_ax is not None:
        raise NotImplementedError(
            f"flash attention on a mesh that shards the sequence "
            f"({seq_ax}={ways(seq_ax)}) is not brought up: the kernel "
            "needs whole rows (ops/ring_attention.py and ops/ulysses.py "
            "are not wired into the model); use sp=1 or use_flash=False")
    if q.shape[0] % ways(b_ax) or k.shape[2] % ways(kv_h_ax) \
            or q.shape[2] % ways(h_ax):
        raise ValueError(
            f"flash attention cannot split batch={q.shape[0]}, "
            f"heads={q.shape[2]}, kv_heads={k.shape[2]} over mesh axes "
            f"{b_ax}={ways(b_ax)}, {h_ax}={ways(h_ax)}: each must divide "
            "evenly (tp has to divide n_kv_heads)")
    if mesh.manual_axes:
        raise NotImplementedError(
            f"flash attention inside a region already manual over "
            f"{sorted(mesh.manual_axes)} (parallel/pipeline.py's pp "
            "stages) is not brought up: the nested shard_map's cotangents "
            "lose the region's varying-axes type; use use_flash=False on "
            "a pp mesh")
    # manual over EVERY axis: one left automatic would hand the Mosaic
    # call back to GSPMD, which cannot partition it. check_vma off:
    # pallas_call outputs carry no varying-axes type.
    return jax.shard_map(
        kernel, in_specs=(q_spec, kv_spec, kv_spec), out_specs=q_spec,
        check_vma=False,
    )(q, k, v)
