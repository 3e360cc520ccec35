"""Flash attention for TPU, written in pallas.

Online-softmax tiled attention: grid (batch, q_head, q_block, k_block) with the
k_block dimension innermost — TPU grids execute sequentially per core, so f32
scratch accumulators (m, l, acc) carry across k iterations and the output tile
is written once on the last k step. Causal blocks strictly above the diagonal
are predicated off with pl.when, skipping ~half the FLOPs.

GQA is handled in the BlockSpec index maps: q head h reads kv head h // group,
so no kv replication ever materializes.

Backward is the standard flash-2 kernel pair: the forward additionally emits
the per-row logsumexp ([B, H, T] f32); the backward recomputes
p = exp(s - lse) per tile and runs two kernels — dq with
the k dimension innermost, dk/dv with the q dimension innermost — so memory
stays O(block²) and nothing [T, S]-shaped ever materializes.

Which call runs which body (whose call is which: the docstring of
``ops.attention.attention``). :func:`flash_attention` (differentiable:
every block's ``forward``, both train steps, the Llama block's prefill)
runs :func:`_fwd_kernel`, or :func:`_fwd_kernel_1pass` where the keys
are one tile, and ALWAYS makes the lse, since nothing there can see
whether a call will be differentiated: two results, ``flash_fwd`` in a
trace. A SERVING prefill never is, and takes the call below (PR 69).

Forward only (:func:`flash_fwd`, PR 50): keys wider than values (``d_qk``
192 beside ``d_v`` 128: the output and the accumulator take v's width),
the query rows at a TRACED ``offset`` behind the keys' first row (a
segment of a prompt against the rows written so far, or at 0 a whole
bucket: the grid is bounded by the call's diagonal and an index map
that stops at a q block's last live k block keeps its dead steps off
the HBM), and a learned sink (a logit a head that joins the
denominator and takes no value). Without a band it is a body
of its own since PR 67 (:func:`_fwd_kernel_t`, ONE result, ``flash_fwd``
in a trace as well): a cell is as many of a kv head's query heads as
give 2,048 rows (:func:`_fwd_blocks`), the scores are formed TRANSPOSED
as the band's below, and the online update LAGS a tile (a tile's
exponentials are taken against the max of the tiles before it, so that
none waits for its own tile's max); it shares no branch with the
differentiable call's kernels, whose program text is their parent's.

A band (``window``: row i sees keys i + offset - window + 1 ... i +
offset) is a kernel of its own (:func:`_window_kernel`, PR 55, shown in
a trace as ``flash_fwd_window``) and shares no branch with the others.
Its cell is ONE KV HEAD'S WHOLE GROUP of query heads over one q block:
the group's rows are one ``[group * block_q, d_qk]`` operand against the
two or three k blocks the q block's band touches, each an operand of the
one grid step, so k and v are fetched once a group, the softmax is plain
(no scratch, no running max) and the blocks come from the window
(:func:`_window_blocks`), not from ``flash_block_q`` / ``_k``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.utils.math import cdiv

# Runtime block-size defaults live in _private/config.py (flash_block_q/_k,
# env-overridable); round-3 v5e measurement: bq=1024 with a full-row k tile
# wins at T=2048 — per-grid-cell overhead dominates, fewer/bigger cells win.
# Up to this sequence length the kernels take the whole row/column as one
# inner tile: per-block overhead and dead-block DMA cost more than the
# causal-flop saving at short-to-medium T (measured on v5e: full-row
# noncausal matmuls at this shape beat half-flop tiled causal by ~30%).
_FULL_INNER_MAX = 2048
_BWD_INNER = 1024  # min tile width along each bwd kernel's inner grid dim
_NEG_INF = -1e30
_LOG2E = 1.4426950408889634


def _heads_per_block(flag: str, hq: int, group: int) -> int:
    """Clamped heads-per-grid-cell for `flag` in the single-pass forward
    and the fused backward: must divide hq, and with grouped kv heads
    (``group > 1``) it is one, since those kernels' cells pair head h of
    q with head h of k (only the forward-only kernels,
    :func:`_fwd_kernel_t` and :func:`_window_kernel`, put a kv head's
    query heads in a cell). One helper so the forward
    and fused-backward eligibility rules can't diverge."""
    from ray_tpu._private import config as _cfg

    hb = max(1, _cfg.get(flag))
    while hb > 1 and (hq % hb or group > 1):
        hb //= 2
    return hb


def _vmem_limit() -> int:
    """Scoped-VMEM ceiling for mosaic (bytes). The compiler's 16MB default
    is far under the 128MB a v5e core physically has; the multi-head
    single-pass forward needs the headroom for its per-head [bq, s] f32
    score/probability intermediates."""
    from ray_tpu._private import config as _cfg

    return int(_cfg.get("flash_vmem_limit_mb")) * 1024 * 1024


def _causal_mask(s, q_start, k_start, offset):
    """End-aligned causal mask: query row i attends keys <= i + offset."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_start
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start
    return jnp.where(rows + offset >= cols, s, _NEG_INF)


def _last_block(q_start, block_q, block_k, offset, nk):
    """The last k block, of ``nk``, that rows ``q_start`` .. of a q block
    see: the diagonal's."""
    # (lax.div truncates: the numerator is >= 0)
    return jnp.minimum(
        jax.lax.div(q_start + block_q - 1 + offset, block_k), nk - 1)


def _last_walked(q_start, block_q, block_k, offset, nk):
    """:func:`_last_block`, or block 0 where the q block's rows lie
    before every key (T > S): that block is walked, masked whole."""
    return jnp.maximum(_last_block(q_start, block_q, block_k, offset, nk), 0)


def _band_blocks(q_start, block_q, block_k, offset, window, nk):
    """(first, last) k block that a q block's band touches, of ``nk``:
    from its lower edge's up to the diagonal's."""
    return jax.lax.div(
        jnp.maximum(q_start + offset - window + 1, 0), block_k), _last_block(
            q_start, block_q, block_k, offset, nk)


def _block_live(causal, q_start, k_start, block_q, offset):
    """A [q, k] tile is dead iff it lies strictly above the shifted diagonal."""
    return jnp.logical_or(
        jnp.logical_not(causal), k_start <= q_start + block_q - 1 + offset
    )


def _straddles(q_start, k_start, block_k, offset):
    """Traced predicate: the tile straddles the diagonal (some entries
    masked). Fully-live tiles take a branch without the iota/compare/
    select VPU passes — the kernel is exp/VPU-bound at d=64, so skipping
    them on the (majority) interior tiles is a real win."""
    return k_start + block_k - 1 > q_start + offset


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                causal, scale, block_q, block_k, offset):
    """offset = S - T: the causal mask is end-aligned (query row i attends
    keys <= i + offset), matching attention_reference's tril(k=S-T) so decode
    (T=1 against a long cache) sees the whole prefix."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    def _compute(masked: bool):
        # Matmul operands stay in the input dtype (bf16 hits the MXU's native
        # mode; f32 operands would run at a fraction of peak); accumulation
        # and all softmax statistics are f32 — in LOG2 domain: exp2 is the
        # VPU primitive, so scale*log2e folds into the one post-dot multiply
        # and the natural-log path's extra per-element pass disappears.
        q = q_ref[0, 0]  # [bq, d]
        k = k_ref[0, 0]  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (scale * _LOG2E)  # [bq, bk], log2 domain
        if masked:
            s = _causal_mask(s, q_start, k_start, offset)

        m_prev = m_scr[:, :1]  # [bq, 1] (lanes replicated)
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        if masked:
            # Rows whose every key is masked (possible when T > S under
            # causal) keep m_new at _NEG_INF; exp2(s - m_new) would be
            # exp2(0) = 1 there, so force p to 0 on dead rows.
            p = jnp.where(
                m_new > _NEG_INF * 0.5, jnp.exp2(s - m_new), 0.0
            )  # [bq, bk]
        else:
            p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)  # [bq, 1]
        l_new = corr * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)

        v = v_ref[0, 0]  # [bk, d]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, d]
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    live = _block_live(causal, q_start, k_start, block_q, offset)
    if causal:
        straddle = _straddles(q_start, k_start, block_k, offset)
        pl.when(jnp.logical_and(live, straddle))(
            lambda: _compute(masked=True)
        )
        pl.when(jnp.logical_and(live, jnp.logical_not(straddle)))(
            lambda: _compute(masked=False)
        )
    else:
        pl.when(live)(lambda: _compute(masked=False))

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        acc = acc_scr[:]
        o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
        # lse is exposed in NATURAL log (public residual contract); the
        # kernel's m statistic is log2-domain, so convert: ln Z =
        # (m2 + log2 l) * ln2. Rows that attend nothing (only possible
        # when T > S under causal) get lse = +LARGE so the backward's
        # exp2(s - lse*log2e) underflows to 0.
        lse = jnp.where(
            l == 0.0, -_NEG_INF,
            (m_scr[:, :1] + jnp.log2(l_safe)) * (1.0 / _LOG2E),
        )
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _fwd_kernel_1pass(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal,
                      scale, block_q, offset, heads_per_block):
    """Whole k row in one tile (nk == 1): plain softmax, no online-update
    machinery — no scratch init/finalize, no running max/corr passes.
    The common short-to-medium-T case.

    heads_per_block > 1 amortizes the per-grid-cell overhead (the
    dominant cost at these shapes) by computing several heads per cell —
    an inner python loop the compiler unrolls."""
    iq = pl.program_id(2)
    q_start = iq * block_q

    def _one_head(h: int, masked: bool):
        q = q_ref[0, h]  # [bq, d]
        k = k_ref[0, h]  # [s, d] (multi-head cells are MHA-only)
        s_ = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (scale * _LOG2E)  # log2 domain
        if masked:
            s_ = _causal_mask(s_, q_start, 0, offset)
        m = jnp.max(s_, axis=-1, keepdims=True)
        if masked:
            p = jnp.where(m > _NEG_INF * 0.5, jnp.exp2(s_ - m), 0.0)
        else:
            p = jnp.exp2(s_ - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        v = v_ref[0, h]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[0, h] = (pv / l_safe).astype(o_ref.dtype)
        lse = jnp.where(
            l == 0.0, -_NEG_INF, (m + jnp.log2(l_safe)) * (1.0 / _LOG2E))
        lse_ref[0, h] = jnp.broadcast_to(lse, lse_ref.shape[2:])

    # every tile in a causal single-pass row straddles the diagonal
    for h in range(heads_per_block):
        _one_head(h, masked=causal)


def _flash_fwd(q, k, v, *, causal, block_q, block_k, interpret):
    b, hq, t, d = q.shape
    _, hkv, s, _ = k.shape
    dv = v.shape[-1]
    group = hq // hkv
    block_q = min(block_q, t)
    if s <= _FULL_INNER_MAX:
        block_k = s  # one k tile per q row: no dead-block grid/DMA overhead
    else:
        block_k = min(block_k, s)
    if t % block_q or s % block_k:
        raise ValueError(
            f"flash_attention: T={t} / S={s} must be multiples of block sizes "
            f"({block_q}, {block_k}); pad inputs or use attention()."
        )
    scale = d ** -0.5
    nk = cdiv(s, block_k)

    if nk == 1:
        hb = _heads_per_block("flash_heads_per_block", hq, group)
        kernel = functools.partial(
            _fwd_kernel_1pass, causal=causal, scale=scale,
            block_q=block_q, offset=s - t, heads_per_block=hb,
        )
        grid = (b, hq // hb, cdiv(t, block_q))
        scratch = []

        def q_idx(bi, hi, qi):
            return (bi, hi, qi, 0)

        def kv_idx(bi, hi, qi):
            # hb > 1 implies group == 1 (guard above), so the grouped
            # mapping is correct in both branches
            return (bi, hi // group, 0, 0)

        in_specs = [
            pl.BlockSpec((1, hb, block_q, d), q_idx),
            pl.BlockSpec((1, hb, block_k, d), kv_idx),
            pl.BlockSpec((1, hb, block_k, dv), kv_idx),
        ]
        out_specs = [
            pl.BlockSpec((1, hb, block_q, dv), q_idx),
            pl.BlockSpec((1, hb, block_q, 8), q_idx),
        ]
        dims = ("parallel", "parallel", "parallel")
    else:
        kernel = functools.partial(
            _fwd_kernel, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, offset=s - t,
        )
        grid = (b, hq, cdiv(t, block_q), nk)
        scratch = [
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 128), jnp.float32),  # running denom l
            pltpu.VMEM((block_q, dv), jnp.float32),  # output accumulator
        ]

        def q_idx4(bi, hi, qi, ki):
            return (bi, hi, qi, 0)

        def kv_idx4(bi, hi, qi, ki):
            return (bi, hi // group, ki, 0)

        in_specs = [
            pl.BlockSpec((1, 1, block_q, d), q_idx4),
            pl.BlockSpec((1, 1, block_k, d), kv_idx4),
            pl.BlockSpec((1, 1, block_k, dv), kv_idx4),
        ]
        # lse is written 8-lane-replicated: mosaic requires the last
        # block dim be a multiple of 128 or the full array dim, so a
        # packed [B, H, T] output can't be blocked per-head; 8 lanes is
        # the narrowest legal layout (16x less HBM than 128); a lane-
        # major [8, bq] tile measured WORSE (the in-kernel sublane->
        # lane transpose outcosts the narrow DMA).
        out_specs = [
            pl.BlockSpec((1, 1, block_q, dv), q_idx4),
            pl.BlockSpec((1, 1, block_q, 8), q_idx4),
        ]
        dims = ("parallel", "parallel", "parallel", "arbitrary")

    out, lse4 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, t, dv), q.dtype),
            jax.ShapeDtypeStruct((b, hq, t, 8), jnp.float32),
        ],
        scratch_shapes=scratch,
        # b/head/q rows are independent -> mosaic may pipeline them; only
        # the innermost k dim carries scratch state.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=dims,
            vmem_limit_bytes=_vmem_limit(),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse4[..., 0]  # lse: [B, H, T] f32


def _fwd_blocks(group: int, t: int, s: int):
    """The forward-only call's (query heads a cell, block_q, block_k)
    where the caller gives none, from its shapes: a cell is [1,024 keys,
    2,048 rows] of scores, the rows as many of a kv head's query heads
    as divide its group (k and v fetched once for them) over a q block
    as short as whole lanes allow (128 rows: the least of a straddling
    tile above the diagonal), one head's 2,048 rows where the group is
    one. Read on the chip at groups of 16 to 1 (``PERF.md`` §6 PR 67:
    every content of 2,048 rows reads the same at 1,024 keys, 512 keys
    cost 4% and 4,096 rows by 1,024 keys do not fit)."""
    heads = max(h for h in range(1, min(group, 16) + 1) if group % h == 0)
    block_q = 128
    while heads * block_q * 2 <= 2048:
        block_q *= 2
    return heads, min(t, block_q), min(s, 1024)


# The lagged update's room, log2 domain: a tile's scores may stand this far
# above the rows' max so far before the tile is redone with the max first
# (2^64 x block_k probabilities x |v| is far inside float32).
_LAG_MAX = 64.0


def _fwd_kernel_t(offset_ref, q_ref, k_ref, v_ref, *refs, scale, block_q,
                  block_k, nk, sink):
    """The forward-only body: ``heads`` query heads of one kv head over
    one q block, q_ref [1, heads, block_q, d_qk] ONE [heads * block_q,
    d_qk] operand, against k block ``ik`` (the grid's step; dead past
    :func:`_last_walked`'s, of ``nk``); ``refs`` are the cell's [1, 1,
    heads * block_q] sink logits (a head's, once a row) with ``sink``, o
    [1, heads, block_q, d_v] and the scratch.

    The scores are formed TRANSPOSED, ``k @ q^T`` [block_k, rows], as
    :func:`_window_kernel`'s: keys down the sublanes, the cell's rows
    along the lanes, so that a row's max and sum are elementwise over
    registers and ``m``, ``l`` and the correction whole lanes ([1,
    rows]); the accumulator is ``v^T @ p^T`` [d_v, rows], scaled along
    the sublanes and turned ONCE a q block, in the last live step, where
    the sink joins. Statistics in float32 in the log2 domain as
    :func:`_fwd_kernel`'s; no lse.

    The update LAGS: behind a q block's first tile a tile's
    probabilities are ``exp2(s - m)`` with ``m`` the rows' max over the
    tiles BEFORE it, so that no exponential waits for the tile's own max
    (that wait, not the statistics' passes, is what a tile cost beside
    its products: ``PERF.md`` §6 PR 67); the tile's sum and product join
    the state in that reference and the state is then moved to the new
    max: the same sums, every term against another reference. Where a
    tile's scores stand more than ``_LAG_MAX`` above the max so far (a
    float32 overflow in sight) nothing of it is kept and the tile is
    done again max first, as a q block's first tile is."""
    *sink_ref, o_ref, m_scr, l_scr, acc_scr, redo_scr = refs
    heads, _, d = q_ref.shape[1:]
    rows = heads * block_q
    ik = pl.program_id(3)
    offset = offset_ref[0]
    q_start = pl.program_id(2) * block_q
    last = _last_walked(q_start, block_q, block_k, offset, nk)
    k_start = ik * block_k

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def scores(masked: bool):
        s = jax.lax.dot_general(
            k_ref[0, 0], q_ref[0].reshape(rows, d), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (scale * _LOG2E)  # [bk, rows], log2 domain
        if masked:  # one head's [block_k, block_q], the same for the cell
            key = jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0) + k_start
            at = jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1) + (q_start + offset)
            unseen = jnp.where(key <= at, 0.0, _NEG_INF)  # s + it: _NEG_INF
            s = s + jnp.concatenate([unseen] * heads, axis=1)
        return s

    def weighted(p):
        v = v_ref[0, 0]
        return jax.lax.dot_general(
            v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [dv, rows]

    def finite(m):
        # a row that has seen nothing keeps _NEG_INF: exp2(s - 0) is 0
        # there (possible when T > S), not exp2(0)
        return jnp.where(m > _NEG_INF * 0.5, m, 0.0)

    def max_first():
        s = scores(masked=True)
        m_prev = m_scr[...]  # [1, rows]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp2(s - finite(m_new))
        corr = jnp.exp2(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=0, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + weighted(p)
        m_scr[...] = m_new

    def lagged(masked: bool):
        # (behind an unmasked tile every row has seen a key)
        real = finite if masked else (lambda m: m)
        s = scores(masked)
        m_prev = m_scr[...]
        ref = real(m_prev)
        p = jnp.exp2(s - ref)
        m_tile = jnp.max(s, axis=0, keepdims=True)
        l_tile, acc_tile = jnp.sum(p, axis=0, keepdims=True), weighted(p)
        # (a row's first keys stand _NEG_INF above nothing: max first)
        fits = jnp.max(m_tile - m_prev) <= _LAG_MAX
        redo_scr[0] = jnp.where(fits, 0, 1)

        @pl.when(fits)
        def _join():
            m_new = jnp.maximum(m_prev, m_tile)
            moved = jnp.exp2(ref - real(m_new))
            l_scr[...] = (l_scr[...] + l_tile) * moved
            acc_scr[...] = (acc_scr[...] + acc_tile) * moved
            m_scr[...] = m_new

    redo_scr[0] = 0
    behind = jnp.logical_and(ik > 0, ik <= last)
    straddle = _straddles(q_start, k_start, block_k, offset)
    pl.when(jnp.logical_and(behind, straddle))(lambda: lagged(masked=True))
    pl.when(jnp.logical_and(behind, jnp.logical_not(straddle)))(
        lambda: lagged(masked=False))
    pl.when(jnp.logical_or(ik == 0, redo_scr[0] == 1))(max_first)

    @pl.when(ik == last)
    def _finalize():
        l, acc = l_scr[...], acc_scr[...]
        if sink_ref:  # the sink's term joins the sum; its value is nothing
            sink2 = sink_ref[0][0] * _LOG2E  # [1, rows], log2 domain
            m_all = jnp.maximum(m_scr[...], sink2)
            shrink = jnp.exp2(m_scr[...] - m_all)
            l = l * shrink + jnp.exp2(sink2 - m_all)
            acc = acc * shrink
        o = (acc / jnp.where(l == 0.0, 1.0, l)).T  # [rows, dv]
        o_ref[0] = o.reshape(heads, block_q, -1).astype(o_ref.dtype)


def _flash_fwd_at(q, k, v, offset, sink, *, heads, block_q, block_k,
                  interpret):
    """The forward-only call: grid (batch, q heads / ``heads``, q block,
    k block), ``offset`` [1] int32 scalar-prefetched; the last grid
    dimension is the k blocks that ANY row of the call sees (a bound
    traced with the offset: the blocks behind the call's diagonal are no
    steps at all), and the k / v index map stops at a q block's own last
    live block, so that a dead step asks for the block that is there
    already. ONE result: nothing reads an lse."""
    b, hq, t, d = q.shape
    _, hkv, s, dv = v.shape
    group = hq // hkv
    if t % block_q or s % block_k or group % heads:
        raise ValueError(
            f"flash_fwd: T={t} / S={s} must be multiples of the blocks "
            f"({block_q}, {block_k}) and a kv head's {group} query heads of "
            f"a cell's {heads}; pad inputs or pass blocks.")
    nk = s // block_k
    walked = jnp.clip(jax.lax.div(offset[0] + t - 1, block_k) + 1, 1, nk)
    rows = heads * block_q

    def q_idx(bi, hi, qi, ki, off):
        return (bi, hi, qi, 0)

    def kv_idx(bi, hi, qi, ki, off):
        last = _last_walked(qi * block_q, block_q, block_k, off[0], nk)
        return (bi, hi * heads // group, jnp.minimum(ki, last), 0)

    operands, in_specs = [q, k, v], [
        pl.BlockSpec((1, heads, block_q, d), q_idx),
        pl.BlockSpec((1, 1, block_k, d), kv_idx),
        pl.BlockSpec((1, 1, block_k, dv), kv_idx)]
    if sink is not None:
        operands.append(jnp.repeat(
            sink.astype(jnp.float32), block_q).reshape(hq // heads, 1, -1))
        in_specs.append(pl.BlockSpec(
            (1, 1, rows), lambda bi, hi, qi, ki, off: (hi, 0, 0)))
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel_t, scale=d ** -0.5, block_q=block_q,
            block_k=block_k, nk=nk, sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hq // heads, t // block_q, walked),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, heads, block_q, dv), q_idx),
            scratch_shapes=[
                pltpu.VMEM((1, rows), jnp.float32),  # running max m
                pltpu.VMEM((1, rows), jnp.float32),  # running denom l
                pltpu.VMEM((dv, rows), jnp.float32),  # accumulator
                pltpu.SMEM((1,), jnp.int32),  # a tile to do again
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hq, t, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_vmem_limit(),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(offset, *operands)


def _window_blocks(window: int, group: int):
    """The band's (block_q, block_k) where the caller gives none: a k
    block is the window in whole 128-lane tiles; a q block is as long
    (the fewest scores outside the band that whole blocks allow: two k
    blocks a q block), or the multiple of it that gives a cell's
    products 512 rows where the group is small. Read on the chip at
    groups of 8, 4 and 1 (``PERF.md`` §6 PR 55)."""
    block_k = cdiv(window, 128) * 128
    return block_k * max(1, 512 // (group * block_k)), block_k


def _window_kernel(offset_ref, q_ref, *refs, scale, block_q, block_k, window,
                   nk_all, plain, spare, sink):
    """One kv head's group of query heads over one q block's band: q_ref
    [1, group, block_q, d_qk] is one [group * block_q, d_qk] operand of
    the score product and of ``p @ v``; ``refs`` are ``plain + spare``
    k blocks, as many v blocks, the group's [1, 1, group * block_q] sink
    logits (a head's, once a row) with ``sink``, and o [1, group,
    block_q, d_v]. Operand j is k block ``first + j`` of
    :func:`_band_blocks`.

    The scores are formed TRANSPOSED, ``k @ q^T`` [block_k, rows]: keys
    down the sublanes, the group's rows along the lanes, so that a row's
    max and sum are elementwise over registers and its statistics whole
    lanes (a ``[rows, 1]`` statistic is one lane a register, and a pass
    over it costs what a pass over a whole tile costs: ``PERF.md`` §6
    PR 55); the output ``v^T @ p^T`` is turned once, at the end.

    The first ``plain`` blocks hold the whole band wherever its lower
    edge begins a block (the served case: ``offset`` a multiple of the
    segment): one plain softmax over them, statistics in float32 in the
    log2 domain as :func:`_fwd_kernel`'s. At any other offset the band
    reaches one block further (``spare``), and that block joins by the
    online update, under a ``pl.when`` that the served case never
    enters."""
    *kv_refs, o_ref = refs
    blocks = plain + spare
    k_refs, v_refs = kv_refs[:blocks], kv_refs[blocks:2 * blocks]
    group, _, d = q_ref.shape[1:]
    rows = group * block_q
    q_start = pl.program_id(2) * block_q
    offset = offset_ref[0]
    first, last = _band_blocks(q_start, block_q, block_k, offset, window,
                               nk_all)
    q = q_ref[0].reshape(rows, d)
    # the mask is one head's [block_k, block_q], the same for the group
    key = jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
    at = jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 1) + (q_start + offset)

    def scores(j):
        s = jax.lax.dot_general(
            k_refs[j][0, 0], q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (scale * _LOG2E)  # [bk, rows], log2 domain
        key_j = key + (first + j) * block_k
        # (a block past the last is the last one again: nothing of it)
        seen = (key_j <= at) & (key_j > at - window) & (first + j <= last)
        unseen = jnp.where(seen, 0.0, _NEG_INF)  # s + it: _NEG_INF itself
        return s + jnp.concatenate([unseen] * group, axis=1)

    def weighted(p, j):
        v = v_refs[j][0, 0]
        return jax.lax.dot_general(
            v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [dv, rows]

    def finite(m):
        # a row that sees nothing keeps _NEG_INF: exp2(s - 0) is 0 there
        return jnp.where(m > _NEG_INF * 0.5, m, 0.0)

    def write(l, acc):
        o = (acc / jnp.where(l == 0.0, 1.0, l)).T  # [rows, dv]
        o_ref[0] = o.reshape(group, block_q, -1).astype(o_ref.dtype)

    s = [scores(j) for j in range(plain)]
    m = functools.reduce(
        jnp.maximum, [jnp.max(x, axis=0, keepdims=True) for x in s])
    if sink:  # the sink's term joins the sum; its value is nothing
        sink2 = kv_refs[-1][0] * _LOG2E  # [1, rows]
        m = jnp.maximum(m, sink2)
    p = [jnp.exp2(x - (m if sink else finite(m))) for x in s]
    l = functools.reduce(
        jnp.add, [jnp.sum(x, axis=0, keepdims=True) for x in p])
    if sink:
        l = l + jnp.exp2(sink2 - m)
    acc = functools.reduce(
        jnp.add, [weighted(x, j) for j, x in enumerate(p)])
    write(l, acc)

    def unaligned():
        s = scores(plain)
        m_new = finite(jnp.maximum(m, jnp.max(s, axis=0, keepdims=True)))
        shrink = jnp.exp2(m - m_new)
        p = jnp.exp2(s - m_new)
        write(l * shrink + jnp.sum(p, axis=0, keepdims=True),
              acc * shrink + weighted(p, plain))

    if spare:
        pl.when(first + plain <= last)(unaligned)


def _flash_fwd_window(q, k, v, offset, sink, *, window, block_q, block_k,
                      interpret):
    """The band's call: grid (batch, kv head, q block), ``offset`` [1]
    int32 scalar-prefetched, k and v handed over once a k block the band
    may touch (``cdiv(block_q + window - 1, block_k)``, and one more
    where the offset is no multiple of a block), each with an index map
    of its own that stops at the band's last block. No lse: the call is
    forward only and nothing reads one."""
    b, hq, t, d = q.shape
    _, hkv, s, dv = v.shape
    group = hq // hkv
    if t % block_q or s % block_k:
        raise ValueError(
            f"flash_fwd: T={t} / S={s} must be multiples of the band's "
            f"blocks ({block_q}, {block_k}); pad inputs or pass blocks.")
    nk = s // block_k
    plain = min(nk, cdiv(block_q + window - 1, block_k))
    spare = int(plain < nk)

    def q_idx(bi, hi, qi, off):
        return (bi, hi, qi, 0)

    def kv_spec(j, width):
        def idx(bi, hi, qi, off):
            first, last = _band_blocks(qi * block_q, block_q, block_k,
                                       off[0], window, nk)
            return (bi, hi, jnp.minimum(first + j, last), 0)
        return pl.BlockSpec((1, 1, block_k, width), idx)

    blocks = plain + spare
    operands = [q] + [k] * blocks + [v] * blocks
    in_specs = [pl.BlockSpec((1, group, block_q, d), q_idx)] \
        + [kv_spec(j, d) for j in range(blocks)] \
        + [kv_spec(j, dv) for j in range(blocks)]
    if sink is not None:
        operands.append(jnp.repeat(
            sink.astype(jnp.float32), block_q).reshape(hkv, 1, -1))
        in_specs.append(pl.BlockSpec(
            (1, 1, group * block_q), lambda bi, hi, qi, off: (hi, 0, 0)))
    return pl.pallas_call(
        functools.partial(
            _window_kernel, scale=d ** -0.5, block_q=block_q,
            block_k=block_k, window=window, nk_all=nk, plain=plain,
            spare=spare, sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, t // block_q),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, group, block_q, dv), q_idx)),
        out_shape=jax.ShapeDtypeStruct((b, hq, t, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_vmem_limit(),
        ),
        interpret=interpret,
        name="flash_fwd_window",
    )(offset, *operands)


def flash_fwd(q, k, v, *, offset=None, window: int | None = None, sink=None,
              block_q: int | None = None, block_k: int | None = None,
              interpret: bool = False):
    """The forward kernel alone, in ITS layout: q [B, Hq, T, d_qk] at
    positions ``offset`` .. ``offset + T - 1`` (``None``: S - T; may be
    traced) over k [B, Hkv, S, d_qk], v [B, Hkv, S, d_v] of positions 0
    .. S - 1 -> [B, Hq, T, d_v]. Row i sees keys <= i + offset, with
    ``window`` the last ``window`` of them (the band's own kernel, whose
    blocks come from the window where none are given); ``sink`` [Hq]
    float32 joins each head's denominator (module docstring). Forward
    only: a band, a sink, an offset and k wider than v have no backward
    kernel, and a differentiated call raises (training such a block:
    ROADMAP A3)."""
    group = q.shape[1] // k.shape[1]
    if window is not None:
        wq, wk = _window_blocks(window, group)
    else:
        heads, wq, wk = _fwd_blocks(group, q.shape[2], k.shape[2])
    block_q = min(block_q or wq, q.shape[2])
    block_k = min(block_k or wk, k.shape[2])
    if offset is None:
        offset = k.shape[2] - q.shape[2]

    @jax.custom_vjp
    def forward(q, k, v, offset, sink):
        offset = jnp.asarray(offset, jnp.int32).reshape(1)
        if window is not None:
            return _flash_fwd_window(
                q, k, v, offset, sink, window=window, block_q=block_q,
                block_k=block_k, interpret=interpret)
        return _flash_fwd_at(
            q, k, v, offset, sink, heads=heads, block_q=block_q,
            block_k=block_k, interpret=interpret)

    def refuse(*_):
        raise NotImplementedError(
            "flash_fwd is forward only: a band (window), a sink, a traced "
            "offset and keys wider than values have no backward kernel "
            "(ops/flash_attention.py; training such a block is ROADMAP A3)")

    forward.defvjp(refuse, refuse)
    return forward(q, k, v, offset, sink)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, causal, scale, block_q, block_k, offset):
    """Grid (b, hq, iq, ik), ik innermost: dq tile accumulates across k."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    def _compute(masked: bool):
        q = q_ref[0, 0]  # [bq, d], input dtype (MXU-native)
        k = k_ref[0, 0]  # [bk, d]
        v = v_ref[0, 0]  # [bk, d]
        do = do_ref[0, 0]  # [bq, d]
        lse = jnp.expand_dims(lse_ref[0, 0, 0], -1)  # [bq, 1] f32
        delta = jnp.expand_dims(delta_ref[0, 0, 0], -1)  # [bq, 1] f32

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        if masked:
            s = _causal_mask(s, q_start, k_start, offset)
        p = jnp.exp(s - lse)  # [bq, bk] f32
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        ds = (p * (dp - delta) * scale).astype(k.dtype)  # [bq, bk]
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    live = _block_live(causal, q_start, k_start, block_q, offset)
    if causal:
        straddle = _straddles(q_start, k_start, block_k, offset)
        pl.when(jnp.logical_and(live, straddle))(
            lambda: _compute(masked=True)
        )
        pl.when(jnp.logical_and(live, jnp.logical_not(straddle)))(
            lambda: _compute(masked=False)
        )
    else:
        pl.when(live)(lambda: _compute(masked=False))

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, causal, scale,
                block_q, block_k, offset):
    """Grid (b, hq, ik, iq), iq innermost: dk/dv tiles accumulate across q.

    Outputs are per *query* head ([B, Hq, S, D]); the wrapper sums over the
    GQA group to produce kv-head gradients without any in-kernel races.
    """
    ik = pl.program_id(2)
    iq = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    def _compute(masked: bool):
        q = q_ref[0, 0]  # [bq, d], input dtype (MXU-native)
        k = k_ref[0, 0]  # [bk, d]
        v = v_ref[0, 0]  # [bk, d]
        do = do_ref[0, 0]  # [bq, d]
        lse = jnp.expand_dims(lse_ref[0, 0, 0], -1)  # [bq, 1] f32
        delta = jnp.expand_dims(delta_ref[0, 0, 0], -1)  # [bq, 1] f32

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        if masked:
            s = _causal_mask(s, q_start, k_start, offset)
        p = jnp.exp(s - lse)  # [bq, bk] f32
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # p^T @ do -> [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # ds^T @ q -> [bk, d]

    live = _block_live(causal, q_start, k_start, block_q, offset)
    if causal:
        straddle = _straddles(q_start, k_start, block_k, offset)
        pl.when(jnp.logical_and(live, straddle))(
            lambda: _compute(masked=True)
        )
        pl.when(jnp.logical_and(live, jnp.logical_not(straddle)))(
            lambda: _compute(masked=False)
        )
    else:
        pl.when(live)(lambda: _compute(masked=False))

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse2_ref, delta_ref,
                      dqp_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                      causal, scale, block_q, block_k, offset,
                      heads_per_block=1):
    """Fused dq/dk/dv backward: grid (b, hq/hb, ik, iq), iq innermost.

    heads_per_block > 1 (MHA only, mirroring the single-pass forward)
    computes several heads per grid cell — a python loop the compiler
    unrolls — amortizing the per-cell overhead that binds at these tile
    counts. dk/dv scratch is [hb*block_k, d] with per-head row bands.

    The classic two-kernel split (dq with k inner, dkv with q inner) pays
    for s, p and dp TWICE — 7 MXU dots and 2 softmax recomputes per tile
    pair. Fused, each (q, k) tile is visited once: 5 dots, 1 exp2 pass.
    dk/dv accumulate in VMEM scratch across the inner iq loop; dq would
    have to accumulate across the OUTER ik loop, so each ik writes an f32
    partial ([nk, B, H, T, D]) that the wrapper sums — sequential-grid
    TPU's answer to the atomics a GPU would use here.

    Softmax statistics ride in log2 domain: s2 = (q@k^T)*(scale*log2e),
    p = exp2(s2 - lse*log2e) — exp2 is the VPU primitive, so the natural-
    log path's extra per-element multiply disappears.
    """
    ik = pl.program_id(2)
    iq = pl.program_id(3)
    nq = pl.num_programs(3)
    hb = heads_per_block

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    def _one_head(h: int, masked: bool):
        q = q_ref[0, h]  # [bq, d], input dtype (MXU-native)
        k = k_ref[0, h]  # [bk, d]
        v = v_ref[0, h]  # [bk, d]
        do = do_ref[0, h]  # [bq, d]
        lse2 = jnp.expand_dims(lse2_ref[0, h, 0], -1)  # [bq, 1] f32, log2
        delta = jnp.expand_dims(delta_ref[0, h, 0], -1)  # [bq, 1] f32

        s2 = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (scale * _LOG2E)  # [bq, bk], log2 domain
        if masked:
            s2 = _causal_mask(s2, q_start, k_start, offset)
        p = jnp.exp2(s2 - lse2)  # [bq, bk] f32
        lo, hi_ = h * block_k, (h + 1) * block_k
        dv_scr[lo:hi_] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # p^T @ do -> [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        ds = (p * (dp - delta) * scale).astype(q.dtype)  # [bq, bk]
        dk_scr[lo:hi_] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # ds^T @ q -> [bk, d]
        dqp_ref[0, 0, h] = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dqp_ref.dtype)  # [bq, d] partial

    def _compute(masked: bool):
        for h in range(hb):
            _one_head(h, masked)

    def _zero_dqp():
        for h in range(hb):
            dqp_ref[0, 0, h] = jnp.zeros_like(dqp_ref[0, 0, h])

    live = _block_live(causal, q_start, k_start, block_q, offset)
    if causal:
        straddle = _straddles(q_start, k_start, block_k, offset)
        pl.when(jnp.logical_and(live, straddle))(
            lambda: _compute(masked=True)
        )
        pl.when(jnp.logical_and(live, jnp.logical_not(straddle)))(
            lambda: _compute(masked=False)
        )
        # dead tile: its dq partial still must be defined
        pl.when(jnp.logical_not(live))(_zero_dqp)
    else:
        pl.when(live)(lambda: _compute(masked=False))

    @pl.when(iq == nq - 1)
    def _finalize():
        for h in range(hb):
            lo, hi_ = h * block_k, (h + 1) * block_k
            dk_ref[0, h] = dk_scr[lo:hi_].astype(dk_ref.dtype)
            dv_ref[0, h] = dv_scr[lo:hi_].astype(dv_ref.dtype)


# Above this many dq partials the fused kernel's [nk, B, H, T, D]
# side-array outgrows its win; fall back to the two-kernel path.
_MAX_DQ_PARTIALS = 8


def _fused_blocks(t: int, s: int, block_q: int, block_k: int):
    """The fused backward's tile shape, or None when ineligible — the ONE
    place this is computed, so the gate and the kernel can't disagree."""
    bq = min(block_q, t, 1024)
    bk = min(max(block_k, 512), s, 1024)
    # [bq, bk] f32 tiles dominate VMEM (measured: a full-row bk=2048 tile
    # under the raised scoped limit LOSES ~2% MFU at T=2048 — bigger
    # tiles starve mosaic's cross-cell pipelining before cell-count wins)
    while bq * bk > 1024 * 1024:
        bq //= 2
    if t % bq or s % bk or cdiv(s, bk) > _MAX_DQ_PARTIALS:
        return None
    return bq, bk


def _flash_bwd_fused(q, k, v, o, lse, do, *, causal, block_q, block_k,
                     interpret):
    b, hq, t, d = q.shape
    _, hkv, s, _ = k.shape
    group = hq // hkv
    scale = d ** -0.5
    offset = s - t

    blocks = _fused_blocks(t, s, block_q, block_k)
    assert blocks is not None, "caller gates on _fused_blocks"
    block_q, block_k = blocks
    nq, nk = cdiv(t, block_q), cdiv(s, block_k)

    hb = _heads_per_block("flash_bwd_heads_per_block", hq, group)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse2 = lse * _LOG2E  # natural-log residual -> log2 domain
    lse2_r = lse2[:, :, None, :]
    delta_r = delta[:, :, None, :]

    def row_spec(block, index):
        return pl.BlockSpec((1, hb, 1, block), index)

    dqp, dk_full, dv_full = pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, offset=offset,
            heads_per_block=hb,
        ),
        grid=(b, hq // hb, nk, nq),
        in_specs=[
            pl.BlockSpec((1, hb, block_q, d), lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, hb, block_k, d), lambda bi, hi, ki, qi: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, hb, block_k, d), lambda bi, hi, ki, qi: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, hb, block_q, d), lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            row_spec(block_q, lambda bi, hi, ki, qi: (bi, hi, 0, qi)),
            row_spec(block_q, lambda bi, hi, ki, qi: (bi, hi, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, hb, block_q, d),
                lambda bi, hi, ki, qi: (ki, bi, hi, qi, 0),
            ),
            pl.BlockSpec((1, hb, block_k, d), lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, hb, block_k, d), lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
        ],
        out_shape=[
            # partials ride in the INPUT dtype: f32 inputs keep exact
            # accumulation, bf16 training halves the side-array traffic
            # (each partial is itself an f32 MXU accumulation)
            jax.ShapeDtypeStruct((nk, b, hq, t, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, s, d), k.dtype),
            jax.ShapeDtypeStruct((b, hq, s, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb * block_k, d), jnp.float32),
            pltpu.VMEM((hb * block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_vmem_limit(),
        ),
        interpret=interpret,
        name="flash_bwd_fused",
    )(q, k, v, do, lse2_r, delta_r)

    dq = jnp.sum(dqp.astype(jnp.float32), axis=0).astype(q.dtype)
    if group > 1:
        dk = dk_full.reshape(b, hkv, group, s, d).sum(axis=2).astype(k.dtype)
        dv = dv_full.reshape(b, hkv, group, s, d).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_full, dv_full
    return dq, dk, dv


def _flash_bwd(q, k, v, o, lse, do, *, causal, block_q, block_k, interpret):
    """Fused single-pass backward when the dq-partial side array is small
    enough (the common case); otherwise two kernels with independently
    tuned tile shapes.

    Legacy path: the dq kernel iterates k innermost, so it wants wide k
    tiles (fewer grid steps, bigger contractions); the dkv kernel iterates
    q innermost and wants wide q tiles. The caller's (block_q, block_k)
    seed the *outer* tile of each kernel; the inner tile is widened to the
    sequence length capped at _BWD_INNER.
    """
    if _fused_blocks(q.shape[2], k.shape[2], block_q, block_k) is not None:
        return _flash_bwd_fused(
            q, k, v, o, lse, do, causal=causal, block_q=block_q,
            block_k=block_k, interpret=interpret,
        )
    b, hq, t, d = q.shape
    _, hkv, s, _ = k.shape
    group = hq // hkv
    scale = d ** -0.5
    offset = s - t

    def widen(block, seqlen):
        # Double the tile while it still divides the sequence (the forward
        # already validated seqlen % block == 0), capped at _BWD_INNER:
        # pallas pads ragged blocks with undefined values, which must never
        # reach the accumulating matmuls.
        block = min(block, seqlen)
        while block * 2 <= min(_BWD_INNER, seqlen) and seqlen % (block * 2) == 0:
            block *= 2
        return block

    # dq kernel tiles: [bq_dq, bk_dq], k innermost and wide (the whole row
    # when it fits in VMEM).
    bq_dq = min(block_q, t, 512)
    bk_dq = s if s <= _FULL_INNER_MAX else widen(block_k, s)
    # dkv kernel tiles: [bq_kv, bk_kv], q innermost and wide.
    bq_kv = t if t <= _FULL_INNER_MAX else widen(block_q, t)
    bk_kv = min(block_k, s, 512)

    # delta_i = rowsum(do_i * o_i); cheap elementwise reduce, XLA fuses it.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # [B, H, 1, T] so kernels read (1, 1, 1, block) lane-vectors.
    lse_r = lse[:, :, None, :]
    delta_r = delta[:, :, None, :]

    def row_spec(block, index):
        return pl.BlockSpec((1, 1, 1, block), index)

    block_q, block_k = bq_dq, bk_dq
    nq, nk = cdiv(t, block_q), cdiv(s, block_k)
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, offset=offset,
        ),
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            row_spec(block_q, lambda bi, hi, qi, ki: (bi, hi, 0, qi)),
            row_spec(block_q, lambda bi, hi, qi, ki: (bi, hi, 0, qi)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_vmem_limit(),
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse_r, delta_r)

    block_q, block_k = bq_kv, bk_kv
    nq, nk = cdiv(t, block_q), cdiv(s, block_k)
    dk_full, dv_full = pl.pallas_call(
        functools.partial(
            _dkv_kernel, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, offset=offset,
        ),
        grid=(b, hq, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki, qi: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki, qi: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            row_spec(block_q, lambda bi, hi, ki, qi: (bi, hi, 0, qi)),
            row_spec(block_q, lambda bi, hi, ki, qi: (bi, hi, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, s, d), k.dtype),
            jax.ShapeDtypeStruct((b, hq, s, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_vmem_limit(),
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse_r, delta_r)

    if group > 1:
        dk = dk_full.reshape(b, hkv, group, s, d).sum(axis=2).astype(k.dtype)
        dv = dv_full.reshape(b, hkv, group, s, d).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_full, dv_full
    return dq, dk, dv


# The forward kernel runs OUTSIDE the custom_vjp and its (out, lse) pass
# through `_flash_apply` under stop_gradient: gradients flow only via the
# apply's vjp (the flash-2 backward), while out/lse are plain graph
# tensors that jax.checkpoint policies can save BY NAME ("flash_out" /
# "flash_lse"). Under remat that skips re-running the forward kernel in
# the backward pass (the biggest recompute in the layer) at the cost of
# ~T*(d+1) floats per layer.


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_apply(q, k, v, out, lse, causal, block_q, block_k, interpret):
    return out


def _flash_apply_fwd(q, k, v, out, lse, causal, block_q, block_k,
                     interpret):
    return out, (q, k, v, out, lse)


def _flash_apply_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd(
        q, k, v, o, lse, g, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret,
    )
    # out/lse arrive stop_gradiented; their cotangents are unused
    return dq, dk, dv, jnp.zeros_like(o), jnp.zeros_like(lse)


_flash_apply.defvjp(_flash_apply_fwd, _flash_apply_bwd)


def _flash(q, k, v, causal, block_q, block_k, interpret):
    from jax.ad_checkpoint import checkpoint_name

    # stop_gradient on the kernel inputs: no tangents may enter the
    # pallas forward (it has no JVP rule and must not need one — all
    # differentiation rides _flash_apply's custom_vjp)
    out, lse = _flash_fwd(
        jax.lax.stop_gradient(q), jax.lax.stop_gradient(k),
        jax.lax.stop_gradient(v), causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret,
    )
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return _flash_apply(
        q, k, v, jax.lax.stop_gradient(out), jax.lax.stop_gradient(lse),
        causal, block_q, block_k, interpret,
    )


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
):
    """Flash attention. Layout [B, T, H, D] (matching ops.attention).

    Requires T and S to be multiples of the (clamped) block sizes; callers
    pad. Block sizes default from the config flags flash_block_q/_k
    (RAY_TPU_FLASH_BLOCK_Q/_K) so deployments can retune per chip
    generation without code changes. ``interpret=True`` runs the kernels
    in the Pallas interpreter — a test's explicit choice on a host
    without the chip, never inferred from the backend: a process that
    should be on a TPU and is not must fail to lower, not serve from the
    interpreter.
    """
    if v.shape[-1] != q.shape[-1]:
        raise ValueError(
            f"flash_attention is differentiable and needs values as wide "
            f"as keys ({q.shape[-1]}), not {v.shape[-1]}: flash_fwd is the "
            "forward alone")
    if block_q is None or block_k is None:
        from ray_tpu._private import config as _cfg

        block_q = block_q or _cfg.get("flash_block_q")
        block_k = block_k or _cfg.get("flash_block_k")
    # Kernel-internal layout is [B, H, T, D].
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash(qt, kt, vt, causal, block_q, block_k, interpret)
    return out.transpose(0, 2, 1, 3)
