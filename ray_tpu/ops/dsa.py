"""Learned sparse attention's own operations (DeepSeek-V3.2-Exp's
lightning indexer, as ``models/dots.py`` serves it): the index scores,
an EXACT selection of the ``k`` best of them a query row, and attention
over the selected rows alone.

- **Index scores.** ``I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])``
  over the indexer's heads ``j``, products of the compute dtype
  accumulated in float32, the sum in float32. A decode step's one row a
  slot is the XLA body (:func:`index_scores_xla`); a prefill segment's
  rows over every key so far is a Pallas kernel on a TPU (``dsa_index``
  in a trace, :func:`index_scores`): a [rows, keys] tile is the heads'
  products one after another, each through ``relu`` and its weight into
  the one float32 tile, so that no ``[heads, rows, keys]`` array exists
  (64 x 2,048 x 32,768 x 4 B would be 17 GB). Tiles above the diagonal
  are not computed and hold whatever the buffer held: the selection
  masks by position.
- **Selection** (:func:`select`): the positions of the ``min(k, valid)``
  largest scores of a row, a tie to the earlier position; nothing is
  sorted. The float32 scores are read as unsigned keys of the same
  order; the k-th largest key is found bit by bit (32 counting passes:
  the largest threshold that ``k`` keys still reach), every key above it
  is chosen, and of those equal to it the first ``k - chosen`` by
  position (a running count, made only where such a tie stands). -> a
  [rows, keys] bool mask. On a TPU the 32 passes are one kernel
  (``dsa_kth``: a block of rows read once, counted where it lies).
- **Attention over the selected rows.** A decode step attends absorbed
  over the slot's live latent rows with the unchosen masked
  (``dsa_decode_attn``, :func:`decode_attention_masked`:
  ``decode_attn_latent``'s visits with a bias a key); a gather of the
  chosen rows first was measured and is not the form (below). A prefill
  segment attends UNABSORBED
  over every earlier key with the unchosen masked: a flash kernel that
  takes the mask as an additive bfloat16 bias (0 or ``-1e30``) beside k
  and v (``dsa_attn`` in a trace, :func:`masked_attention`), several
  heads a grid cell so that a bias tile is fetched once for them, the
  one rotated key of all heads an operand of its own (laid beside every
  head's k it was 7% of a prefill's device time, my chip run, PR 58); the
  blocks above the diagonal are skipped as ``flash_fwd``'s are. Its
  XLA body (:func:`masked_attention_xla`) forms the scores whole.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
_FLOOR = -1e20  # under every real score, far over ``NEG``
_LOG2E = 1.4426950408889634
_VMEM_LIMIT = 96 * 1024 * 1024
_LEAST = -2**31  # an invalid entry's key: the least int32


# --------------------------------------------------------------------------
# Index scores
# --------------------------------------------------------------------------

def index_scores_xla(q, w, k):
    """q [B, T, Hi, d], w [B, T, Hi] float32, k [B, S, d] -> [B, T, S]
    float32: ``sum_j w[t, j] relu(q[t, j] . k[s])``, every key (the
    caller masks by position)."""
    s = jnp.einsum("bthd,bsd->bths", q, k,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w[..., None], axis=2)


def _index_kernel(offset_ref, q_ref, w_ref, k_ref, o_ref, *, block_q: int,
                  block_k: int):
    q_start = pl.program_id(1) * block_q
    k_start = pl.program_id(2) * block_k

    @pl.when(k_start <= q_start + block_q - 1 + offset_ref[0])
    def _live():
        k = k_ref[0]  # [bk, d]
        w = w_ref[0]  # [bq, Hi] float32
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for j in range(q_ref.shape[1]):
            s = jax.lax.dot_general(
                q_ref[0, j], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(s, 0.0) * w[:, j:j + 1]
        o_ref[0] = acc


def index_scores(q, w, k, offset, *, use_kernel: bool | None = None,
                 interpret: bool = False, block_q: int = 256,
                 block_k: int = 512):
    """A segment's index scores: q [B, T, Hi, d] at positions ``offset``
    .. (traced or not), w [B, T, Hi] float32, k [B, S, d] of positions
    0 .. S - 1 -> [B, T, S] float32. Only ``s <= t + offset`` means
    anything: the kernel leaves the tiles above the diagonal unwritten.

    ``use_kernel=None``: the Pallas kernel on a TPU where the rows and
    keys are whole blocks, the XLA body elsewhere; ``interpret=True``
    runs the kernel in the Pallas interpreter (never inferred)."""
    b, t, hi, d = q.shape
    s = k.shape[1]
    block_q, block_k = min(block_q, t), min(block_k, s)
    if use_kernel is None:
        use_kernel = interpret or (
            jax.default_backend() == "tpu" and t % block_q == 0
            and s % block_k == 0 and d % 128 == 0)
    if not use_kernel:
        return index_scores_xla(q, w, k)
    if t % block_q or s % block_k:
        raise ValueError(f"dsa_index: T={t} / S={s} must be multiples of "
                         f"the blocks ({block_q}, {block_k})")
    offset = jnp.asarray(offset, jnp.int32).reshape(1)
    return pl.pallas_call(
        functools.partial(_index_kernel, block_q=block_q, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, t // block_q, s // block_k),
            in_specs=[
                pl.BlockSpec((1, hi, block_q, d),
                             lambda bi, qi, ki, off: (bi, 0, qi, 0)),
                pl.BlockSpec((1, block_q, hi),
                             lambda bi, qi, ki, off: (bi, qi, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda bi, qi, ki, off: (bi, ki, 0))],
            out_specs=pl.BlockSpec(
                (1, block_q, block_k),
                lambda bi, qi, ki, off: (bi, qi, ki))),
        out_shape=jax.ShapeDtypeStruct((b, t, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dsa_index",
    )(offset, q.transpose(0, 2, 1, 3), w, k)


# --------------------------------------------------------------------------
# Selection
# --------------------------------------------------------------------------

def ordered_keys(scores, valid):
    """float32 ``scores`` -> int32 keys of the same order (a larger
    score, a larger key; -0.0 as 0.0): a float's bits as they are where
    its sign is clear, their low 31 flipped where it is set. An invalid
    entry's key is the least int32, under every valid one's."""
    scores = jnp.where(scores == 0, 0.0, scores)
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    keys = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jnp.where(valid, keys, _LEAST)


def kth_largest_xla(keys, kk):
    """keys [..., S] int32, kk [...] int32 -> [...] int32: the ``kk``-th
    largest key (``kk`` 0: the greatest int32). The threshold is built
    bit by bit from the top in the keys' UNSIGNED order (a key's bits
    with the sign bit flipped): the largest that at least ``kk`` keys
    reach, one counting pass over the keys a bit."""
    def bit(i, t):
        cand = t | jnp.left_shift(jnp.int32(1), 31 - i)
        n = jnp.sum(keys >= (cand ^ _LEAST)[..., None], axis=-1,
                    dtype=jnp.int32)
        return jnp.where(n >= kk, cand, t)

    return jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.int32)) ^ _LEAST


def _kth_kernel(keys_ref, kk_ref, o_ref):
    keys = keys_ref[...]  # [rows, S]
    kk = kk_ref[...].astype(jnp.float32)  # [rows, 1]

    def bit(i, t):
        cand = t | jnp.left_shift(jnp.int32(1), 31 - i)
        # (counted in float32: exact up to 2^24 keys a row)
        n = jnp.sum(jnp.where(keys >= (cand ^ _LEAST), 1.0, 0.0), axis=-1,
                    keepdims=True)
        return jnp.where(n >= kk, cand, t)

    t = jax.lax.fori_loop(0, 32, bit, jnp.zeros(kk.shape, jnp.int32))
    o_ref[...] = jnp.broadcast_to(t ^ _LEAST, o_ref.shape)


def kth_largest(keys, kk, *, use_kernel: bool | None = None,
                interpret: bool = False, rows: int = 8):
    """:func:`kth_largest_xla` of keys [N, S], kk [N]. On a TPU (rows in
    whole blocks of ``rows``, keys in whole lanes) the Pallas kernel
    ``dsa_kth``: a block of rows is read ONCE and its 32 counting passes
    run over it where it lies, where the XLA body reads the keys 32
    times. ``interpret=True`` runs the kernel in the Pallas interpreter
    (never inferred)."""
    n, s = keys.shape
    if use_kernel is None:
        use_kernel = interpret or (
            jax.default_backend() == "tpu" and n % rows == 0
            and s % 128 == 0)
    if not use_kernel:
        return kth_largest_xla(keys, kk)
    if n % rows or s % 128:
        raise ValueError(f"dsa_kth: {n} rows of {s} keys are no whole "
                         f"blocks of {rows} rows and 128 lanes")
    out = pl.pallas_call(
        _kth_kernel,
        grid=(n // rows,),
        in_specs=[pl.BlockSpec((rows, s), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 128), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dsa_kth",
    )(keys, kk[:, None].astype(jnp.int32))
    return out[:, 0]


def select(scores, valid, k: int, *, use_kernel: bool | None = None,
           interpret: bool = False):
    """scores [..., S] float32, valid [..., S] bool -> [..., S] bool: the
    ``min(k, valid entries)`` largest valid scores of each row, a tie to
    the earlier position. Exact: the set a stable descending sort's
    first entries give. ``use_kernel`` / ``interpret`` as
    :func:`kth_largest`'s (the keys padded to whole lanes for it)."""
    lead, s = scores.shape[:-1], scores.shape[-1]
    keys = ordered_keys(scores, valid).reshape(-1, s)
    kk = jnp.minimum(k, jnp.sum(valid, axis=-1, dtype=jnp.int32))
    kk = jnp.broadcast_to(kk, lead).reshape(-1)
    if use_kernel is None:
        use_kernel = interpret or (
            jax.default_backend() == "tpu" and keys.shape[0] % 8 == 0)
    padded = keys
    if use_kernel and s % 128:
        padded = jnp.pad(keys, ((0, 0), (0, -s % 128)),
                         constant_values=_LEAST)
    t = kth_largest(padded, kk, use_kernel=use_kernel,
                    interpret=interpret)[:, None]
    above = keys > t
    equal = (keys == t) & (keys != _LEAST)
    need = kk - jnp.sum(above, axis=-1, dtype=jnp.int32)
    ties = jnp.sum(equal, axis=-1, dtype=jnp.int32)

    def by_position():  # (more keys at the threshold than places left)
        rank = jnp.cumsum(equal.astype(jnp.int32), axis=-1)
        return above | (equal & (rank <= need[:, None]))

    chosen = jax.lax.cond(jnp.any(ties > need), by_position,
                          lambda: above | equal)
    return chosen.reshape(*lead, s)


# --------------------------------------------------------------------------
# Attention over the selected rows
# --------------------------------------------------------------------------

def masked_attention_xla(q_n, q_r, k_n, k_r, v, bias, scale: float):
    """q_n [B, H, T, dn], q_r [B, H, T, dr] over k_n [B, H, S, dn], the
    one rotated key of all heads k_r [B, S, dr] and v [B, H, S, dv]; bias
    [B, T, S] (0 where query t sees key s, ``NEG`` where not) -> [B, H,
    T, dv]: the scores formed whole, float32 softmax."""
    s = (jnp.einsum("bhtd,bhsd->bhts", q_n, k_n,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhtd,bsd->bhts", q_r, k_r,
                      preferred_element_type=jnp.float32)) * scale
    probs = jax.nn.softmax(s + bias[:, None].astype(jnp.float32), axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", probs.astype(q_n.dtype), v,
                      preferred_element_type=jnp.float32).astype(q_n.dtype)


def _last_block(q_start, block_q, block_k, offset, nk):
    return jnp.minimum(
        jax.lax.div(q_start + block_q - 1 + offset, block_k), nk - 1)


def _attn_kernel(offset_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, bias_ref,
                 o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                 block_q: int, block_k: int, nk: int):
    ik = pl.program_id(3)
    heads = qn_ref.shape[1]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    last = _last_block(pl.program_id(2) * block_q, block_q, block_k,
                       offset_ref[0], nk)

    @pl.when(ik <= last)
    def _live():
        bias = bias_ref[0].astype(jnp.float32)  # [bq, bk]
        k_r = kr_ref[0]  # [bk, dr]: the one rotated key of all heads
        for h in range(heads):
            s = (jax.lax.dot_general(
                qn_ref[0, h], kn_ref[0, h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) + jax.lax.dot_general(
                qr_ref[0, h], k_r, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)) \
                * (scale * _LOG2E) + bias
            m_prev = m_scr[h, :, :1]
            # (floored far above NEG: a row that has seen nothing yet
            # gives exp2(NEG - floor) = 0, not exp2(0))
            m_new = jnp.maximum(jnp.maximum(
                m_prev, jnp.max(s, axis=-1, keepdims=True)), _FLOOR)
            p = jnp.exp2(s - m_new)
            corr = jnp.exp2(m_prev - m_new)
            l_new = corr * l_scr[h, :, :1] + jnp.sum(p, axis=-1,
                                                     keepdims=True)
            v = v_ref[0, h]
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(ik == nk - 1)
    def _store():
        for h in range(heads):
            l = l_scr[h, :, :1]
            o_ref[0, h] = (acc_scr[h] / jnp.where(l == 0.0, 1.0, l)).astype(
                o_ref.dtype)


def masked_attention(q_n, q_r, k_n, k_r, v, bias, offset, *, scale: float,
                     use_kernel: bool | None = None,
                     interpret: bool = False, block_q: int = 512,
                     block_k: int = 512, heads: int = 4):
    """A segment's attention over the rows so far with the unchosen
    masked: q_n [B, H, T, dn], q_r [B, H, T, dr] at positions ``offset``
    .. (traced or not) over k_n [B, H, S, dn], the one rotated key of all
    heads k_r [B, S, dr] (never repeated a head: the scores are two
    products) and v [B, H, S, dv] of positions 0 .. S - 1, bias [B, T,
    S] (0 or ``NEG``; everything past ``t + offset`` must be ``NEG``) ->
    [B, H, T, dv]. A row with no key seen gives zeros.

    ``use_kernel=None``: the Pallas kernel (``dsa_attn``) on a TPU where
    rows and keys are whole blocks, the XLA body elsewhere;
    ``interpret=True`` runs the kernel in the Pallas interpreter."""
    b, h, t, dn = q_n.shape
    dr = q_r.shape[3]
    s, dv = v.shape[2:]
    block_q, block_k = min(block_q, t), min(block_k, s)
    while h % heads:
        heads //= 2
    if use_kernel is None:
        use_kernel = interpret or (
            jax.default_backend() == "tpu" and t % block_q == 0
            and s % block_k == 0)
    if not use_kernel:
        return masked_attention_xla(q_n, q_r, k_n, k_r, v, bias, scale)
    if t % block_q or s % block_k:
        raise ValueError(f"dsa_attn: T={t} / S={s} must be multiples of "
                         f"the blocks ({block_q}, {block_k})")
    nk = s // block_k
    offset = jnp.asarray(offset, jnp.int32).reshape(1)

    def q_idx(bi, hi, qi, ki, off):
        return bi, hi, qi, 0

    def kv_idx(bi, hi, qi, ki, off):
        # (a dead step asks for the block that is there already)
        return bi, hi, jnp.minimum(ki, _last_block(
            qi * block_q, block_q, block_k, off[0], nk)), 0

    def bias_idx(bi, hi, qi, ki, off):
        return bi, qi, jnp.minimum(ki, _last_block(
            qi * block_q, block_q, block_k, off[0], nk))

    def kr_idx(bi, hi, qi, ki, off):
        return bi, jnp.minimum(ki, _last_block(
            qi * block_q, block_q, block_k, off[0], nk)), 0

    return pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // heads, t // block_q, nk),
            in_specs=[pl.BlockSpec((1, heads, block_q, dn), q_idx),
                      pl.BlockSpec((1, heads, block_q, dr), q_idx),
                      pl.BlockSpec((1, heads, block_k, dn), kv_idx),
                      pl.BlockSpec((1, block_k, dr), kr_idx),
                      pl.BlockSpec((1, heads, block_k, dv), kv_idx),
                      pl.BlockSpec((1, block_q, block_k), bias_idx)],
            out_specs=pl.BlockSpec((1, heads, block_q, dv), q_idx),
            scratch_shapes=[
                pltpu.VMEM((heads, block_q, 128), jnp.float32),  # max
                pltpu.VMEM((heads, block_q, 128), jnp.float32),  # sum
                pltpu.VMEM((heads, block_q, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, t, dv), q_n.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dsa_attn",
    )(offset, q_n, q_r, k_n, k_r, v, bias)


# --------------------------------------------------------------------------
# A decode step over a slot's latent rows, the unchosen masked
# --------------------------------------------------------------------------
#
# A decode step read its chosen rows by a gather first (``gather_rows``
# into ``decode_attention.attend_latent``): 2 x 32 x 2,048 rows of
# 1,280 B a step, 6.5 ms a layer-step on the chip, 14 GB/s (my chip run,
# PR 58): XLA moves the rows one by one. So the step reads every LIVE
# row of a slot in blocks, as ``decode_attn_latent`` does, and masks the
# unchosen: ``decode_attention._latent_kernel`` with a bias a key.

def attend_latent_masked(q, rows, lengths, bias, dv: int, scale: float):
    """The XLA body: q [B, H, W] over ONE layer's rows [B, S, W], slot b
    seeing rows < ``lengths[b]`` whose ``bias`` [B, S] is 0 (``NEG``:
    not chosen); a row's value is its first ``dv`` numbers. -> [B, H,
    dv] in q's dtype (a slot of length 0: zeros)."""
    logits = jnp.einsum("bhw,bsw->bhs", q, rows,
                        preferred_element_type=jnp.float32) * scale
    live = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :] \
        < lengths[:, None]
    logits = jnp.where(
        live[:, None], logits + bias.astype(jnp.float32)[:, None], NEG)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhs,bsv->bhv", probs.astype(q.dtype), rows[..., :dv],
                   preferred_element_type=jnp.float32).astype(q.dtype)
    return jnp.where((lengths > 0)[:, None, None], o, 0)


def _decode_kernel(slot_ids, block_ids, lengths, layer_ref, q_ref, rows_ref,
                   bias_ref, o_ref, m_scr, l_scr, acc_scr, *, s: int,
                   bs: int, dv: int, scale: float):
    step = pl.program_id(0)
    slot, j = slot_ids[step], block_ids[step]
    length = lengths[slot]
    last = (jnp.minimum(length, s) + bs - 1) // bs - 1

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    heads = q_ref.shape[0]
    k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (heads, bs), 1)
    seen = k_pos < jnp.minimum(length, s)
    logits = jax.lax.dot_general(
        q_ref[...], rows_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [heads, bs]
    logits = jnp.where(seen, logits + bias_ref[...].astype(jnp.float32),
                       NEG)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    # (a block may hold no chosen row: exp(0) is not its probability)
    p = jnp.where(m_new > NEG * 0.5, jnp.exp(logits - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    v = rows_ref[:, :dv]
    if s % bs:  # what lies past the cache is not zero, nor finite
        inside = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (bs, dv), 0) < s
        v = jnp.where(inside, v, jnp.zeros_like(v))
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == last)
    def _store():
        l = l_scr[:, :1]
        o_ref[...] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def decode_attention_masked(q, rows, layer, lengths, bias, *, dv: int,
                            scale: float, plan=None, block: int,
                            use_kernel: bool | None = None,
                            interpret: bool = False):
    """A decode step's attention over the CHOSEN latent rows: q [B, H, W]
    over ``rows[layer]`` of the stack [L, B, S, W] up to ``lengths`` [B]
    (0: the slot is inactive and its output zeros), of which a slot sees
    the rows whose ``bias`` [B, S] (bfloat16) is 0 and not those at
    ``NEG`` -> [B, H, dv] in q's dtype.

    On a TPU (a row and its value whole lanes) the Pallas kernel
    ``dsa_decode_attn``: ``decode_attn_latent``'s visits of the (slot,
    block) pairs that hold a row (``plan`` = ``decode_attention.visits(
    lengths, S, block)``), a block's bias beside its rows; elsewhere
    :func:`attend_latent_masked`. ``interpret=True`` runs the kernel in
    the Pallas interpreter (never inferred)."""
    from ray_tpu.ops import decode_attention as _da

    b, h, w = q.shape
    s = rows.shape[2]
    lengths = lengths.astype(jnp.int32)
    if use_kernel is None:
        use_kernel = interpret or (
            jax.default_backend() == "tpu" and w % 128 == 0
            and dv % 128 == 0 and h % 8 == 0)
    if not use_kernel:
        return attend_latent_masked(q, rows[layer], lengths, bias, dv, scale)
    meta, steps = plan or _da.visits(lengths, s, block)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_index(step, slot_ids, block_ids, lengths, layer_ref):
        return slot_ids[step], 0, 0

    def rows_index(step, slot_ids, block_ids, lengths, layer_ref):
        return layer_ref[0], slot_ids[step], block_ids[step], 0

    def bias_index(step, slot_ids, block_ids, lengths, layer_ref):
        return slot_ids[step], 0, block_ids[step]

    out = pl.pallas_call(
        functools.partial(_decode_kernel, s=s, bs=block, dv=dv,
                          scale=scale),
        out_shape=jax.ShapeDtypeStruct((b, h, dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((None, h, w), q_index),
                      pl.BlockSpec((None, None, block, w), rows_index),
                      pl.BlockSpec((None, 1, block), bias_index)],
            out_specs=pl.BlockSpec((None, h, dv), q_index),
            grid=(steps,),
            scratch_shapes=[
                pltpu.VMEM((h, 128), jnp.float32),  # max
                pltpu.VMEM((h, 128), jnp.float32),  # sum
                pltpu.VMEM((h, dv), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dsa_decode_attn",
    )(*meta, lengths, layer, q, rows, bias[:, None, :])
    # a slot without a row was never visited: what its block of the
    # output holds is whatever the buffer held
    return jnp.where((lengths > 0)[:, None, None], out, 0)
