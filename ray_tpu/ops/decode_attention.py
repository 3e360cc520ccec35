"""Decode attention over the stacked ragged cache: each slot's few query
rows against that slot's k and v rows **up to its own length**.

``q [B, T, Hq, hd]`` (T == 1: a decode step; T == K + 1: the speculative
verify) attends over ``k[layer]``, ``v[layer]`` of the engine's stack
``[L, B, S, Hkv * hd]`` (``models/decode_engine.py``: a row of the cache
is a position's kv heads laid end to end). ``lengths [B]`` says how many
rows of a slot hold something, the T rows just written included
(``pos + T``); 0 is an inactive slot. Query row t of slot b sees
``k_pos < lengths[b] - (T - 1 - t)``, which is ``k_pos <= pos[b] + t``.

On a TPU it is a Pallas kernel, shown in a device trace as
``decode_attn``. Its grid **visits only the (slot, row block) pairs that
hold a row** (``visits``, ``grouped_matmul.group_metadata``'s way): a
slot half full costs half its rows' bytes, an inactive slot nothing (its
output is zeros). The blocks are taken from the stack in place, by a
scalar-prefetched ``layer`` in the block spec's index map: slicing
``stack[layer]`` first would read the whole layer, filled or not. A
visit holds every kv head of its rows; the query heads of a kv head
(head h = kv * group + r) contract against that head's lanes of the
block, so no head is repeated. The arithmetic is the XLA body's
(``attend_ragged``, the path off the TPU and the tests' second opinion):
products of the compute dtype accumulated in float32, float32 softmax
statistics (a running max and sum across a slot's blocks), the
probabilities cast to the compute dtype before the values' product.
``interpret=True`` (a test's explicit choice) runs the kernel in the
Pallas interpreter.

The layout and the block's rows, read on the chip (TPU v5 lite, my chip
runs, PR 33; the kernel's own event in a trace, median of 200 calls;
bf16, hd 128; 8 slots of 1,296 rows at lengths 268...1,108, mean 683):
  - the layout is the kernel's. With the kv heads in the lanes
    (``[S, Hkv * hd]``) a head's ``[rows, hd]`` is a lane-aligned view
    of the block; as ``[S, Hkv, hd]`` it is one sublane of every row's
    tile, and the same kernel took twice as long (a layer-step of the
    probe's loop, row write included: 54.3 against 107.8 us at 8 kv
    heads, 110.2 against 218.7 at 16). The row write into the lanes'
    layout costs what it did (1.6 us a stack for 8 rows);
  - rows a block, 16 query / 8 kv heads: 336 -> 53.0 us, 432 -> 46.0,
    656 -> 45.9, 1,296 (every row, as the XLA body reads) -> 60.5;
    16 / 16 heads: 432 -> 84.5, 656 -> 90.8. The live rows' bytes at
    the HBM's peak are 27.3 and 54.7 us: 59% and 65% of the roofline.
    32 slots of 512 rows, 3 live (108...208): 512 -> 12.0, 256 -> 9.7;
  - what bounds it: the products, not the reads. With every step on one
    block (no new DMA) the 8-head call takes 46.3 us, with the blocks
    fetched and not multiplied 38.2: a visit's 2 x Hkv products of 16
    query rows against ``rows`` x 128 pass the block through the
    matrix unit at about 1.2 times the time its DMA takes. A call
    costs about 5 us before its first block is there.
So: the fewest equal blocks of at most ``_BLOCK_ROWS`` rows
(``block_rows``): 3 x 432 for 1,296 rows, one block for 512.

**Keys and values of different widths, a head that is not whole lanes,
a sink (PR 50).** k and v are two stacks and their rows need not be as
wide: ``q [B, T, Hq, dk]`` over ``k [L, B, S, Hkv * dk]`` and ``v [L,
B, S, Hkv * dv]`` gives ``[B, T, Hq, dv]``. A key head of 192 is one
and a half lane tiles; heads laid end to end would start every second
one in the middle of a tile. A row of such keys is stored **packed**
(:func:`pack_heads`): every head's whole lanes first (``main`` = 128 of
its 192), end to end, then every head's remainder (64), end to end, so
that two remainders share a tile and nothing is padded: the row is
``Hkv * dk`` numbers, as unpacked. The kernel contracts a head's main
part against its own lanes and its remainder, laid into a zeroed tile
at its place, against the tile that holds it: two aligned products,
the one and a half tiles a 192-wide contraction takes anyway. With
``sink`` [Hq] float32 a learned logit a query head joins the softmax's
denominator and takes no value: the running max starts at the sink and
the running sum at 1 (``exp(sink - sink)``), nothing else changes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_BLOCK_ROWS = 512
_VMEM_LIMIT = 64 * 1024 * 1024
_ROW_PAD = 16  # query rows a kv head, padded to a bf16 tile's sublanes


def _cdiv(a, b):
    return -(-a // b)


def block_rows(s: int, most: int = _BLOCK_ROWS) -> int:
    """Rows a block for a cache of ``s`` rows: the fewest equal blocks
    of at most ``most``, a multiple of 16 (1,296 -> 3 x 432, 512
    -> 1 x 512), so that the last block ends with the cache wherever
    ``s`` allows it."""
    n = _cdiv(s, most)
    return _cdiv(_cdiv(s, n), 16) * 16


def _split(hd: int) -> tuple:
    """(main, rem): a head's whole lanes and what is left of it (a
    head under one tile is taken whole: nothing to pack)."""
    return (hd // 128 * 128, hd % 128) if hd > 128 else (hd, 0)


def pack_heads(x):
    """[..., H, hd] -> [..., H * hd], a row as the kernel reads it: the
    heads end to end where a head is whole lanes, else every head's
    whole lanes end to end and then every head's remainder (module
    docstring)."""
    *lead, h, hd = x.shape
    main, rem = _split(hd)
    if not rem:
        return x.reshape(*lead, h * hd)
    return jnp.concatenate([x[..., :main].reshape(*lead, h * main),
                            x[..., main:].reshape(*lead, h * rem)], axis=-1)


def unpack_heads(rows, h: int):
    """:func:`pack_heads` undone: [..., H * hd] -> [..., H, hd]."""
    *lead, width = rows.shape
    hd = width // h
    main, rem = _split(hd)
    if not rem:
        return rows.reshape(*lead, h, hd)
    return jnp.concatenate(
        [rows[..., :h * main].reshape(*lead, h, main),
         rows[..., h * main:].reshape(*lead, h, rem)], axis=-1)


def attend_ragged(q, ck, cv, qpos, sink=None):
    """The XLA body: attention of T query rows a slot over ONE layer of
    the cache, every row of it. q: [B, T, Hq, hd]; ck/cv:
    [B, S, Hkv, hd] (cv's heads may be of another width); qpos: [B, T],
    the position of each query row; row t
    of slot b sees k_pos <= qpos[b, t]. The query heads are grouped by
    the kv head they share (head h = kv * group + r, the order a repeat
    of the kv heads would give) and each group contracts against its one
    kv head: no repeated copy of the cache is made, and the cache is
    read once in its own dtype. Products accumulate in float32, the
    softmax is float32 (with ``sink`` [Hq] a head's logit joins its
    denominator), the probabilities are cast to q's dtype. Returns
    [B, T, Hq, cv's head width]."""
    b, t, hq, hd = q.shape
    s, hkv = ck.shape[1:3]
    qg = q.reshape(b, t, hkv, hq // hkv, hd)
    logits = jnp.einsum(
        "btkgd,bskd->bkgts", qg, ck, preferred_element_type=jnp.float32
    ) * (hd ** -0.5)
    k_pos = jnp.arange(s, dtype=jnp.int32)[None, None, :]  # [1, 1, S]
    live = k_pos <= qpos[:, :, None]  # [B, T, S]
    logits = jnp.where(live[:, None, None], logits, _NEG)
    if sink is None:
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    else:
        from ray_tpu.ops.attention import softmax_with_sink

        probs = softmax_with_sink(logits, sink.astype(jnp.float32).reshape(
            hkv, hq // hkv)[None, :, :, None, None]).astype(q.dtype)
    o = jnp.einsum(
        "bkgts,bskd->btkgd", probs, cv, preferred_element_type=jnp.float32
    ).astype(q.dtype)
    return o.reshape(b, t, hq, cv.shape[-1])


def visits(lengths, s: int, bs: int | None = None):
    """Which slot and which of its row blocks each grid step works on,
    for blocks of ``bs`` rows (``block_rows(s)`` unless given).

    -> ((slot_ids [G], block_ids [G]), num_steps) with G = B x blocks of
    ``s`` rows, of which the first ``num_steps`` are real: a slot's
    blocks below its length, consecutively; a slot of length 0 has none.
    At least one step (block 0 of an empty slot, which stores nothing)."""
    bs = bs or block_rows(s)
    b = lengths.shape[0]
    slots = b * _cdiv(s, bs)
    blocks = _cdiv(jnp.clip(lengths, 0, s), bs).astype(jnp.int32)
    ends = jnp.cumsum(blocks)
    slot_ids = jnp.repeat(jnp.arange(b, dtype=jnp.int32), blocks,
                          total_repeat_length=slots)
    block_ids = jnp.arange(slots, dtype=jnp.int32) - (ends - blocks)[slot_ids]
    return (slot_ids, block_ids), jnp.maximum(ends[-1], 1)


def _kernel(slot_ids, block_ids, lengths, layer_ref, q_ref, k_ref, v_ref,
            *rest, s: int, bs: int, t: int, group: int, hkv: int, hd: int,
            dv: int, scale: float):
    # (with a sink its rows [Hkv, _ROW_PAD, 128] come before the output)
    *sink_ref, o_ref, m_scr, l_scr, acc_scr = rest
    main, rem = _split(hd)
    step = pl.program_id(0)
    slot, j = slot_ids[step], block_ids[step]
    length = lengths[slot]
    last = (jnp.minimum(length, s) + bs - 1) // bs - 1

    @pl.when(j == 0)
    def _init():
        if sink_ref:  # exp(sink - sink) = 1 is in the sum already
            m_scr[...] = sink_ref[0][...]
            l_scr[...] = jnp.ones_like(l_scr)
        else:
            m_scr[...] = jnp.full_like(m_scr, _NEG)
            l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # row r of a head's [_ROW_PAD, hd] is query row t = r // group (the
    # padding rows take the last limit: finite, discarded)
    r = jax.lax.broadcasted_iota(jnp.int32, (_ROW_PAD, bs), 0)
    row_t = sum((r >= i * group).astype(jnp.int32) for i in range(1, t))
    k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (_ROW_PAD, bs), 1)
    seen = k_pos < length - (t - 1) + row_t
    if s % bs:  # the cache ends inside the last block
        seen &= k_pos < s
        inside = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (bs, dv), 0) < s
    for h in range(hkv):
        if not rem:
            logits = jax.lax.dot_general(
                q_ref[h], k_ref[:, pl.ds(h * hd, hd)],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [_ROW_PAD, bs]
        else:  # packed: the head's whole lanes, then its remainder's tile
            tile = (hkv * main + h * rem) // 128 * 128
            logits = (jax.lax.dot_general(
                q_ref[h, :, :main], k_ref[:, pl.ds(h * main, main)],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) + jax.lax.dot_general(
                q_ref[h, :, main:], k_ref[:, pl.ds(tile, 128)],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)) * scale
        logits = jnp.where(seen, logits, _NEG)
        m_prev = m_scr[h, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_scr[h, :, :1] + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[:, pl.ds(h * dv, dv)]
        if s % bs:  # what lies past the cache is not zero, nor finite
            v = jnp.where(inside, v, jnp.zeros_like(v))
        acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
        l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(j == last)
    def _store():
        for h in range(hkv):
            o_ref[h] = (acc_scr[h] / l_scr[h, :, :1]).astype(o_ref.dtype)


def _decode_attn(q, k, v, layer, lengths, plan, *, bs: int,
                 interpret: bool, sink=None, scale: float | None = None):
    """The kernel's call: q [B, T, Hq, hd] -> [B, T, Hq, dv]; ``plan``
    is ``visits(lengths, s, bs)``; ``scale``: where not ``hd ** -0.5``."""
    b, t, hq, hd = q.shape
    s = k.shape[2]
    hkv = k.shape[3] // hd
    dv = v.shape[3] // hkv
    group = hq // hkv
    rows = t * group
    if rows > _ROW_PAD:
        raise ValueError(
            f"{t} query rows x {group} heads a kv head exceed {_ROW_PAD}")
    main, rem = _split(hd)
    if rem:
        # q as an array of its own before it is laid out for the kernel:
        # fused into the rotation that made q, the lay-out below gave
        # the rings' calls wrong rows on the chip (outputs off by 0.2 of
        # 0.5 inside the model's step, right on a q that was an argument;
        # my chip runs, PR 50, PERF.md section 7)
        q = jax.lax.optimization_barrier(q)
    # [B, Hkv, T x group (padded), hd]: a kv head's query rows together
    qh = q.reshape(b, t, hkv, group, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, rows, hd)
    qh = jnp.pad(qh, ((0, 0), (0, 0), (0, _ROW_PAD - rows), (0, 0)))
    if rem:  # a head's remainder at its place in a zeroed tile of its own
        # (static pads: as a gather this took 8 ms a call on the chip)
        place = [(hkv * main + h * rem) % 128 for h in range(hkv)]
        tail = jnp.stack([jnp.pad(
            qh[:, h, :, main:], ((0, 0), (0, 0), (at, 128 - rem - at)))
            for h, at in enumerate(place)], axis=1)
        qh = jnp.concatenate([qh[..., :main], tail], axis=-1)
    width = qh.shape[-1]
    meta, steps = plan
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_index(step, slot_ids, block_ids, lengths, layer_ref):
        return slot_ids[step], 0, 0, 0

    def kv_index(step, slot_ids, block_ids, lengths, layer_ref):
        return layer_ref[0], slot_ids[step], block_ids[step], 0

    kernel = functools.partial(
        _kernel, s=s, bs=bs, t=t, group=group, hkv=hkv, hd=hd, dv=dv,
        scale=scale or hd ** -0.5)
    q_block = pl.BlockSpec((None, hkv, _ROW_PAD, width), q_index)
    operands, in_specs = [qh, k, v], [
        q_block, pl.BlockSpec((None, None, bs, hkv * hd), kv_index),
        pl.BlockSpec((None, None, bs, hkv * dv), kv_index)]
    if sink is not None:  # row r of kv head h is query head h * group + r % group
        by_row = jnp.tile(sink.astype(jnp.float32).reshape(hkv, group),
                          (1, t))
        by_row = jnp.pad(by_row, ((0, 0), (0, _ROW_PAD - rows)))
        operands.append(jnp.broadcast_to(by_row[..., None],
                                         (hkv, _ROW_PAD, 128)))
        in_specs.append(pl.BlockSpec(
            (hkv, _ROW_PAD, 128), lambda step, *_: (0, 0, 0)))
    itemsize = k.dtype.itemsize
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hkv, _ROW_PAD, dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, hkv, _ROW_PAD, dv), q_index),
            grid=(steps,),
            scratch_shapes=[
                pltpu.VMEM((hkv, _ROW_PAD, 128), jnp.float32),  # max
                pltpu.VMEM((hkv, _ROW_PAD, 128), jnp.float32),  # sum
                pltpu.VMEM((hkv, _ROW_PAD, dv), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * _ROW_PAD * s * hkv * (hd + dv),
            transcendentals=b * _ROW_PAD * s * hkv,
            bytes_accessed=b * s * hkv * (hd + dv) * itemsize),
        interpret=interpret,
        name="decode_attn",
    )(*meta, lengths, layer, *operands)
    # a slot without a row was never visited: what its block of the
    # output holds is whatever the buffer held
    out = jnp.where((lengths > 0)[:, None, None, None], out[:, :, :rows], 0)
    return out.reshape(b, hkv, t, group, dv).transpose(
        0, 2, 1, 3, 4).reshape(b, t, hq, dv)


def decode_attention(q, k, v, layer, lengths, *, plan=None,
                     use_kernel: bool | None = None,
                     interpret: bool = False, rows: int | None = None,
                     sink=None):
    """q [B, T, Hq, hd] over ``k[layer]``, ``v[layer]`` of the stacks
    [L, B, S, Hkv * hd] and [L, B, S, Hkv * dv] (rows as
    :func:`pack_heads` lays them) up to ``lengths`` [B] (``pos + T``; 0:
    the slot is inactive and its output zeros) -> [B, T, Hq, dv] in q's
    dtype. ``sink`` [Hq] float32: a logit a query head that joins its
    softmax's denominator and takes no value.

    ``use_kernel=None`` takes the backend's: the Pallas kernel on a TPU
    (where a value head fills whole lanes and a key head's remainder
    divides a tile, or heads share a tile), ``attend_ragged`` elsewhere, which
    reads every row of the layer and leaves an inactive slot's output to
    its frozen position. ``interpret=True`` runs the kernel in the
    Pallas interpreter (never inferred). ``rows`` overrides the block's
    rows (the chip's tuning sweep and the tests). ``plan`` is
    ``visits(lengths, S, rows)`` where the caller made
    it already: a layer loop makes it once a step, before the loop (as
    written in the loop's body XLA leaves its dozen small operations
    there, every layer)."""
    b, t, hq, hd = q.shape
    s = k.shape[2]
    hkv = k.shape[3] // hd
    if use_kernel is None:
        rem, per = hd % 128, _heads_a_tile(hd, v.shape[3] // hkv, hkv)
        use_kernel = interpret or (
            jax.default_backend() == "tpu"
            and (per > 1 or (v.shape[3] // hkv) % 128 == 0)
            and (per > 1 or not rem or (hd > 128 and 128 % rem == 0
                                        and hkv * rem % 128 == 0))
            and t * (hq // hkv) * per <= _ROW_PAD)
    if not use_kernel:
        qpos = (lengths - t)[:, None] + jnp.arange(t, dtype=jnp.int32)
        o = attend_ragged(q, unpack_heads(k[layer], hkv),
                          unpack_heads(v[layer], hkv), qpos, sink)
        return jnp.where((lengths > 0)[:, None, None, None], o, 0)
    bs = rows or block_rows(s)
    lengths = lengths.astype(jnp.int32)
    return _kernel_call(hd, k, v)(q, k, v, layer, lengths,
                        plan or visits(lengths, s, bs), bs=bs,
                        interpret=interpret, sink=sink)  # (columns: below)


# --------------------------------------------------------------------------
# Latent rows (MLA's absorbed step): a row is key AND value
# --------------------------------------------------------------------------
#
# A latent row is [the normalised latent (``dv``) ‖ the one rotated key
# ‖ zeros to whole lanes]: a STACK of 640 (Instella-MoE, Ling, dots3's
# full layers, whose masked read is ``ops/dsa.py``'s) or a RING of 1,152
# (dots3's window layers: ``attend_latent``). A head contracts ``[q in
# the latent's space ‖ q_rope ‖ 0]`` against the whole row; the first
# ``dv`` numbers are the value: a block passes the matrix unit twice.
#
# The layout and the block's rows, read before they were fixed (PR 39).
# Layout (compile for a described v5e): a rotated key of 32 kept apart
# as ``[L, slots, rows, 32]`` bf16 is tiled to 128 lanes in HBM and takes
# 256 B a row, the same as the 96 lanes of zeros that fill latent ‖ key
# up to 640; one array is one DMA a block where two would be two. Rows a
# block (TPU v5 lite, my chip runs, PR 39; one call over 32 slots of
# 16,912 rows x 640 bf16 at the longdoc cell's lengths, prompts of 4,096 to 16,384
# and some output, one inactive: 285,458 live rows = 365 MB as stored, 446 us at the
# HBM's peak; mean of 600 calls in a loop): 512 -> 580 us, 1,024 ->
# 526 (85%), 2,048 -> 551, 4,096 -> 614 (a slot's last block is read
# whole: half a block a slot is waste). The XLA body over every row:
# 4,088 us. With ``max_len`` doubled to 33,824 and the same lengths: the
# kernel 528 us, the XLA body 8,087.

LATENT_BLOCK_ROWS = 1024


def attend_latent(q, rows, lengths, dv: int, scale: float):
    """The XLA body: q [B, H, W] over ONE layer's rows [B, S, W], every
    row of it, slot b seeing rows < ``lengths[b]``; the value of a row
    is its first ``dv`` numbers. Products accumulate in float32, the
    softmax is float32, the probabilities are cast to q's dtype.
    -> [B, H, dv] in q's dtype (a slot of length 0: zeros)."""
    logits = jnp.einsum("bhw,bsw->bhs", q, rows,
                        preferred_element_type=jnp.float32) * scale
    live = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :] \
        < lengths[:, None]
    probs = jax.nn.softmax(jnp.where(live[:, None], logits, _NEG), axis=-1)
    o = jnp.einsum("bhs,bsv->bhv", probs.astype(q.dtype), rows[..., :dv],
                   preferred_element_type=jnp.float32).astype(q.dtype)
    return jnp.where((lengths > 0)[:, None, None], o, 0)


def _latent_kernel(slot_ids, block_ids, lengths, layer_ref, q_ref, rows_ref,
                   o_ref, m_scr, l_scr, acc_scr, *, s: int, bs: int,
                   dv: int, scale: float):
    step = pl.program_id(0)
    slot, j = slot_ids[step], block_ids[step]
    length = lengths[slot]
    last = (jnp.minimum(length, s) + bs - 1) // bs - 1

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    heads = q_ref.shape[0]
    k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (heads, bs), 1)
    seen = k_pos < jnp.minimum(length, s)
    logits = jax.lax.dot_general(
        q_ref[...], rows_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [heads, bs]
    logits = jnp.where(seen, logits, _NEG)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
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
        o_ref[...] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


def _decode_attn_latent(q, rows, layer, lengths, plan, *, dv: int,
                        scale: float, bs: int, interpret: bool):
    """The kernel's call: q [B, H, W] -> [B, H, dv]; ``plan`` is
    ``visits(lengths, s, bs)``."""
    b, h, w = q.shape
    s = rows.shape[2]
    meta, steps = plan
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_index(step, slot_ids, block_ids, lengths, layer_ref):
        return slot_ids[step], 0, 0

    def rows_index(step, slot_ids, block_ids, lengths, layer_ref):
        return layer_ref[0], slot_ids[step], block_ids[step], 0

    out = pl.pallas_call(
        functools.partial(_latent_kernel, s=s, bs=bs, dv=dv, scale=scale),
        out_shape=jax.ShapeDtypeStruct((b, h, dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((None, h, w), q_index),
                      pl.BlockSpec((None, None, bs, w), rows_index)],
            out_specs=pl.BlockSpec((None, h, dv), q_index),
            grid=(steps,),
            scratch_shapes=[
                pltpu.VMEM((h, 128), jnp.float32),  # max
                pltpu.VMEM((h, 128), jnp.float32),  # sum
                pltpu.VMEM((h, dv), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * s * (w + dv),
            transcendentals=b * h * s,
            bytes_accessed=b * s * w * rows.dtype.itemsize),
        interpret=interpret,
        name="decode_attn_latent",
    )(*meta, lengths, layer, q, rows)
    # a slot without a row was never visited: what its block of the
    # output holds is whatever the buffer held
    return jnp.where((lengths > 0)[:, None, None], out, 0)


def decode_attention_latent(q, rows, layer, lengths, *, dv: int,
                            scale: float, plan=None,
                            use_kernel: bool | None = None,
                            interpret: bool = False,
                            block: int | None = None):
    """A decode step's attention over latent rows: q [B, H, W] (a head's
    query carried into the row's space) over ``rows[layer]`` of the stack
    [L, B, S, W] up to ``lengths`` [B] (``pos + 1``; 0: the slot is
    inactive and its output zeros), logits times ``scale``, a row's
    value its first ``dv`` numbers -> [B, H, dv] in q's dtype.

    ``use_kernel=None`` takes the backend's: the Pallas kernel
    (``decode_attn_latent`` in a trace) on a TPU where a row and its
    value are whole lanes and the heads at most a tile's sublanes,
    :func:`attend_latent` elsewhere. ``interpret=True`` runs the kernel
    in the Pallas interpreter (never inferred); ``block`` overrides the
    block's rows; ``plan`` is ``visits(lengths, S, block)`` where the
    caller made it already (once a step, before its layers)."""
    b, h, w = q.shape
    s = rows.shape[2]
    if use_kernel is None:
        use_kernel = interpret or (
            jax.default_backend() == "tpu" and w % 128 == 0
            and dv % 128 == 0 and h <= _ROW_PAD)
    lengths = lengths.astype(jnp.int32)
    if not use_kernel:
        return attend_latent(q, rows[layer], lengths, dv, scale)
    bs = block or block_rows(s, LATENT_BLOCK_ROWS)
    return _decode_attn_latent(
        q, rows, layer, lengths, plan or visits(lengths, s, bs), dv=dv,
        scale=scale, bs=bs, interpret=interpret)


# --------------------------------------------------------------------------
# A head that is a part of a lane tile (PR 68)
# --------------------------------------------------------------------------
#
# (At the END of the file, and what stands above it changed only inside
# its lines: the Mosaic modules of both kernels carry the lines AND
# columns they were traced from, their callers' among them, so a line
# added above one, or the kernel's call in ``decode_attention`` indented
# anew, moves the cache key of every program that holds it.)
#
# A 64-wide head is half a tile and a row of 8 such kv heads four tiles
# with two heads each (``128 // hd`` heads a tile). ``_kernel``'s body is
# not told: the call hands it each TILE as one kv head of 128 whose query
# rows are those of every head in it, a head's rows laid into a zeroed
# tile at the head's own lanes (the form a 192-wide head's remainder has
# in the module docstring, for the whole head). A row's scores against
# the tile are then its own head's, since the other heads' lanes meet
# zeros; its row of ``p v`` is the tile's 128 lanes, of which the head's
# own ``hd`` are taken once the kernel is done. So the keys and values
# pass the matrix unit once a tile, not once a head, and a step's 4 query
# rows a head are 8 of a tile's 16. Keys and values are as wide as each
# other there, and ``T x group x heads a tile`` is at most ``_ROW_PAD``.


def _heads_a_tile(hd: int, dv: int, hkv: int) -> int:
    """How many kv heads share a lane tile where the call lays them so:
    ``128 // hd`` for a head that divides a tile, keys and values alike,
    in a row of whole tiles; else 1."""
    per = 128 // hd if hd < 128 and 128 % hd == 0 and dv == hd else 1
    return per if hkv % per == 0 else 1


def _kernel_call(hd: int, k, v):
    """:func:`_decode_attn` for a head of ``hd`` over rows ``k``, ``v``,
    or the call that hands it a tile of heads as one."""
    hkv = k.shape[3] // hd
    per = _heads_a_tile(hd, v.shape[3] // hkv, hkv)
    return _decode_attn if per == 1 else functools.partial(_tiles_of_heads,
                                                           per)


def _tiles_of_heads(per: int, q, k, v, layer, lengths, plan, **kw):
    """:func:`_decode_attn` with each tile of ``per`` kv heads as ONE
    head of 128 (the comment above)."""
    b, t, hq, hd = q.shape
    hkv = k.shape[3] // hd
    lanes = jnp.eye(per, dtype=q.dtype)[:, None, :, None]
    tiles = q.reshape(b, t, hkv // per, per, hq // hkv, 1, hd) * lanes
    out = _decode_attn(tiles.reshape(b, t, hq, 128), k, v, layer, lengths,
                       plan, scale=hd ** -0.5, **kw)
    out = out.reshape(b, t, hkv // per, per, hq // hkv, per, hd)
    return jnp.stack([out[:, :, :, i, :, i] for i in range(per)],
                     axis=3).reshape(b, t, hq, hd)
