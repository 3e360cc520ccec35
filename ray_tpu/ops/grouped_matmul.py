"""Grouped matrix multiplication: ``[M, K] x [E, K, N]`` by ``group_sizes``.

Row block ``i`` of ``lhs`` (``group_sizes[i]`` consecutive rows, the rows
of all groups laid end to end) is multiplied by ``rhs[i]``. It is the
expert half of the dropless mixture-of-experts layer
(``models/llama.py: _moe_mlp_dropless``): the (token, expert) assignments
sorted by expert are the rows, the experts' matrices the groups.

On a TPU it is a Pallas kernel, shown in a device trace as ``moe_gmm``.
Its grid **visits only the (row tile, group) pairs that hold a row**, so
an expert that nobody was routed to is never read: in a decode step 64
assignment rows touch about 42 of 64 experts, and the step is bound by
the bytes of the experts it reads. Off the TPU the same function is
``jax.lax.ragged_dot``; ``interpret=True`` (a test's explicit choice)
runs the kernel in the Pallas interpreter.

Adapted from ``jax.experimental.pallas.ops.tpu.megablox`` (jax 0.9.0):
the group metadata (which group and which row tile a grid step works
on), the masked store of a tile that several groups share, and the
transposed product ``tgmm`` for the backward pass are that package's.
Left out: sharded groups (``group_offset``), ``existing_out``, tiling of
the contracted dimension in ``gmm`` (a block holds all of ``K``, so a
visit is one product and no accumulator) and the masking of a ragged
last ``K`` tile.

Tile sizes, read on the chip (TPU v5 lite, my chip run, PR 27: the whole
jitted call, metadata included, mean of 50) for the two regimes the
serving cell has, at OLMoE's experts (gate / up 2048 -> 1024, down
1024 -> 2048, bf16, 64 experts), as microseconds gate | down:
  - a decode step, 64 rows over 43 experts: one row tile (``tm`` = the
    64 rows), so a visit is one expert and the time is the experts'
    bytes. tn 256: 307 | 302; 512: 267 | 259; 1024: 261 | 264; 2048:
    - | 261 (85% of the HBM roofline);
  - a 1024-token prefill, 8192 rows over 64 experts (tm, tn): (128,
    512) 671 | 783; (128, 1024) 626 | 706; (256, 512) 641 | 725; (256,
    1024) **610** | 674; (256, 2048) - | **639**; (512, 1024) 1,020 |
    1,050; (1024, 1024) 1,590 | 1,618. A smaller row tile re-reads an
    expert's matrix for each tile its ~128 rows straddle, a larger one
    multiplies more rows that belong to another group (64% of the
    roofline at the best; bound by the 64 experts' bytes, not FLOPs);
  - a 256-token prefill, 2048 rows: (128, 1024) 437 | 461; (256, 1024)
    450 | 482; (256, 2048) - | 458; (512, 1024) 769 | 781.
So: 256 rows a tile (or all of them, 16-padded, when fewer), and every
output column that keeps an expert's block within 8 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# (rows a tile, output columns a tile) preferred and the most bytes one
# expert's [K, tn] block may take; see the module docstring
TILE_M = 256
TILE_N = 2048
_BLOCK_BYTES = 8 * 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024


def _round_up(x: int, to: int) -> int:
    return -(-x // to) * to


def tiling(m: int, k: int, n: int, tm: int | None = None,
           tn: int | None = None, itemsize: int = 2) -> tuple[int, int]:
    """-> (tm, tn) for ``m`` rows, ``k`` contracted and ``n`` output
    columns: the preferred tile, no larger than the (16-padded) rows;
    ``tn`` a divisor of ``n`` whose [k, tn] block fits ``_BLOCK_BYTES``:
    the budget halved until it divides ``n`` or, where halving leaves
    the lanes (6144 -> 2048: of 640, 320, ... none divides 2048) or runs
    out of twos on ONE lane tile (2688 = 21 x 128), the largest whole-lane
    divisor within the budget (512; 896); else all of ``n`` (file's end)."""
    tm = min(tm or TILE_M, _round_up(m, 16))
    if tn is None:
        tn = max(128, _BLOCK_BYTES // (k * itemsize) // 128 * 128)
        tn = min(TILE_N, tn)
    budget = tn = min(tn, n)
    while n % tn:
        tn //= 2
    if (tn % 128 and tn != n) or tn == 128 < budget:
        tn = max((c for c in range(128, budget + 1, 128) if n % c == 0),
                 default=n)
    return tm, tn


def group_metadata(group_sizes, m: int, tm: int, *, visit_empty: bool):
    """Which group and which row tile each grid step works on.

    -> ((group_offsets [E + 1], group_ids [G], m_tile_ids [G]),
    num_steps) with G = m / tm + E - 1 slots of which the first
    ``num_steps`` are real. A row tile that several groups share is
    visited once by each, consecutively. With ``visit_empty`` an empty
    group gets one step (``tgmm`` must zero its output); without, none
    (``gmm`` never reads its matrix). megablox's ``make_group_metadata``
    without sharding."""
    num_groups = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    rounded = (ends + tm - 1) // tm * tm - starts // tm * tm
    group_tiles = jnp.where(group_sizes == 0, int(visit_empty),
                            rounded // tm)
    slots = tiles_m + num_groups - 1
    group_ids = jnp.repeat(jnp.arange(num_groups, dtype=jnp.int32),
                           group_tiles, total_repeat_length=slots)
    # a tile is visited once by the group that owns its first row and
    # once more by every group that starts inside it
    aligned = (starts % tm == 0) | (group_sizes == 0)
    if visit_empty:
        aligned = jnp.where(group_sizes == 0, False, aligned)
    partial_tile = jnp.where(aligned, tiles_m, starts // tm)
    visits = jnp.zeros(tiles_m + 1, jnp.int32).at[partial_tile].add(1)[
        :tiles_m] + 1
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), visits,
                            total_repeat_length=slots)
    return (offsets, group_ids, m_tile_ids), group_tiles.sum()


def _row_mask(offsets, group_ids, m_tile_ids, step, tm: int, width: int):
    """[tm, width] bool: rows of this step's tile in this step's group."""
    group = group_ids[step]
    rows = m_tile_ids[step] * tm + jax.lax.broadcasted_iota(
        jnp.int32, (tm, width), 0)
    return (rows >= offsets[group]) & (rows < offsets[group + 1])


def _gmm(lhs, rhs, meta, num_steps, *, tm: int, tn: int,
         transpose_rhs: bool, interpret: bool, layer=None):
    """lhs [M, K] (M a multiple of tm) x rhs [E, K, N] ([E, N, K] with
    ``transpose_rhs``) -> [M, N] in lhs's dtype. With ``layer`` (an
    int32 scalar) rhs is a stack [L, E, K, N] and the blocks are taken
    from ``rhs[layer]`` in place: slicing the stack first would copy
    every expert of the layer, read or not."""
    m, k = lhs.shape
    if layer is None:
        rhs, layer = rhs[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    n = rhs.shape[2] if transpose_rhs else rhs.shape[3]
    dims = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))

    def kernel(offsets, group_ids, m_tile_ids, layer_ref, lhs_ref, rhs_ref,
               out_ref):
        step = pl.program_id(1)
        acc = jax.lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                                  preferred_element_type=jnp.float32)
        mask = _row_mask(offsets, group_ids, m_tile_ids, step, tm, tn)
        # rows of the tile that belong to another group keep what that
        # group's visit stored (or will store)
        out_ref[...] = jnp.where(
            mask, acc, out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)

    def lhs_index(n_i, step, offsets, group_ids, m_tile_ids, layer_ref):
        return m_tile_ids[step], 0

    def rhs_index(n_i, step, offsets, group_ids, m_tile_ids, layer_ref):
        if transpose_rhs:
            return layer_ref[0], group_ids[step], n_i, 0
        return layer_ref[0], group_ids[step], 0, n_i

    def out_index(n_i, step, offsets, group_ids, m_tile_ids, layer_ref):
        return m_tile_ids[step], n_i

    rhs_block = (None, None, tn, k) if transpose_rhs \
        else (None, None, k, tn)
    itemsize = lhs.dtype.itemsize
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((tm, k), lhs_index),
                      pl.BlockSpec(rhs_block, rhs_index)],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(n // tn, num_steps),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k + m * n
                            + rhs.size // rhs.shape[0]) * itemsize),
        interpret=interpret,
        name="moe_gmm",
    )(*meta, layer, lhs, rhs)


def _tgmm(lhs, dout, meta, num_steps, *, num_groups: int, tm: int,
          tk: int, tn: int, interpret: bool):
    """lhs [M, K], dout [M, N] -> [E, K, N]: each group's rows of ``lhs``
    transposed times its rows of ``dout`` (the gradient of ``rhs``). An
    empty group's block is zeroed. megablox's ``tgmm``."""
    m, k = lhs.shape
    n = dout.shape[1]

    def kernel(offsets, group_ids, m_tile_ids, lhs_ref, dout_ref, out_ref,
               acc):
        step = pl.program_id(2)
        last = pl.num_programs(2) - 1
        group = group_ids[step]
        prev = group_ids[jnp.maximum(step - 1, 0)]
        nxt = group_ids[jnp.minimum(step + 1, last)]

        @pl.when((step == 0) | (prev != group))
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(offsets[group + 1] > offsets[group])
        def _accumulate():
            lhs_rows = jnp.where(
                _row_mask(offsets, group_ids, m_tile_ids, step, tm, tk),
                lhs_ref[...].astype(jnp.float32), 0.0)
            dout_rows = jnp.where(
                _row_mask(offsets, group_ids, m_tile_ids, step, tm, tn),
                dout_ref[...].astype(jnp.float32), 0.0)
            acc[...] += jax.lax.dot_general(
                lhs_rows.astype(lhs_ref.dtype),
                dout_rows.astype(dout_ref.dtype),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when((step == last) | (nxt != group))
        def _store():
            out_ref[...] = acc[...].astype(out_ref.dtype)

    def lhs_index(n_i, k_i, step, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], k_i

    def dout_index(n_i, k_i, step, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], n_i

    def out_index(n_i, k_i, step, offsets, group_ids, m_tile_ids):
        return group_ids[step], k_i, n_i

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((num_groups, k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec((tm, tn), dout_index)],
            out_specs=pl.BlockSpec((None, tk, tn), out_index),
            grid=(n // tn, k // tk, num_steps),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_tgmm",
    )(*meta, lhs, dout)


def _pad_rows(x, m_padded: int):
    return x if x.shape[0] == m_padded else jnp.pad(
        x, ((0, m_padded - x.shape[0]), (0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gmm_kernel(lhs, rhs, group_sizes, tm, tn, interpret):
    return _gmm_fwd(lhs, rhs, group_sizes, tm, tn, interpret)[0]


def _forward(lhs, rhs, group_sizes, tm, tn, interpret, layer=None):
    """The kernel's forward call: tile, pad the rows to whole tiles,
    build the grid's metadata, multiply, cut the padding off."""
    m = lhs.shape[0]
    tm, tn = tiling(m, lhs.shape[1], rhs.shape[-1], tm, tn,
                    lhs.dtype.itemsize)
    m_padded = _round_up(m, tm)
    meta, steps = group_metadata(group_sizes, m_padded, tm,
                                 visit_empty=False)
    return _gmm(_pad_rows(lhs, m_padded), rhs, meta, steps, tm=tm, tn=tn,
                transpose_rhs=False, interpret=interpret, layer=layer)[:m]


def _gmm_fwd(lhs, rhs, group_sizes, tm, tn, interpret):
    return (_forward(lhs, rhs, group_sizes, tm, tn, interpret),
            (lhs, rhs, group_sizes))


def _gmm_bwd(tm, tn, interpret, res, g):
    lhs, rhs, group_sizes = res
    m, k = lhs.shape
    n = rhs.shape[2]
    g = g.astype(lhs.dtype)
    tm_, tk_ = tiling(m, n, k, tm, tn, lhs.dtype.itemsize)
    _, tn_ = tiling(m, k, n, tm, tn, lhs.dtype.itemsize)
    m_padded = _round_up(m, tm_)
    lhs_p, g_p = _pad_rows(lhs, m_padded), _pad_rows(g, m_padded)
    meta, steps = group_metadata(group_sizes, m_padded, tm_,
                                 visit_empty=False)
    dlhs = _gmm(g_p, rhs, meta, steps, tm=tm_, tn=tk_, transpose_rhs=True,
                interpret=interpret)[:m]
    meta_all, steps_all = group_metadata(group_sizes, m_padded, tm_,
                                         visit_empty=True)
    drhs = _tgmm(lhs_p, g_p, meta_all, steps_all,
                 num_groups=rhs.shape[0], tm=tm_, tk=min(tk_, 512),
                 tn=min(tn_, 512), interpret=interpret)
    return dlhs, drhs.astype(rhs.dtype), None


_gmm_kernel.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, *, layer=None, transpose_rhs=False,
                   use_kernel: bool | None = None, interpret: bool = False,
                   tm: int | None = None, tn: int | None = None):
    """lhs [M, K], rhs [E, K, N], group_sizes [E] int32 summing to M ->
    [M, N] in lhs's dtype; differentiable in lhs and rhs. With ``layer``
    (an int32 scalar) rhs is a stack [L, E, K, N] of which ``rhs[layer]``
    is used, read in place by the kernel (a layer scan; not differentiable).
    ``use_kernel=None``: the Pallas kernel on a TPU, ``jax.lax.ragged_dot``
    elsewhere; ``interpret=True``: the kernel in the Pallas interpreter
    (never inferred); ``tm`` / ``tn`` override the tile (sweeps, tests)."""
    if transpose_rhs:  # (rhs [E, N, K]: the file's end)
        return _transposed(lhs, rhs, group_sizes, layer, use_kernel,
                           interpret, tm, tn)
    if use_kernel is None:
        use_kernel = interpret or jax.default_backend() == "tpu"
    rhs = rhs.astype(lhs.dtype)
    if not use_kernel:
        return jax.lax.ragged_dot(
            lhs, rhs if layer is None else rhs[layer], group_sizes)
    group_sizes = group_sizes.astype(jnp.int32)
    if layer is None:
        return _gmm_kernel(lhs, rhs, group_sizes, tm, tn, interpret)
    return _forward(lhs, rhs, group_sizes, tm, tn, interpret, layer)


# --------------------------------------------------------------------------
# At the file's END, and what reaches it from above keeps its lines: the
# kernel's Mosaic module carries the lines and columns of every call on
# its way, so a line added above moves the compile-cache key of every
# program that holds a ``moe_gmm`` (``PERF.md`` section 6, PR 70).
#
# ``tiling``, the rest of it. A halving that stops on two lane tiles or
# more stands, though a larger whole-lane divisor may lie within the
# budget (5120 under 2048: 1024, not 1280): those are the tiles the older
# widths were read at (``tests/test_grouped_matmul.py`` pins every one). A
# width with no whole-lane divisor is one block whatever the budget (1856 =
# 14.5 x 128: 9.98 MB beside a contracted 2688), its last lane tile half
# full. Read on the chip at Nemotron-3-Nano's experts (2688 -> 1856 ->
# 2688, bf16, 16 held; TPU v5 lite, my chip run, PR 70: the whole jitted
# call, mean of 50, best of two), as microseconds at a decode step's 192
# rows over 11 touched experts | a 1,024-row prompt's compact 1,536 rows
# over 16 | a 512-row prompt's 768:
#   - down (k 1856, n 2688), tn 128: 229.5 | 399.3 | 382.0; 384: 218.4 |
#     319.8 | 298.1; **896**: 211.7 | 297.9 | 279.8; 2688 (9.98 MB, over
#     the budget): 215.2 | 293.4 | 276.0. So 896: the clause above;
#   - up (k 2688, n 1856) from a matrix stored ``[E, N, K]``
#     (``transpose_rhs``), all of n in one block: 213.0 | 289.2 | 273.9
#     (63% of the HBM's roofline at the decode step's rows); the same
#     product from ``[E, K, N]`` reads 689 | 813 | 791: XLA keeps a matrix
#     whose minor dimension is 14.5 lane tiles TRANSPOSED in HBM (its
#     parameter's layout ``{1,2,0}``) and copies all sixteen experts back
#     before every call of the kernel.
# --------------------------------------------------------------------------

def _transposed(lhs, rhs, group_sizes, layer, use_kernel, interpret, tm, tn):
    """:func:`grouped_matmul` with rhs ``[E, N, K]`` (a stack ``[L, E, N,
    K]`` with ``layer``): an expert's output columns down the sublanes
    and the contracted width along the lanes, so that an N that is not
    whole lane tiles is no matrix's minor dimension. The kernel's block
    is an expert's ``[tn, K]``: all of N where it has no whole-lane
    divisor (the output's lanes), refused where two such blocks would
    not fit the kernel's VMEM. Not differentiable through the kernel."""
    if use_kernel is None:
        use_kernel = interpret or jax.default_backend() == "tpu"
    rhs = rhs.astype(lhs.dtype)
    if not use_kernel:
        return jax.lax.ragged_dot(lhs, jnp.swapaxes(
            rhs if layer is None else rhs[layer], 1, 2), group_sizes)
    m, k = lhs.shape
    tm, tn = tiling(m, k, rhs.shape[-2], tm, tn, lhs.dtype.itemsize)
    if 4 * tn * k * lhs.dtype.itemsize > _VMEM_LIMIT:
        raise ValueError(
            f"an expert's [{tn}, {k}] block twice over is more than half "
            f"of the kernel's {_VMEM_LIMIT >> 20} MiB of VMEM: lay the "
            "weight out padded to whole lane tiles")
    # (``_forward``'s lines with the other layout: its own call of
    # ``_gmm`` keeps its text, which the older programs' keys hold)
    m_padded = _round_up(m, tm)
    meta, steps = group_metadata(group_sizes.astype(jnp.int32), m_padded,
                                 tm, visit_empty=False)
    return _gmm(_pad_rows(lhs, m_padded), rhs, meta, steps, tm=tm, tn=tn,
                transpose_rhs=True, interpret=interpret, layer=layer)[:m]
