"""One token of the SSD recurrence (Mamba-2) on every slot's state, in
place.

(The second recurrence in the tree. The delta rule of ``ops/kda_step.py``
decays a channel, predicts and corrects; this one has ONE scalar decay a
head and step, no prediction, and a ``B`` and a ``C`` row a GROUP of
heads: one group that all heads share (``models/granite.py``) or several
(``models/nemotron.py``: eight groups of eight heads). A decode step is
this file's recurrence, one token on every slot; a prefill is its
chunked form over a prompt's rows, ``ops/ssd_chunk.py``.)

A Mamba-2 layer (``models/granite.py``: 128 heads of 64 with a state of
128 in one group; ``models/nemotron.py``: 64 heads of 64 with a state of
128 in eight groups) keeps a float32 state ``H [P, N]`` a head (P the
head's width, N the state's); a decode step decays it, adds the outer
product of the step's input and its group's ``B`` and reads it against
its group's ``C``::

    H <- H * da + (dt x)[:, None] * B[None, :]
    y = sum_N H * C[None, :]

``da = exp(dt A)`` and ``dt x`` are made by the caller (both float32);
the skip ``D x`` is the caller's too.

**The state's layout is this file's** (:func:`pack` / :func:`unpack`):
``[slots, heads / g, N, g * P]``, the state's N along the sublanes and
``g`` heads' P side by side in the lanes (``g = 128 / P``: two heads of
64 fill a lane row; :func:`lane_heads`). So everything a head brings
(``dt x``, ``da``, and the output ``y``) is a ROW of lanes, as XLA
leaves it (``[slots, heads * P]`` read as ``[slots, heads / g, g * P]``:
no transpose anywhere), broadcast along the sublanes by the load; ``B``
and ``C`` are columns, broadcast along the lanes ONCE a grid step and
group for all the group's heads; and the sum over N runs down the
sublanes: fifteen adds of whole registers and one fold of eight sublanes
a lane row. **A group is whole lane rows** (heads ``g H / G .. (g + 1) H
/ G - 1`` are lane rows ``g R / G ..`` of the R: refused at trace time
otherwise), and a grid step's block of lane rows is whole groups or a
part of one: its ``B`` and ``C`` columns come in with it, picked by the
block's index (Granite: a block of 16 of the 64 lane rows of its one
group; Nemotron: a block of 16 of 32 lane rows is four groups of four).

Read on the chip (TPU v5 lite, my chip runs, PR 54; 96 slots x 128 heads
x 64 x 128 float32, a call in a loop of 50 on a donated state, best of
two; state and output equal to the XLA body's bit for bit): this kernel
1,271 / **1,268** / 1,263 / 1,262 us at 8 / 16 / 32 / 64 lane rows a
block; the XLA body alone in a loop 1,250; the bytes at the HBM's peak
983, so 77.5%, which is what a Pallas copy in place reaches on this chip
(``ops/kda_step.py``: 76.4%): the pipeline's floor. The first form of
this kernel kept P along the sublanes and N in the lanes, as the
recurrence is usually written: every register of the state then wanted a
sum across its lanes and a one-lane store, and it read 1,799 / 1,546 /
1,432 / 1,387 / 1,379 us at 8 / 16 / 32 / 64 / 128 heads a block.

The least a layer-step can do is read ``H`` once and write it once. On a
TPU that is what the Pallas kernel ``ssd_step`` does (``custom-call/
.../ssd_step`` in a device trace): a grid step brings a block of lane
rows of one slot in, computes the two lines in float32 elementwise
arithmetic (no matrix unit: a product is never rounded) and writes the
block back **to the buffer it came from** (``input_output_aliases``: the
engine donates its state to the decode chunk, so nothing is copied). A
slot that is not ``active`` gets back what was read, bit for bit. Off a
TPU, or where a lane row is not whole lanes, :func:`ssd_recurrence` and
a ``where`` run (the XLA body, and the tests' second opinion). Like
``ops/kda_step.py`` the call carries no ``cost_estimate`` (with one, XLA
moves states into VMEM round their calls).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# lane rows (of ``lane_heads`` heads each) of a slot a grid step holds: a
# block of 16 x 128 x 128 float32 is 1 MB, the size of ``kda_step``'s
BLOCK_ROWS = 16
_VMEM_LIMIT = 64 * 1024 * 1024


def lane_heads(heads: int, p: int) -> int:
    """Heads side by side in one row of lanes: 128 / P where that is
    whole and divides ``heads``, else 1."""
    g = 128 // p if p < 128 and 128 % p == 0 else 1
    return g if heads % g == 0 else 1


def pack(h):
    """h [B, H, P, N] -> the state's layout [B, H / g, N, g * P]."""
    b, heads, p, n = h.shape
    g = lane_heads(heads, p)
    return jnp.transpose(h.reshape(b, heads // g, g, p, n),
                         (0, 1, 4, 2, 3)).reshape(b, heads // g, n, g * p)


def unpack(h, p: int):
    """The state's layout [B, G, N, g * P] -> [B, G * g, P, N]."""
    b, rows, n, lanes = h.shape
    g = lanes // p
    return jnp.transpose(h.reshape(b, rows, n, g, p),
                         (0, 1, 3, 4, 2)).reshape(b, rows * g, p, n)


def group_rows(rows: int, groups: int) -> int:
    """Lane rows a group of heads: ``rows / groups``, which must be
    whole (a lane row's heads share one ``B`` and ``C``)."""
    if groups < 1 or rows % groups:
        raise ValueError(
            f"{groups} groups of heads do not divide the state's {rows} "
            "lane rows: a group's lane rows must be whole")
    return rows // groups


def ssd_recurrence(h, x, da, b, c):
    """One token of the recurrence on the state h [B, R, N, L] (float32,
    elementwise: no product is rounded). x [B, R, L]: the step's input
    times its step size; da [B, R, L]: ``exp(dt A)``, a head's repeated
    over its lanes; b, c [B, G, N]: a group's rows, lane rows ``g R / G
    ..`` group g's. -> (h, y [B, R, L])."""
    per = group_rows(h.shape[1], b.shape[1])
    b, c = (jnp.repeat(a, per, axis=1)[..., None] for a in (b, c))
    h = h * da[:, :, None, :] + b * x[:, :, None, :]
    return h, jnp.sum(h * c, axis=2)


def block_rows(rows: int, most: int = BLOCK_ROWS) -> int:
    """Lane rows a block: all of them, or the largest divisor of
    ``rows`` in whole tiles of 8 that is at most ``most``."""
    fit = [r for r in range(8, min(rows, most) + 1, 8) if rows % r == 0]
    return max(fit) if fit and rows > most else rows


def _kernel(active_ref, x_ref, da_ref, bc_ref, h_ref, h_out_ref, y_ref, *,
            rb: int, per: int):
    active = active_ref[pl.program_id(0)] != 0
    n, lanes = h_ref.shape[1:]
    for i in range(rb):
        if i % per == 0:
            # a group's B and C down the sublanes, across every lane:
            # once for the ``per`` lane rows of the block that share them
            b = jnp.broadcast_to(bc_ref[i // per, :, 0:1], (n, lanes))
            c = jnp.broadcast_to(bc_ref[i // per, :, 1:2], (n, lanes))
        h = h_ref[i]  # [N, L]
        new = h * da_ref[i:i + 1, :] + b * x_ref[i:i + 1, :]
        y_ref[i:i + 1, :] = jnp.sum(new * c, axis=0, keepdims=True)
        h_out_ref[i] = jnp.where(active, new, h)


def _ssd_step(h, x, da, bc, active, *, rb: int, interpret: bool):
    """The kernel's call. h [B, R, N, L]; x, da [B, R, L]; bc [B, G, N,
    2]: each group's B and C as columns; active [B] int32. A block of
    ``rb`` lane rows is whole groups or lies inside one. -> (h, y [B, R,
    L])."""
    bsz, rows, n, lanes = h.shape
    of_group = group_rows(rows, bc.shape[1])
    if rb % of_group and of_group % rb:
        raise ValueError(
            f"a block of {rb} lane rows is neither whole groups of "
            f"{of_group} lane rows nor a part of one")
    per, span = min(rb, of_group), max(rb, of_group)

    def state(i, j, active_ref):
        return i, j, 0, 0

    def vectors(i, j, active_ref):
        return i, j, 0

    h_block = pl.BlockSpec((None, rb, n, lanes), state)
    x_block = pl.BlockSpec((None, rb, lanes), vectors)
    return pl.pallas_call(
        functools.partial(_kernel, rb=rb, per=per),
        out_shape=(jax.ShapeDtypeStruct(h.shape, h.dtype),
                   jax.ShapeDtypeStruct(x.shape, h.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[x_block, x_block,
                      # (the block's groups: ``rb / per`` of them)
                      pl.BlockSpec((None, rb // per, n, 2),
                                   lambda i, j, active_ref: (
                                       i, j * rb // span, 0, 0)),
                      h_block],
            out_specs=[h_block, x_block],
            grid=(bsz, rows // rb),
        ),
        # (operand 0 is the prefetched ``active``)
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        # (no ``cost_estimate``: module docstring)
        interpret=interpret,
        name="ssd_step",
    )(active, x, da, bc, h)


def ssd_step(h, dtx, da, b, c, active, *, use_kernel: bool | None = None,
             interpret: bool = False, rows: int | None = None):
    """A decode step of the recurrence on the slots' state: h [B, R, N,
    L] float32 in this file's layout (:func:`pack`); dtx [B, H, P], da
    [B, H], b, c [B, G, N] float32 (heads ``g H / G ..`` read group g's:
    whole lane rows); ``active`` [B] bool. -> (h: updated where
    ``active``, kept bit for bit elsewhere; y [B, H, P], every slot's).

    ``use_kernel=None`` takes the backend's: the Pallas kernel on a TPU
    where a lane row is whole lanes and N whole sublanes,
    :func:`ssd_recurrence` and a ``where`` elsewhere. ``interpret=True``
    runs the kernel in the Pallas interpreter (never inferred). ``rows``
    overrides the lane rows a block (the chip's tuning sweep and the
    tests)."""
    bsz, lane_rows, n, lanes = h.shape
    heads, p = dtx.shape[1:]
    if use_kernel is None:
        use_kernel = interpret or (
            jax.default_backend() == "tpu" and lanes % 128 == 0
            and n % 8 == 0)
    x = dtx.reshape(bsz, lane_rows, lanes)
    da = jnp.repeat(da, p, axis=1).reshape(bsz, lane_rows, lanes)
    if not use_kernel:
        new, y = ssd_recurrence(h, x, da, b, c)
        new = jnp.where(active[:, None, None, None], new, h)
    else:
        new, y = _ssd_step(h, x, da, jnp.stack([b, c], axis=3),
                           active.astype(jnp.int32),
                           rb=block_rows(lane_rows, rows or BLOCK_ROWS),
                           interpret=interpret)
    return new, y.reshape(bsz, heads, p)
