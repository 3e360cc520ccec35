"""What a KDA layer's recurrence starts from: q, k, v and the log decay.

Everything between a KDA layer's projections and its delta rule
(``models/ling.py``, ``models/solar.py``; arXiv:2510.26692,
``fla/layers/kda.py``): over the projection's rows ``x @ w_qkv`` and the
K - 1 rows before them, the causal depthwise convolution of K taps,
SiLU, the split into q, k and v, q's and k's L2 norm a head (q scaled by
``dk ** -0.5``); and from the decay's pre-activation ``f`` the log decay
a channel in the block's own form, which one static argument tells
apart:

- ``lower_bound=None``: Kimi Linear's, ``g = -exp(a_log) softplus(f)``,
  unbounded below (Solar-Open2);
- ``lower_bound=c``: ``g = c sigmoid(exp(a_log) f)``, in (c, 0) (Ling).

A padding row (index >= ``real_rows`` of its stream) gets ``g`` 0 where
``g`` is made, so that no pass of its own zeroes it before the delta
rule. Float32 throughout; the projection's rows arrive in the
configuration's bf16.

Two forms, one result:

- :func:`kda_inputs_xla`, **the XLA body**: the lines ``ling.kda_qkv``
  and the two ``_kda_inputs`` held until PR 57, moved here. The path off
  a TPU, of a decode step (T = 1: both blocks' decode chunks compile to
  the text they had), of a head that is not whole lanes, of a row count
  that is not whole blocks, and the only form that is differentiated.
  XLA runs it as several fusions over ``[T, 3 H dk]``: the convolution
  over the concatenated rows, SiLU and the split, two norms, the decay,
  the ``where``: 2.5 ms a 2,048-row layer-segment at 64 heads inside
  Solar-Open2's prefill program for 0.53 ms of bytes (``PERF.md``
  section 6, PR 57).
- the Pallas kernel ``kda_inputs`` (``custom-call/.../kda_inputs`` in a
  device trace), **one call a layer-segment**, one pass. Grid (batch,
  blocks of heads, blocks of rows), the row axis sequential. A grid step
  reads the projection's rows of its heads three times over (the q, the
  k and the v columns: three operands, one array) and ``f``, and writes
  q, k, v, g as float32 ``[B, T, H * dk]``, a row's heads end to end:
  the layout ``ops/kda_chunk.py`` takes, nothing relaid between the
  two. ``concatenate([conv_rows, x @ w_qkv])`` does not exist: the three
  rows before a block's first are the last rows of the block before it,
  kept in a VMEM scratch along the row axis, and before a segment's
  first block they are ``conv_rows`` (handed in as eight float32 rows,
  the three last of them real: a tile). The taps are sublane rotations
  of a head's ``[rows, 128]`` (``pltpu.roll``) whose first rows are put
  right from that scratch; they are summed in the body's order. The
  rows a slot keeps (:func:`kept_rows`) are gathered from
  ``conv_rows`` and the product, three rows of two operands.

``interpret=True`` (a test's explicit choice) runs the kernel in the
Pallas interpreter. Like ``ops/kda_chunk.py`` the call is jitted by
itself (a program holds one private function that its KDA layers call)
and carries no ``cost_estimate``.

Read on the chip (TPU v5 lite, my chip runs, PR 57; one 2,048-row
layer-segment at 64 heads, device time from a trace): 572 us at 256
rows x 8 heads a grid step (576 at 64 x 64, 579 at 128 x 32, 586 at 512
x 16, 776 at 32 x 64, 1,193 at 16 x 64) for 533 us of bytes at the
HBM's 819 GB/s; a copy kernel of the same blocks 648-666 us; the body
with the convolution, SiLU, the norms and the decay taken out 561 us:
the kernel is bound by its bytes at every block that is not tiny, the
plain lane sum of a head's squares costs nothing that shows, and q, k,
v, g equal the XLA body's bit for bit (the taps are summed in its
order). Inside Solar-Open2's prefill program 0.61 ms a call (XLA keeps
``f`` of a segment in VMEM there).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.kda_step import block_heads

# the rows and the heads a grid step holds (T must be whole row blocks
# for the kernel to engage; the largest divisor of H at most this)
BLOCK_ROWS = 256
BLOCK_HEADS = 8
_HALO = 8  # rows of a float32 tile: what is kept of the block before
_VMEM_LIMIT = 64 * 1024 * 1024


# --------------------------------------------------------------------------
# The XLA body
# --------------------------------------------------------------------------

def kda_inputs_xla(proj, conv_rows, conv, f, a_log, *, lower_bound=None,
                   real_rows=None):
    """The arguments and the result of :func:`kda_inputs`, plain XLA."""
    b, t, _ = proj.shape
    h = a_log.shape[0]
    dk = proj.shape[-1] // (3 * h)
    f32 = jnp.float32
    u = jnp.concatenate([conv_rows, proj], axis=1)
    w = conv.astype(f32)
    y = sum(w[i] * u[:, i:i + t].astype(f32) for i in range(conv.shape[0]))
    q, k, v = (a.reshape(b, t, h, dk)
               for a in jnp.split(jax.nn.silu(y), 3, axis=-1))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    if callable(f):  # (made here, behind q, k and v, as the blocks did)
        f = f()
    if lower_bound is None:
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            f.reshape(b, t, h, dk))
    else:
        g = lower_bound * jax.nn.sigmoid(
            jnp.exp(a_log)[:, None] * f.reshape(b, t, h, dk))
    if real_rows is not None:
        real = jnp.arange(t)[None, :] < real_rows[:, None]  # [B, T]
        g = jnp.where(real[..., None, None], g, 0.0)
    return q, k, v, g


def kept_rows(conv_rows, proj, first):
    """Rows ``first`` .. ``first + K - 2`` of ``concatenate([conv_rows,
    proj], 1)`` for each stream (``first`` [B] int32 in 0 .. T): the
    projection rows a slot keeps for its next convolution, gathered from
    the two arrays as they lie. -> [B, K-1, W]."""
    kw, t = conv_rows.shape[1], proj.shape[1]
    at = first[:, None] + jnp.arange(kw)[None, :]  # [B, K-1]
    old = jnp.take_along_axis(
        conv_rows, jnp.clip(at, 0, kw - 1)[..., None], axis=1)
    new = jnp.take_along_axis(
        proj, jnp.clip(at - kw, 0, t - 1)[..., None], axis=1)
    return jnp.where((at < kw)[..., None], old, new)


# --------------------------------------------------------------------------
# The kernel
# --------------------------------------------------------------------------

def _kernel(real_ref, q_in, k_in, v_in, before_ref, w_ref, f_ref, a_ref,
            q_ref, k_ref, v_ref, g_ref, halo_ref, *, hb: int, dk: int,
            lower_bound):
    """One block of rows of one block of heads. ``q_in`` / ``k_in`` /
    ``v_in`` [rows, hb * dk] bf16: the projection's q, k and v columns;
    ``before_ref`` [3, 8, hb * dk] float32: the rows before the segment
    (the last K - 1 of the eight real); ``w_ref`` [3, K, hb * dk]: the
    taps; ``f_ref`` [rows, hb * dk]; ``a_ref`` [1, hb * dk]: exp(a_log)
    a lane; ``halo_ref`` [3, 8, hb * dk]: the last eight rows of the
    block before, carried."""
    f32 = jnp.float32
    rows = f_ref.shape[0]
    taps = w_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        halo_ref[...] = before_ref[...]

    row = jax.lax.broadcasted_iota(jnp.int32, (rows, dk), 0)
    first = row[:_HALO]
    real = row + pl.program_id(2) * rows < real_ref[pl.program_id(0)]

    def convolved(x_ref, part, at):
        """SiLU of the causal convolution of one head's columns."""
        x = x_ref[:, at].astype(f32)  # [rows, dk]
        w = w_ref[part, :, at]  # [K, dk]
        halo = halo_ref[part, :, at]  # [8, dk]
        top, y, y_top = x[:_HALO], None, None
        for i in range(taps):  # (the body's order: the oldest row first)
            back = taps - 1 - i
            if back:
                # row r takes row r - back: a rotation down the sublanes,
                # whose first rows come from the block before
                moved = pltpu.roll(x, back, 0)
                moved_top = jnp.where(first < back,
                                       pltpu.roll(halo, back, 0),
                                       pltpu.roll(top, back, 0))
            else:
                moved, moved_top = x, top
            y = w[i:i + 1] * moved if y is None else y + w[i:i + 1] * moved
            y_top = w[i:i + 1] * moved_top if y_top is None \
                else y_top + w[i:i + 1] * moved_top
        halo_ref[part, :, at] = x[rows - _HALO:]
        y = jnp.concatenate([y_top, y[_HALO:]], axis=0)
        return y * jax.nn.sigmoid(y)

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                                 + 1e-6)

    def head(h, _):
        at = pl.ds(pl.multiple_of(h * dk, dk), dk)
        q_ref[:, at] = unit(convolved(q_in, 0, at)) * dk ** -0.5
        k_ref[:, at] = unit(convolved(k_in, 1, at))
        v_ref[:, at] = convolved(v_in, 2, at)
        f, a = f_ref[:, at], a_ref[:, at]
        if lower_bound is None:
            # (``jax.nn.softplus``: ``logaddexp(f, 0)``, written out)
            g = -a * (jnp.maximum(f, 0.0)
                      + jnp.log1p(jnp.exp(-jnp.abs(f))))
        else:
            g = lower_bound * jax.nn.sigmoid(a * f)
        g_ref[:, at] = jnp.where(real, g, 0.0)
        return 0

    jax.lax.fori_loop(0, hb, head, 0)


@functools.partial(jax.jit, static_argnames=(
    "lower_bound", "rows", "hb", "interpret"))
def _kda_inputs(proj, conv_rows, conv, f, a_log, real_rows, *, lower_bound,
                rows: int, hb: int, interpret: bool):
    """The kernel's call. (Jitted by itself: a program holds one private
    function that every KDA layer calls.) -> q, k, v, g [B, T, H * dk]."""
    b, t, width = proj.shape
    h = a_log.shape[0]
    dk = width // (3 * h)
    kw = conv_rows.shape[1]
    f32 = jnp.float32
    n = hb * dk
    parts = h // hb  # (column blocks a third of the projection)
    # the K - 1 rows before, as the LAST rows of a float32 tile a third
    before = jnp.pad(conv_rows.astype(f32).reshape(b, kw, 3, h * dk),
                     ((0, 0), (_HALO - kw, 0), (0, 0), (0, 0)))
    before = jnp.moveaxis(before, 2, 1)  # [B, 3, 8, H * dk]
    w = jnp.moveaxis(conv.astype(f32).reshape(-1, 3, h * dk), 1, 0)
    a = jnp.repeat(jnp.exp(a_log), dk)[None, :]  # [1, H * dk]

    def columns(part):
        return pl.BlockSpec((None, rows, n),
                            lambda i, j, r, real: (i, r, part * parts + j))

    block = pl.BlockSpec((None, rows, n), lambda i, j, r, real: (i, r, j))
    out = jax.ShapeDtypeStruct((b, t, h * dk), f32)
    return pl.pallas_call(
        functools.partial(_kernel, hb=hb, dk=dk, lower_bound=lower_bound),
        out_shape=(out,) * 4,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[columns(0), columns(1), columns(2),
                      pl.BlockSpec((None, 3, _HALO, n),
                                   lambda i, j, r, real: (i, 0, 0, j)),
                      pl.BlockSpec((3, w.shape[1], n),
                                   lambda i, j, r, real: (0, 0, j)),
                      block,
                      pl.BlockSpec((1, n), lambda i, j, r, real: (0, j))],
            out_specs=[block] * 4,
            grid=(b, h // hb, t // rows),
            scratch_shapes=[pltpu.VMEM((3, _HALO, n), f32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="kda_inputs",
    )(real_rows, proj, proj, proj, before, w, f, a)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _kernel_form(proj, conv_rows, conv, f, a_log, real_rows, lower_bound,
                 rows, hb, interpret):
    return _kda_inputs(proj, conv_rows, conv, f, a_log, real_rows,
                       lower_bound=lower_bound, rows=rows, hb=hb,
                       interpret=interpret)


def _kernel_form_fwd(proj, conv_rows, conv, f, a_log, real_rows,
                     lower_bound, rows, hb, interpret):
    return (_kernel_form(proj, conv_rows, conv, f, a_log, real_rows,
                         lower_bound, rows, hb, interpret),
            (proj, conv_rows, conv, f, a_log, real_rows))


def _kernel_form_bwd(lower_bound, rows, hb, interpret, inputs, cotangents):
    """The XLA body's derivative (no cell trains these blocks)."""
    *arrays, real_rows = inputs
    b, t, _ = arrays[0].shape

    def body(*arrays):
        return tuple(a.reshape(b, t, -1) for a in kda_inputs_xla(
            *arrays, lower_bound=lower_bound, real_rows=real_rows))

    return (*jax.vjp(body, *arrays)[1](cotangents), None)


_kernel_form.defvjp(_kernel_form_fwd, _kernel_form_bwd)


def kda_inputs(proj, conv_rows, conv, f, a_log, *, lower_bound=None,
               real_rows=None, use_kernel: bool | None = None,
               interpret: bool = False, rows: int | None = None,
               heads: int | None = None):
    """q, k, v and the log decay of a KDA layer over T rows. ``proj``
    [B, T, 3*H*dk]: the projection's rows ``x @ w_qkv`` (q's heads, then
    k's, then v's); ``conv_rows`` [B, K-1, 3*H*dk]: the rows before
    them; ``conv`` [K, 3*H*dk]: the depthwise taps; ``f`` [B, T, H*dk]
    float32: the decay's pre-activation, bias added, or a function of
    no argument that makes it (the body calls it where the blocks' own
    lines made it, behind q, k and v: a decode step's program keeps its
    text); ``a_log`` [H].
    ``lower_bound`` (static) chooses the decay's form (module
    docstring); ``real_rows`` [B] int32: rows from that index on are
    padding and get ``g`` 0. -> q, k, v, g [B, T, H, dk] float32.

    ``use_kernel=None`` takes the backend's: the Pallas kernel on a TPU
    where a head is whole lanes and T whole row blocks (never a decode
    step), :func:`kda_inputs_xla` elsewhere. ``interpret=True`` runs the
    kernel in the Pallas interpreter (never inferred). ``rows`` and
    ``heads`` override a block's (the chip's tuning sweep and the
    tests). A differentiated call takes the body's derivative."""
    b, t, width = proj.shape
    h = a_log.shape[0]
    dk = width // (3 * h)
    rows = rows or BLOCK_ROWS
    if use_kernel is None:
        use_kernel = interpret or jax.default_backend() == "tpu"
    if not (use_kernel and dk % 128 == 0 and t % rows == 0):
        return kda_inputs_xla(proj, conv_rows, conv, f, a_log,
                              lower_bound=lower_bound, real_rows=real_rows)
    if real_rows is None:
        real_rows = jnp.full((b,), t, jnp.int32)
    out = _kernel_form(proj, conv_rows, conv, f() if callable(f) else f,
                       a_log, real_rows.astype(jnp.int32), lower_bound, rows,
                       block_heads(h, heads or BLOCK_HEADS), interpret)
    return tuple(a.reshape(b, t, h, dk) for a in out)
