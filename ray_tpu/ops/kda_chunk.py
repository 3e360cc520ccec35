"""The chunkwise delta rule of a KDA prefill: T tokens onto a state.

The prefill of a KDA layer (``models/ling.py``, 32 heads, a decay bounded
below; ``models/solar.py``, 64, ``beta`` in (0, 2), ``g`` unbounded: both
taken as given; Granite's Mamba-2 layers run the OTHER recurrence, SSD:
``ops/ssd_chunk.py``) is the chunkwise form of ``kda_step.kda_recurrence``.
Inside a chunk of C rows, with G the running sum of g from the chunk's
start::

    (I + Diag(beta) A) U = Diag(beta) (V - (K e^G) S)
        A[i, j] = sum_c k_i k_j e^(G_i - G_j)      j < i
    O = (Q e^G) S + B U
        B[i, j] = sum_c q_i k_j e^(G_i - G_j)      j <= i
    S' = Diag(e^(G_C)) S + (K e^(G_C - G))^T U

The decays are taken pairwise, ``e^(G_i - G_j) <= 1``, never as
``e^(-G_j)`` and never against the chunk's start: a sum of ``g`` under
-87 inside one chunk is ordinary for the unbounded decay.

Two forms, one result (float32 throughout):

- :func:`kda_chunked`, **the XLA body**: the path off a TPU, for a head
  that is not whole lanes, the tests' second opinion and the only form
  that is differentiated. It makes the decay of every pair of a chunk's
  rows and every channel (a float32 ``[H, C, C, dk]`` a chunk), reduces
  it twice over ``dk``, inverts ``I + Diag(beta) A`` a row at a time and
  carries ``S`` through a ``lax.scan``: everything through HBM.
- the Pallas kernel ``kda_chunk`` (``custom-call/.../kda_chunk`` in a
  device trace), **one call a layer-segment**. Grid (batch, head
  blocks, chunks), the chunk axis sequential. The arrays reach it with
  a row's heads end to end, ``[B, T, H * dk]``: what the projections
  leave (the reshape from ``[B, T, H, dk]`` is undone by XLA in the
  producers' own output, no copy before the call), and in that form a
  ``(C, hb * 128)`` block is whole tiles of which a head's ``[C, dk]``
  is a run (read as ``[B, T, H, dk]`` blocks a head's rows lie a
  sublane stride apart: 2.06 us a row for 1.34, below). ``S`` of a
  head block lives in a float32 VMEM scratch from the segment's first
  chunk to its last, read from ``s0`` once and written once, into the
  buffer ``s0`` came in (``input_output_aliases``: a layer's scan over
  the segments carries it). Nothing of a chunk's inner work exists in
  HBM.

**The pairwise decays in sub-blocks of 16 rows** (exact, the one change
to the algorithm). For a row i in sub-block I, r its first row, and a
column j in an earlier sub-block: ``e^(G_i - G_j) = e^(G_i - G_r)
e^(G_r - G_j)``, both exponents <= 0 since ``j < r <= i`` and ``g <=
0``; a factor underflows only where the product does. So the
off-diagonal blocks of A and B are one product over ``dk`` on the
matrix unit of rows scaled by factors <= 1 (K_I and Q_I stacked against
the earlier keys); the four 16 x 16 diagonal blocks keep the
elementwise form, a column j at a time (``e^(G_i - G_j)`` masked to
``i >= j`` before the exponential, two lane sums). The solve is a
forward substitution: across sub-blocks by products (``R_I -= N[I, <I]
U_<I``), inside one column by column in float32 elementwise arithmetic
(``R_I -= N[:, j] u_j``: no inverse is formed, no product rounded), and
the diagonal block's part of ``B U`` is accumulated the same way.

**Products.** Every ``jnp.dot`` in the kernel carries
``Precision.HIGHEST`` (Mosaic's float32 product: six bf16 passes, as
the XLA body's ``_HI``): ``[K e^G; Q e^G] S``, the off-diagonal blocks,
their products with ``U``, and ``(K e^(G_C - G))^T U``. ``Diag(e^(G_C))
S`` is an elementwise multiply, so a chunk of padding rows (``beta`` 0,
``g`` 0) hands ``S`` on bit for bit.

``interpret=True`` (a test's explicit choice) runs the kernel in the
Pallas interpreter. Like ``ops/kda_step.py`` the call carries no
``cost_estimate``.

Read on the chip (TPU v5 lite, my chip run, PR 47, and PR 46's builder's
before it to the second figure; one call in a loop of five, best of
three, microseconds a ROW of all heads; o and S equal to the XLA body's
within 7e-7 / 2e-7 of their largest number in every row below). One
2,048-row segment at 64 heads: the XLA body 7.53 (8.8 inside the
prefill program); this kernel 1.30 at 16 heads a block (1.33 at 8, 1.40
at 4). On the way there (PR 45's builder, whose tree this
was; not read again), with ``[B, T, H, dk]`` blocks and a head's rows
read a sublane stride apart: 2.19, and 2.06 with the diagonal blocks'
columns in strips of 8 rows (a strip above the column's own is skipped,
a strip below it needs no mask); of those 2.19 the diagonal blocks were
0.98, the off-diagonal products 0.29, what is left (the products with
``S``, the turn of ``K e^(G_C - G)``, the reads) 0.68-0.74; sub-blocks
of 8 rows 2.21 and of 32 rows 2.48 for 16's 2.12. At 32 heads (PR 47):
1,024 rows 3.10 -> 0.80, 256 rows 3.45 -> 1.67 (the host's dispatch
holds both sides there): the faster form at every shape a cell has.
The prefill program around the call (``tests/test_tpu_compile_ling.py``,
``_solar.py``): the four arrays come out of the producers'
fusions in the kernel's layout, ``S`` out of the scan's carry, no
``copy`` of an operand; XLA keeps the output ``o`` of a segment in VMEM
(``S(1)``) for the output norm behind it and, at Ling's two smaller
buckets, prefetches a 4-8 MB ``g`` there ahead of two of the six calls.

What the kernel costs a process's start is its TRACE, not its compile
(a Mosaic compile is a second, and cached). PR 45's builder found it:
written out, a head's 96 column steps a chunk made a jaxpr of 200 k
characters, traced again for every layer of every bucket, 2.2 s each on
the chip's host: Ling's set-up read 120-160 s for the parent's 80-88
(18 traces). So the column step is a jitted function of its own (traced
twice) and the kernel's call is jitted by itself (one trace a shape:
three in Ling's cell, one in Solar-Open2's, whose segments are all
2,048 rows; ``tests/test_tpu_compile_ling.py -k lowering_lings`` counts
them). ``PERF.md`` section 6, PR 47, has both cells' set-up on both
sides. (That builder also read the columns as a ``fori_loop``: 1.62 us a row.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.kda_step import block_heads

_HI = jax.lax.Precision.HIGHEST
# heads a grid step holds (the largest divisor of H at most this), and
# the rows of a sub-block of the pairwise decays; see the module docstring
BLOCK_HEADS = 16
SUB = 16
STRIP = 8  # rows of a float32 tile: what a diagonal block's column works on
_VMEM_LIMIT = 64 * 1024 * 1024


# --------------------------------------------------------------------------
# The XLA body
# --------------------------------------------------------------------------

def _unit_lower_inverse(n):
    """(I + n)^-1 for strictly lower-triangular n [..., C, C], by
    forward substitution a row at a time in float32 elementwise
    arithmetic (C steps, each over every chunk and head at once)."""
    c = n.shape[-1]
    eye = jnp.broadcast_to(jnp.eye(c, dtype=n.dtype), n.shape)

    def row(i, t):
        n_i = jax.lax.dynamic_index_in_dim(n, i, axis=-2, keepdims=False)
        r = jax.lax.dynamic_index_in_dim(eye, i, axis=-2, keepdims=False) \
            - jnp.sum(n_i[..., :, None] * t, axis=-2)
        return jax.lax.dynamic_update_index_in_dim(t, r, i, axis=-2)

    return jax.lax.fori_loop(1, c, row, eye)


def kda_chunked(q, k, v, g, beta, s0, *, chunk: int):
    """The chunkwise form of ``kda_recurrence`` over T tokens (T a
    multiple of ``chunk``), plain XLA. q, k, g [B, T, H, dk], v [B, T,
    H, dv], beta [B, T, H], all float32; s0 [B, H, dk, dv]. A token with
    ``beta`` 0 and ``g`` 0 leaves the state as it was (padding).
    -> (o [B, T, H, dv], the state after the last token). The module
    docstring has the chunk's equations."""
    b, t, h, dk = q.shape
    c = chunk
    nc = t // c

    def chunks(a):  # [B, T, H, ...] -> [NC, B, H, C, ...]
        a = a.reshape(b, nc, c, h, *a.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-2)  # [NC, B, H, C, dk]
    lower = jnp.tril(jnp.ones((c, c), bool))

    def pairwise(xs):
        q_, k_, g_, beta_ = xs
        diff = g_[..., :, None, :] - g_[..., None, :, :]  # [B,H,C,C,dk]
        decay = jnp.exp(jnp.where(lower[..., None], diff, -jnp.inf))
        kd = k_[..., None, :, :] * decay
        a_ = jnp.sum(k_[..., :, None, :] * kd, -1)
        b_ = jnp.sum(q_[..., :, None, :] * kd, -1)
        # strictly lower for the solve: the diagonal pairs i with itself
        n_ = beta_[..., None] * jnp.where(jnp.tril(lower, -1), a_, 0.0)
        return n_, b_

    n, bm = jax.lax.map(pairwise, (q, k, gc, beta))
    tinv = _unit_lower_inverse(n)  # [NC, B, H, C, C]
    g_end = gc[..., -1:, :]
    kg, qg = k * jnp.exp(gc), q * jnp.exp(gc)
    k_end = k * jnp.exp(g_end - gc)

    def one(s, xs):
        kg_, qg_, k_end_, v_, beta_, tinv_, bm_, g_end_ = xs
        mm = functools.partial(jnp.matmul, precision=_HI)
        u = mm(tinv_, beta_[..., None] * (v_ - mm(kg_, s)))
        o = mm(qg_, s) + mm(bm_, u)
        s = jnp.exp(g_end_)[..., 0, :, None] * s \
            + mm(jnp.swapaxes(k_end_, -1, -2), u)
        return s, o

    s, o = jax.lax.scan(one, s0, (kg, qg, k_end, v, beta, tinv, bm, g_end))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)  # [B, NC, C, H, dv]
    return o.reshape(b, t, h, -1), s


# --------------------------------------------------------------------------
# The kernel
# --------------------------------------------------------------------------

def _dot(a, b):
    return jnp.dot(a, b, precision=_HI, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a [M, K] against the rows of b [N, K] -> [M, N]."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _column(own: bool, g_n, k_n, q_n, beta_n, r_n, o_n, g_j, k_j, u_j, at):
    """Column j of a diagonal block on one strip of its rows (g, k, q,
    beta, the right-hand side r and the output o of the strip's 8 rows;
    row j's g and k, and u_j, which is final): the strip's part of
    ``r -= N[:, j] u_j`` and ``o += B[:, j] u_j``. ``own``: the strip
    holds row j itself, at its row ``at``: rows above it take no part,
    and j pairs with itself in B alone. -> (r, o)."""
    diff = g_n - g_j
    if own:
        row = jax.lax.broadcasted_iota(jnp.int32, (g_n.shape[0], 1), 0)
        diff = jnp.where(row >= at, diff, -jnp.inf)
    kd = k_j * jnp.exp(diff)  # k_j e^(G_i - G_j), at most |k_j|
    a_j = jnp.sum(k_n * kd, axis=-1, keepdims=True)  # [strip, 1]
    b_j = jnp.sum(q_n * kd, axis=-1, keepdims=True)
    n_j = beta_n * a_j
    if own:
        n_j = jnp.where(row > at, n_j, 0.0)
    return r_n - n_j * u_j, o_n + b_j * u_j


# (jitted: a chunk has 96 of them a head; traced twice, not 96 times, the
# kernel's trace is a fifth as long. Lowered, they are written out.)
_column_own = jax.jit(functools.partial(_column, True))
_column_below = jax.jit(functools.partial(_column, False))


def _head(q, k, v, g_sum, beta, s, u_ref):
    """One head's chunk. q, k, g_sum [C, dk], v [C, dv], beta [C, 1],
    s [dk, dv]; ``u_ref`` [C, dv] a VMEM scratch for the pseudo-values.
    -> (o [C, dv], s [dk, dv])."""
    c, dk = q.shape
    e = jnp.exp(g_sum)
    from_s = _dot(jnp.concatenate([k * e, q * e], axis=0), s)  # [2C, dv]
    rest = beta * (v - from_s[:c])
    sub = min(SUB, c)  # (a toy configuration's chunk is one sub-block)
    strip = min(STRIP, sub)
    outs = []
    for first in range(0, c, sub):
        rows = slice(first, first + sub)
        g_i, k_i, q_i, beta_i = g_sum[rows], k[rows], q[rows], beta[rows]
        r_i, o_i = rest[rows], from_s[c + first:c + first + sub]
        if first:
            # the earlier sub-blocks' columns, against this one's first row
            g_r = g_sum[first:first + 1]
            to_r = jnp.exp(g_i - g_r)
            ab = _dot_nt(jnp.concatenate([k_i * to_r, q_i * to_r], axis=0),
                         k[:first] * jnp.exp(g_r - g_sum[:first]))
            ab_u = _dot(ab, u_ref[:first, :])  # [2 sub, dv]
            r_i = r_i - beta_i * ab_u[:sub]
            o_i = o_i + ab_u[sub:]
        # the diagonal block a column j at a time, in strips of 8 rows (a
        # tile): a strip above j's has no row i >= j; j's own is masked
        cut = [slice(i, i + strip) for i in range(0, sub, strip)]
        g_s, k_s, q_s, beta_s, r_s, o_s = (
            [a[rows_] for rows_ in cut]
            for a in (g_i, k_i, q_i, beta_i, r_i, o_i))
        for j in range(sub):
            own, at = divmod(j, strip)
            g_j, k_j = g_s[own][at:at + 1], k_s[own][at:at + 1]
            u_j = r_s[own][at:at + 1]  # final: no earlier row is left in it
            for n in range(own, len(cut)):
                r_s[n], o_s[n] = (_column_own if n == own else _column_below)(
                    g_s[n], k_s[n], q_s[n], beta_s[n], r_s[n], o_s[n],
                    g_j, k_j, u_j, at)
        u_ref[rows, :] = jnp.concatenate(r_s, axis=0)
        outs += o_s
    g_end = g_sum[c - 1:c]
    # e^(G_C) down the sublanes, as S has dk: the row over dk, turned
    decay = jnp.broadcast_to(jnp.exp(g_end), (dk, dk)).T[:, :1]
    s = decay * s + _dot((k * jnp.exp(g_end - g_sum)).T, u_ref[...])
    return jnp.concatenate(outs, axis=0), s


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, s_out_ref,
            s_ref, g_sum_ref, u_ref, *, hb: int):
    chunk = pl.program_id(2)
    dk, dv = s_ref.shape[1:]

    @pl.when(chunk == 0)
    def _():
        s_ref[...] = s0_ref[...]

    # the running sum of g down the chunk's rows, every head at once
    g_sum_ref[0:1, :] = g_ref[0:1, :]
    for i in range(1, g_ref.shape[0]):
        g_sum_ref[i:i + 1, :] = g_sum_ref[i - 1:i, :] + g_ref[i:i + 1, :]
    lane = jax.lax.broadcasted_iota(jnp.int32, beta_ref.shape, 1)
    first = pl.program_id(1) * hb

    def head(h, _):
        # (this head's beta out of [C, H]: a masked lane sum)
        beta = jnp.sum(jnp.where(lane == first + h, beta_ref[...], 0.0),
                       axis=-1, keepdims=True)
        at_k = pl.ds(pl.multiple_of(h * dk, dk), dk)
        at_v = pl.ds(pl.multiple_of(h * dv, dv), dv)
        o, s = _head(q_ref[:, at_k], k_ref[:, at_k], v_ref[:, at_v],
                     g_sum_ref[:, at_k], beta, s_ref[h], u_ref)
        o_ref[:, at_v] = o
        s_ref[h] = s
        return 0

    jax.lax.fori_loop(0, hb, head, 0)

    @pl.when(chunk == pl.num_programs(2) - 1)
    def _():
        s_out_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "hb", "interpret"))
def _kda_chunk(q, k, v, g, beta, s0, *, chunk: int, hb: int,
               interpret: bool):
    """The kernel's call: the arguments of :func:`kda_chunked`. (Jitted
    by itself so that a program traces and lowers the kernel's thousand
    lines once a shape, not once a layer.)"""
    b, t, h, dk = q.shape
    dv = v.shape[-1]

    def rows(width):
        return pl.BlockSpec((None, chunk, hb * width),
                            lambda i, j, c: (i, c, j))

    state = pl.BlockSpec((None, hb, dk, dv), lambda i, j, c: (i, j, 0, 0))
    # a row's heads end to end, [B, T, H * dk], as the projection leaves
    # them: a head's [C, dk] is whole tiles of the block
    o, s = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        out_shape=(jax.ShapeDtypeStruct((b, t, h * dv), jnp.float32),
                   jax.ShapeDtypeStruct(s0.shape, jnp.float32)),
        grid=(b, h // hb, t // chunk),
        in_specs=[rows(dk), rows(dk), rows(dv), rows(dk),
                  pl.BlockSpec((None, chunk, h), lambda i, j, c: (i, c, 0)),
                  state],
        out_specs=[rows(dv), state],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32),
                        pltpu.VMEM((chunk, hb * dk), jnp.float32),
                        pltpu.VMEM((chunk, dv), jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="kda_chunk",
    )(q.reshape(b, t, h * dk), k.reshape(b, t, h * dk),
      v.reshape(b, t, h * dv), g.reshape(b, t, h * dk), beta, s0)
    return o.reshape(b, t, h, dv), s


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _kernel_form(q, k, v, g, beta, s0, chunk, hb, interpret):
    return _kda_chunk(q, k, v, g, beta, s0, chunk=chunk, hb=hb,
                      interpret=interpret)


def _kernel_form_fwd(q, k, v, g, beta, s0, chunk, hb, interpret):
    return (_kernel_form(q, k, v, g, beta, s0, chunk, hb, interpret),
            (q, k, v, g, beta, s0))


def _kernel_form_bwd(chunk, hb, interpret, inputs, cotangents):
    """The XLA body's derivative (no cell trains these blocks)."""
    return jax.vjp(functools.partial(kda_chunked, chunk=chunk),
                   *inputs)[1](cotangents)


_kernel_form.defvjp(_kernel_form_fwd, _kernel_form_bwd)


def kda_chunk(q, k, v, g, beta, s0, *, chunk: int,
              use_kernel: bool | None = None, interpret: bool = False,
              heads: int | None = None):
    """T tokens of the delta rule onto the state, chunk by chunk: q, k,
    g [B, T, H, dk], v [B, T, H, dv], beta [B, T, H], s0 [B, H, dk, dv],
    all float32; T a multiple of ``chunk``. A token with ``beta`` 0 and
    ``g`` 0 leaves the state as it was (padding). -> (o [B, T, H, dv],
    the state after the last token).

    ``use_kernel=None`` takes the backend's: the Pallas kernel on a TPU
    where ``dk`` and ``dv`` are whole lanes (and a chunk whole
    sub-blocks), :func:`kda_chunked` elsewhere. ``interpret=True`` runs
    the kernel in the Pallas interpreter (never inferred). ``heads``
    overrides the heads a block (the chip's tuning sweep and the
    tests). A differentiated call takes :func:`kda_chunked`'s
    derivative."""
    h, dk = q.shape[2:]
    dv = v.shape[-1]
    if use_kernel is None:
        use_kernel = interpret or (
            jax.default_backend() == "tpu" and dk % 128 == 0
            and dv % 128 == 0 and chunk % SUB == 0)
    if not use_kernel:
        return kda_chunked(q, k, v, g, beta, s0, chunk=chunk)
    sub = min(SUB, chunk)
    if chunk % sub or sub % min(STRIP, sub):
        raise ValueError(f"the kernel cuts a chunk into sub-blocks of {SUB} "
                         f"rows and those into strips of {STRIP}: {chunk}")
    return _kernel_form(q, k, v, g, beta, s0, chunk,
                        block_heads(h, heads or BLOCK_HEADS), interpret)
