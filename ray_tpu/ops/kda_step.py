"""One token of the delta rule on every slot's state, in place.

(Two recurrences run in the tree. The delta rule: Ling's and Solar-
Open2's KDA layers, a decode step here, a prefill's chunkwise form in
``ops/kda_chunk.py``, each a Pallas kernel on a TPU where a head is whole
lanes, an XLA body elsewhere. SSD: Granite's Mamba-2, ``ops/ssd_*.py``.)

A KDA layer (``models/ling.py`` at 32 heads; ``models/solar.py`` at 64,
with ``beta`` in (0, 2) and a log decay ``g`` unbounded below: the
kernel takes ``g`` and ``beta`` as given, each block's ``_kda_inputs``
makes its own) keeps a float32 state ``S [slots, H,
dk, dv]``; a decode step decays it a channel, takes the state's
prediction for the new key, adds the delta rule's outer product and
reads the output::

    S <- S * exp(g)[:, None]
    pred = sum_dk S * k[:, None]
    S <- S + (beta k)[:, None] * (v - pred)[None, :]
    o = sum_dk S * q[:, None]

The least a layer-step can do is read ``S`` once and write it once. On a
TPU that is what the Pallas kernel ``kda_step`` does (``custom-call/
2out/kda_step`` in a device trace): a grid step brings a block of heads
of one slot in, computes the four lines in float32 elementwise
arithmetic (no matrix unit: a product is never rounded) and writes the
block back **to the buffer it came from** (``input_output_aliases``: the
engine donates its state to the decode chunk, so nothing is copied). A
slot that is not ``active`` gets back what was read, bit for bit.
Written as plain XLA (:func:`kda_recurrence` and a ``where``, the path
off the TPU and the tests' second opinion) the compiler makes several
passes over ``S`` and moves it between memories in quarter slices around
them.

The per-channel vectors (``exp(g)``, ``k``, ``beta k``, ``q``) reach the
kernel with ``dk`` in the lanes, as XLA leaves them, and are turned once
a block (one transpose of the block's 4 x heads rows) so that ``dk``
lies along the sublanes, as a head's state has it; a head then takes
its four columns, each broadcast along the lanes. ``interpret=True`` (a
test's explicit choice) runs the kernel in the Pallas interpreter.

The body and the heads a block, read on the chip (TPU v5 lite, my chip
run, PR 41; 32 slots x 32 heads x 128 x 128 float32, a call in a loop of
200, best of three; state and output equal to the XLA body's, error
0.0, in every row): a Pallas copy of ``S`` in place 214.6 us (the
pipeline's floor; the bytes at the HBM's peak are 163.9); this body at
8 / 16 / 32 heads a block 242.4 / **218.1** / 218.4; the same lines
over the whole block at once with the vectors as ``[heads, dk, 1]`` (a
lanes-to-sublanes reshape Mosaic makes a head at a time) 304.2 / 303.1 /
299.9; the XLA body 410.6 (400.9 with half the slots inactive, the
kernel 217.5: an inactive slot's bytes move all the same). PR 40's
probes read the same floor and 271 us through the matrix unit, 2.4e-7
off. So: 16 heads a block, elementwise, a head at a time.

The call carries no ``cost_estimate``, on purpose. Told that the call
moves 134 MB, XLA's memory-space assignment took the state for a
profitable thing to hold in VMEM: in the cell's decode chunk it brought
four of the six layers' states there in quarter slices ahead of their
call and copied them back behind it (16 ``slice-start`` + 4
``copy-start`` of an ``f32[8 | 32, 32, 128, 128]`` in the compiled
text). The kernel then read 149 us a call, under what the HBM allows,
and the chunk waited for the copies instead: 153.3 ms for the XLA
body's 154.7, 2,923 / 2,939 tokens/s for 2,906 / 2,919. Without the
estimate no state moves but through the kernel: 213.7 us a call, a
chunk of 142.4 ms, 3,099-3,125 tokens/s for 2,878-2,897 (my chip
runs, PR 41; ``tests/test_tpu_compile_ling.py`` pins the text).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# heads of a slot a grid step holds; see the module docstring
BLOCK_HEADS = 16
_VMEM_LIMIT = 64 * 1024 * 1024


def kda_recurrence(s, q, k, v, g, beta):
    """One token of the delta rule on the state s [B, H, dk, dv]
    (float32, elementwise: no product is rounded). q, k, g [B, H, dk];
    v [B, H, dv]; beta [B, H]. -> (s, o [B, H, dv])."""
    s = s * jnp.exp(g)[..., None]
    pred = jnp.sum(s * k[..., None], axis=-2)
    s = s + (beta[..., None] * k)[..., None] * (v - pred)[..., None, :]
    return s, jnp.sum(s * q[..., None], axis=-2)


def block_heads(h: int, most: int = BLOCK_HEADS) -> int:
    """Heads a block: the largest divisor of ``h`` that is at most
    ``most``."""
    return max(n for n in range(1, min(h, most) + 1) if h % n == 0)


def _kernel(active_ref, w_ref, v_ref, s_ref, s_out_ref, o_ref, *, hb: int):
    dk = w_ref.shape[-1]
    # the block's 4 x hb vectors as the rows of one matrix (padded to
    # whole lanes), turned: column i * hb + h is head h's i-th vector
    # with dk along the sublanes, as a head's state has it
    w = w_ref[...].reshape(4 * hb, dk)
    pad = -4 * hb % 128
    if pad:
        w = jnp.concatenate([w, jnp.zeros((pad, dk), w.dtype)], axis=0)
    w = w.T
    active = active_ref[pl.program_id(0)] != 0
    for h in range(hb):
        decay, k, beta_k, q = (w[:, i * hb + h:i * hb + h + 1]
                               for i in range(4))  # [dk, 1]
        s = s_ref[h]  # [dk, dv]
        new = s * decay
        pred = jnp.sum(new * k, axis=0, keepdims=True)  # [1, dv]
        new = new + beta_k * (v_ref[h:h + 1, :] - pred)
        o_ref[h:h + 1, :] = jnp.sum(new * q, axis=0, keepdims=True)
        s_out_ref[h] = jnp.where(active, new, s)


def _kda_step(s, w, v, active, *, hb: int, interpret: bool):
    """The kernel's call. s [B, H, dk, dv]; w [B, 4, H, dk]: exp(g), k,
    beta k, q; v [B, H, dv]; active [B] int32. -> (s, o [B, H, dv])."""
    b, h, dk, dv = s.shape

    def state(i, j, active_ref):
        return i, j, 0, 0

    def rows(i, j, active_ref):
        return i, j, 0

    s_block = pl.BlockSpec((None, hb, dk, dv), state)
    o_block = pl.BlockSpec((None, hb, dv), rows)
    return pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        out_shape=(jax.ShapeDtypeStruct(s.shape, s.dtype),
                   jax.ShapeDtypeStruct((b, h, dv), s.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((None, 4, hb, dk),
                                   lambda i, j, active_ref: (i, 0, j, 0)),
                      o_block, s_block],
            out_specs=[s_block, o_block],
            grid=(b, h // hb),
        ),
        # (operand 0 is the prefetched ``active``)
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        # (no ``cost_estimate``: with one, XLA moves the state into
        # VMEM round the call; module docstring)
        interpret=interpret,
        name="kda_step",
    )(active, w, v, s)


def kda_step(s, q, k, v, g, beta, active, *,
             use_kernel: bool | None = None, interpret: bool = False,
             heads: int | None = None):
    """A decode step of the recurrence on the slots' state: s [B, H, dk,
    dv] float32; q, k, g [B, H, dk], v [B, H, dv], beta [B, H] float32;
    ``active`` [B] bool. -> (s: updated where ``active``, kept bit for
    bit elsewhere; o [B, H, dv], every slot's).

    ``use_kernel=None`` takes the backend's: the Pallas kernel on a TPU
    where ``dk`` and ``dv`` are whole lanes, :func:`kda_recurrence` and a
    ``where`` elsewhere. ``interpret=True`` runs the kernel in the Pallas
    interpreter (never inferred). ``heads`` overrides the heads a block
    (the chip's tuning sweep and the tests)."""
    h, dk, dv = s.shape[1:]
    if use_kernel is None:
        use_kernel = interpret or (
            jax.default_backend() == "tpu" and dk % 128 == 0
            and dv % 128 == 0)
    if not use_kernel:
        new, o = kda_recurrence(s, q, k, v, g, beta)
        return jnp.where(active[:, None, None, None], new, s), o
    w = jnp.stack([jnp.exp(g), k, beta[..., None] * k, q], axis=1)
    return _kda_step(s, w, v, active.astype(jnp.int32),
                     hb=block_heads(h, heads or BLOCK_HEADS),
                     interpret=interpret)
