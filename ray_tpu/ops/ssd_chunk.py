"""The chunked SSD scan of a Mamba-2 prefill: T tokens onto a state.

The prefill of a Mamba-2 layer (``models/granite.py``,
``models/nemotron.py``) is the chunked
form of :func:`ray_tpu.ops.ssd_step.ssd_recurrence` (Dao & Gu,
arXiv:2405.21060, section 6). With ``l_t = dt_t A`` the log decay of row
t and head h (<= 0), L the running sum of l from the chunk's start, and
``X = dt x``; inside a chunk of Q rows, H0 the state at its start::

    Y = ((C B^T) * e^(L_t - L_s)[t >= s]) X  +  e^(L_t) (C H0^T)
    H' = e^(L_Q) H0 + (X e^(L_Q - L))^T B

One ``C_g B_g^T`` of ``[Q, Q]`` serves every head of group g (``B`` and
``C`` come a GROUP of heads, ``[.., G, N]``: Granite's one group serves
all heads, Nemotron's eight serve eight heads each; head j reads group
``j // (H / G)``). The decays
are taken pairwise, ``e^(L_t - L_s) <= 1`` masked BEFORE the
exponential, and against the chunk's end, never as ``e^(-L_s)``: a sum
of l under -87 inside one chunk is ordinary (dt to 0.1 and more, A to
-16), and the naive quotient is inf * 0 there.

:func:`ssd_chunked` is **the XLA body**, and the only form there is: a
Pallas kernel that carries the state on the chip over a segment's
chunks, as ``ops/kda_chunk.py`` does for the delta rule, is not written
(``ROADMAP.md`` A7). Every chunk's inner work is batched over the chunks
of the call (what is alive at once: the pairwise decays, float32 ``[T /
Q, H, Q, Q]``, and a state a CHUNK, ``[T / Q, H, P, N]``, never one a
row); the states at the chunks' starts come from a ``lax.scan`` over the
chunks that only scales and adds. Products are float32 at
``Precision.HIGHEST``; a row of padding (``dt`` 0) decays nothing and
adds nothing, so a chunk of padding hands the state on bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def ssd_chunked(x, dt, a, b, c, h0, *, chunk: int):
    """x [B, T, H, P], dt [B, T, H] (>= 0; 0 on a padding row), a [H]
    (< 0), b, c [B, T, G, N] (heads ``g H / G ..`` read group g's), h0
    [B, H, P, N]; all float32, T whole chunks. -> (y [B, T, H, P]
    without the skip, the state after row T)."""
    bsz, t, nh, p = x.shape
    g, n = b.shape[-2:]
    if nh % g:
        raise ValueError(f"{g} groups do not divide {nh} heads")
    nc = t // chunk
    mm = functools.partial(jnp.einsum, precision=_HI,
                           preferred_element_type=jnp.float32)
    cs = jnp.cumsum((dt * a).reshape(bsz, nc, chunk, nh), axis=2)
    # (a head's axis as [G, H / G] wherever it meets a group's B or C)
    xdt = (x * dt[..., None]).reshape(bsz, nc, chunk, g, nh // g, p)
    bq = b.reshape(bsz, nc, chunk, g, n)
    cq = c.reshape(bsz, nc, chunk, g, n)
    # rows t >= s of a chunk, a head: e^(L_t - L_s)
    by_head = jnp.moveaxis(cs, 3, 2).reshape(
        bsz, nc, g, nh // g, chunk)  # [B, nc, G, H / G, Q]
    seen = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
    decay = jnp.exp(jnp.where(
        seen, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    scores = mm("bctgn,bcsgn->bcgts", cq, bq)[:, :, :, None] * decay
    y = mm("bcghts,bcsghp->bctghp", scores, xdt)
    # what each chunk adds to the state at its own end, and the states
    # at the chunks' starts
    to_end = jnp.exp(cs[:, :, -1:] - cs).reshape(
        bsz, nc, chunk, g, nh // g)  # [B, nc, Q, G, H / G]
    added = mm("bcsghp,bcsgn->bcghpn", xdt * to_end[..., None], bq)
    whole = jnp.exp(cs[:, :, -1]).reshape(bsz, nc, g, nh // g)

    def over(h, chunk_):
        add, keep = chunk_
        return h * keep[..., None, None] + add, h

    last, starts = jax.lax.scan(
        over, h0.reshape(bsz, g, nh // g, p, n),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)))
    y = y + mm("bctgn,bcghpn->bctghp", cq, jnp.moveaxis(starts, 0, 1)) \
        * jnp.exp(cs).reshape(bsz, nc, chunk, g, nh // g)[..., None]
    return y.reshape(bsz, t, nh, p), last.reshape(bsz, nh, p, n)
