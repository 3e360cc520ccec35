"""A layer's products that meet the ``tp`` axis, their transfer inside.

Megatron's layout leaves two partial sums a layer (``wo``'s, ``w_down``'s)
that GSPMD adds with a synchronous all-reduce of the whole activation:
nothing independent stands between the product and the norm that needs
its sum, so the link's time is the step's. Here the activation between
the products lives sharded by ROWS (its sequence) over the tp group, and
each product that meets the axis is cut in as many pieces as the group
has chips, one piece's transfer (a ``ppermute``: a
``collective-permute-start`` / ``-done`` pair the compiler schedules
asynchronously) riding behind another piece's product:

- :func:`gather`: the chips' rows times this chip's COLUMNS (``wq`` /
  ``wk`` / ``wv``, ``w_gate`` / ``w_up``): a chip sends the rows it holds
  on round the ring while it multiplies them, then multiplies what
  arrived: an all-gather that is never waited for whole.
- :func:`scattered`: every row's partial product over this chip's ROWS of
  the weight (``wo``, ``w_down``), summed over the group and left with
  the chip that owns the rows: a chip multiplies first the piece whose
  sum ends furthest round the ring, sends that partial sum on while it
  multiplies the next piece, adds what arrives, and ends with its own
  rows, summed: a reduce-scatter whose transfers ran behind products.

Both work on PIECES in ring order: piece ``r`` of a list is the rows of
chip ``(i - r) % n``, ``i`` this chip's place in the group, so what is
made of ``gather``'s pieces feeds ``scattered`` as it comes (the MLP), and
:func:`in_order` / :func:`pieces` trade a list for the whole sequence in
its own order (what attention needs, and gives).

The functions ask the ambient mesh what they are in: inside a region
manual over tp (:func:`over_tp` makes it: a ``jax.shard_map`` over that
one axis, every other axis left to GSPMD, so fsdp's weight gathers stay
where they were) they are the ring; anywhere else (no mesh, tp = 1, a
pipeline stage) a list has one piece and they are the plain products.
Autodiff gives the backward its mirror image: a ``ppermute``'s transpose
is a ``ppermute``, a gathered product's a scattered one.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

AXIS = "tp"

# in_specs / out_specs of a region (:func:`over_tp`), by what the tp
# group cuts: an activation's rows [B, T, D]; an activation's columns
# [B, T, N] or heads [B, T, H, hd]; a weight's columns or rows [K, N]
ROWS = P(None, AXIS)
COLUMNS = P(None, None, AXIS)
W_COLUMNS = P(None, AXIS)
W_ROWS = P(AXIS)
WHOLE = P()


def ways() -> int:
    """Over how many chips :func:`over_tp` would cut a layer's rows
    here: the ambient mesh's tp, or 1 where the products stay plain (no
    mesh, tp = 1, or inside a region that is already manual: a
    ``parallel/pipeline.py`` stage, whose products GSPMD partitions)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.manual_axes:
        return 1
    return dict(mesh.shape).get(AXIS, 1)


def over_tp(fn, n: int, *, in_specs, out_specs):
    """``fn`` as a region manual over tp alone, or ``fn`` itself where
    the caller keeps the rows whole (``n`` = 1: :func:`ways`, or its own
    reason)."""
    if n == 1:
        return fn
    return jax.shard_map(fn, axis_names={AXIS}, in_specs=in_specs,
                         out_specs=out_specs)


def _ring() -> int:
    """The tp group's size inside a region manual over it, else 1."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh.shape[AXIS] if AXIS in mesh.manual_axes else 1


def _send_on(x, n):
    return jax.lax.ppermute(x, AXIS, [(j, (j + 1) % n) for j in range(n)])


def gather(x):
    """x [B, T / n, D]: this chip's rows -> n pieces in ring order: this
    chip's rows, then each other chip's as they come round. A piece is
    sent on before anything is multiplied by it, so its transfer rides
    behind its own products: ``[x_r @ w for x_r in gather(x)]`` is the
    group's rows times this chip's columns, an all-gather that is never
    waited for whole."""
    n = _ring()
    out = [x]
    for _ in range(n - 1):
        out.append(_send_on(out[-1], n))
    return out


def scattered(a, w):
    """a: n pieces in ring order, each [B, T / n, K / n] (every chip's
    rows, this chip's part of the contraction); w [K / n, N]: this
    chip's rows of the weight. -> [B, T / n, N]: this chip's rows of
    ``sum over the group of a @ w``. A partial sum travels on while the
    next piece is multiplied."""
    n = len(a)
    acc = a[1 % n] @ w
    for s in range(1, n):
        acc = _send_on(acc, n) + a[(s + 1) % n] @ w
    return acc


def _piece_of(c, n):
    """Which piece of a ring-ordered list holds rows ``c`` of the
    sequence (and, the map being its own inverse, the reverse)."""
    return (jax.lax.axis_index(AXIS) + n - c) % n


def _joined(a):
    # a select among the pieces, which fuses into what reads the result
    # (an update of a buffer at a traced offset would write it twice)
    n = len(a)
    return jax.numpy.concatenate(
        [jax.lax.select_n(_piece_of(c, n), *a) for c in range(n)], axis=1)


def _cut(x, n):
    # a slice at a traced offset, which fuses into the product it feeds
    rows = x.shape[1] // n
    return [jax.lax.dynamic_slice_in_dim(
        x, _piece_of(r, n) * rows, rows, axis=1) for r in range(n)]


# The two are each other's inverse, a permutation of rows, so each
# other's transpose: said by hand, because autodiff's own transposes (a
# slice's: an update of zeros; a select's: selects against zeros) cost
# the backward a pass over the activation each.
@jax.custom_vjp
def _in_order(*a):
    return _joined(a)


_in_order.defvjp(lambda *a: (_joined(a), None),
                 lambda _, g: tuple(_cut(g, _ring())))


@jax.custom_vjp
def _pieces(x):
    return tuple(_cut(x, _ring()))


_pieces.defvjp(lambda x: (tuple(_cut(x, _ring())), None),
               lambda _, g: (_joined(g),))


def in_order(a):
    """Pieces in ring order, each [B, T / n, ...] -> [B, T, ...] in the
    sequence's own order: rows ``c`` are piece ``(i - c) % n``."""
    return a[0] if len(a) == 1 else _in_order(*a)


def pieces(x):
    """[B, T, ...] in the sequence's own order -> n pieces in ring
    order, each [B, T / n, ...]."""
    return [x] if _ring() == 1 else list(_pieces(x))
