"""What the blocks that unroll their layers share, one copy
(``models/ling.py``, ``exaone.py``, ``instella.py``, ``solar.py``,
``mimo.py``, ``granite.py``, ``dots.py``, ``glm_dsa.py``,
``glm_next.py``, ``lfm2.py``): the
expert layer of a device that holds a part of its experts with the
router in front of it (DeepSeek-V3's routing), a layer's MLP around it,
the head, the loss, how the leaves these read are drawn, and how a
long prompt's tokenwise work is cut into row segments.

Sigmoid scores in float32 (softmax scores for a block whose
configuration says ``router_softmax``), a bias added for selection only,
``topk_group`` of ``n_group`` groups kept by the sum of their two best,
the ``top_k`` best of those chosen, their unbiased scores renormalised
(by their sum, or by ``their sum + norm_topk_eps`` where a configuration
states that field: LFM2's published ``1e-6``, :func:`route`; without it
the older blocks' programs are their parent's text)
and scaled; a shared expert beside them (``shared_d_ff`` 0: none, no
leaves and no work). ``held`` = (first, count) tells
the layer which experts live here: it routes over all of them and
computes the part of the result that its own give (:func:`moe`); what
the others would add is left out. Where it holds an eighth of the
experts or less and the call is a prompt's, it counts the assignments
its own got and, when they fit a capacity that the shapes give (twice
its uniform share, :func:`compact_rows`), gathers and multiplies those
rows alone before it sums a token's experts; when they do not fit it
works at every assignment's row, as a decode step and a device that
holds a quarter or all of the experts always do. The capacity chooses
the lines that compute the result, never what is in it: nothing is
dropped, and the bits are the same either way.

``cfg`` is any configuration with the fields read here: ``n_experts``,
``n_group``, ``topk_group``, ``top_k``, ``routed_scaling_factor``,
``held``, ``compute_dtype`` and ``rms_eps``; the makers of leaves read
``d_model``, ``d_ff``, ``shared_d_ff``, ``dense_d_ff`` (a block with a
dense MLP), ``vocab_size``, ``n_layers`` and ``published_layers`` too.
:func:`draw` makes a leaf in its serving type block by block: a model
that holds billions of parameters as bf16 cannot make them as float32
masters first.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ray_tpu.ops.losses import softmax_cross_entropy
from ray_tpu.ops.norms import rms_norm

_BLOCK_ELEMS = 1 << 22  # a leaf is drawn in float32 blocks of this many
# the most rows of a prompt whose tokenwise work is done at once (a
# float32 ``[rows, 64, 128]`` array is then 67 MB): segment_rows
SEGMENT_ROWS = 2048


class HeldExperts:
    """What the functions here read of a configuration besides its
    fields, derived from them. The blocks' frozen dataclasses inherit
    it; it has no field, so their hash and equality as a static
    argument are their own fields'."""

    # what :func:`moe` makes the scores of its router's logits by:
    # DeepSeek-V3's sigmoid, or for a block that says so a softmax over
    # all experts (``models/granite.py``: with no bias, one group and a
    # scaling of 1, :func:`route` then gives the ``top_k`` of the logits
    # and a softmax over the chosen)
    router_softmax = False

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def held(self) -> tuple:
        """(first, count): the experts this device holds."""
        return self.held_experts or (0, self.n_experts)


def draw(key, shape, scale: float, dtype):
    """Normal(0, scale) of ``shape`` in ``dtype``, drawn in float32
    blocks along the leading axis and rounded block by block: a 250 M
    leaf never exists in float32. The bits come from the device's own
    generator (an ``rbg`` key made of ``key``): threefry in XLA
    operations takes over a minute for 5 B numbers on the chip."""
    data = jax.random.key_data(key) if jnp.issubdtype(
        key.dtype, jax.dtypes.prng_key) else key
    key = jax.random.wrap_key_data(
        jnp.concatenate([data, data ^ jnp.uint32(0x9E3779B9)]), impl="rbg")
    n = shape[0]
    rows = max(1, _BLOCK_ELEMS // max(1, math.prod(shape[1:])))
    rows = max(r for r in range(1, min(rows, n) + 1) if n % r == 0)
    if rows == n:
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)
    blocks = jax.lax.map(
        lambda k: (jax.random.normal(k, (rows, *shape[1:]), jnp.float32)
                   * scale).astype(dtype),
        jax.random.split(key, n // rows))
    return blocks.reshape(shape)


def makers(cfg, keys):
    """-> (``mat``, ``around_one``), the two ways a block's
    ``init_params`` draws a leaf, each from the next of ``keys`` (the
    iterator the block draws its own leaves from too: the calls' order
    is the draws'). ``mat(*shape, out=False)``: a matrix in the compute
    dtype, normal / sqrt(fan_in), and with ``out`` (it writes into the
    residual stream) scaled by (2 x depth)^-1/2 besides, depth being
    the model's own (``published_layers``) where the configuration is
    cut in depth (``ling.init_params`` says what for).
    ``around_one(*shape)``: a norm's scale, float32, drawn around 1."""
    out_scale = (2 * (cfg.published_layers or cfg.n_layers)) ** -0.5

    def mat(*shape, out=False):
        scale = shape[-2] ** -0.5 * (out_scale if out else 1.0)
        return draw(next(keys), shape, scale, cfg.compute_dtype)

    def around_one(*shape):
        return 1.0 + 0.25 * jax.random.normal(next(keys), shape, jnp.float32)

    return mat, around_one


def init_dense(cfg, mat) -> dict:
    """A dense SwiGLU's leaves, as :func:`mlp_layer` reads them."""
    d, f = cfg.d_model, cfg.dense_d_ff
    return {"w_gate": mat(d, f), "w_up": mat(d, f),
            "w_down": mat(f, d, out=True)}


def init_experts(cfg, mat, keys) -> dict:
    """An expert layer's leaves, as :func:`moe` reads them: the router
    over ALL experts (with its selection bias where the scores are
    sigmoids: a softmax router has none), the held experts' matrices,
    the shared expert (where the model has one: ``shared_d_ff`` > 0)."""
    d, f, fs = cfg.d_model, cfg.d_ff, cfg.shared_d_ff
    _, count = cfg.held
    leaves = {"router": mat(d, cfg.n_experts)}
    if not cfg.router_softmax:
        # (small against the scores' spread of 0.2: the top 3% of
        # sigmoids lie where a bias of 0.1 is a standard deviation
        # of the logits, and one expert in eight took most rows)
        leaves["router_bias"] = 0.01 * jax.random.normal(
            next(keys), (cfg.n_experts,), jnp.float32)
    leaves.update({"w_gate": mat(count, d, f), "w_up": mat(count, d, f),
                   "w_down": mat(count, f, d, out=True)})
    if fs:
        leaves.update({"shared_gate": mat(d, fs), "shared_up": mat(d, fs),
                       "shared_down": mat(fs, d, out=True)})
    return leaves


def init_model(cfg, mat, around_one, keys, layers: list) -> dict:
    """The whole tree round a block's ``layers`` (drawn before it is
    called): the embedding, the final norm and the head, as
    :func:`logits` reads them."""
    d = cfg.d_model
    return {
        "embed": draw(next(keys), (cfg.vocab_size, d), 1.0,
                      cfg.compute_dtype),
        "layers": layers,
        "final_norm": around_one(d),
        "lm_head": mat(d, cfg.vocab_size),
    }


def gated(gate, up, limit=None):
    """A SwiGLU's middle: ``silu(gate) * up``, and where the model
    states a ``swiglu_limit`` ``silu(min(gate, limit)) * clip(up, -limit,
    limit)``."""
    if limit is not None:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def swiglu(x, w_gate, w_up, w_down, limit=None):
    return gated(x @ w_gate, x @ w_up, limit) @ w_down


def swiglu_limit(cfg):
    """The clamp a configuration states for every SwiGLU, or None."""
    return getattr(cfg, "swiglu_limit", None)


def route(cfg, scores, bias):
    """The router's choice from its sigmoid ``scores`` [..., E] float32:
    -> (weights [..., top_k] float32, expert ids [..., top_k]). The bias
    moves the SELECTION only: groups are ranked by the sum of their two
    best biased scores, the ``top_k`` best biased scores of the kept
    groups are chosen (``lax.top_k``: exactly top_k, the lower index on
    a tie), and the weights are the chosen experts' unbiased scores,
    renormalised to 1 and scaled. With one group (``n_group`` 1,
    ``topk_group`` 1) it is kept and nothing is masked: the plain
    ``top_k`` of the biased scores.

    Where the configuration states ``norm_topk_eps`` (``models/lfm2.py``:
    transformers' ``Lfm2MoeSparseMoeBlock`` divides the chosen scores by
    ``their sum + 1e-6``) the sum gains it before it divides; a
    configuration without the field (every older block's) runs the old
    line and its programs lower to their parent's text
    (``tests/test_moe.py -k lowered_text``)."""
    e, ng = cfg.n_experts, cfg.n_group
    biased = scores + bias
    grouped = biased.reshape(*biased.shape[:-1], ng, e // ng)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, kept = jax.lax.top_k(group_score, cfg.topk_group)
    keep = jnp.any(jax.nn.one_hot(kept, ng, dtype=jnp.bool_), axis=-2)
    masked = jnp.where(keep[..., None], grouped, -jnp.inf)
    _, ids = jax.lax.top_k(masked.reshape(biased.shape), cfg.top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    total = jnp.sum(chosen, axis=-1, keepdims=True)
    eps = getattr(cfg, "norm_topk_eps", None)
    if eps is not None:
        total = total + eps
    weights = chosen / total * cfg.routed_scaling_factor
    return weights, ids


def compact_rows(cfg, n: int) -> int | None:
    """The capacity C of the compact branch of :func:`moe` for ``n``
    assignments (tokens x ``top_k``), from shapes alone: twice the
    share a uniform router sends the ``count`` held of ``n_experts``
    experts, in whole row tiles of the grouped matmul; None (no branch:
    the layer works at ``n`` rows) unless C is at most a quarter of
    ``n``. A device that holds an eighth of the experts or less has it
    at a prompt's rows; none has it at a decode step's (one tile is more
    than a quarter of 256 or 512 assignments), nor one that holds a
    quarter of its experts, or all."""
    from ray_tpu.ops.grouped_matmul import TILE_M

    _, count = cfg.held
    c = -(-2 * n * count // (cfg.n_experts * TILE_M)) * TILE_M
    return c if 4 * c <= n else None


def held_first(cfg, ids):
    """The chosen ids [tokens, top_k] sorted by expert with the held
    ones first -> (held [N] bool by assignment, order [N]: the sort's
    permutation, stable, group_sizes [count]: the assignments each held
    expert got; their sum is how many rows of the sort are held)."""
    first, count = cfg.held
    local = ids.reshape(-1) - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count)  # the others sort last
    order = jnp.argsort(key, stable=True)
    group_sizes = jnp.sum(
        jax.nn.one_hot(key, count, dtype=jnp.int32), axis=0)
    return held, order, group_sizes


def _held_part(cfg, matmul, c, w_gate, w_up, w_down, xf, weights, held,
               order, group_sizes):
    """The held experts' part of the layer's result, [tokens, D], from
    the first ``c`` rows of the sort (``None``: from every assignment's
    row). The rows gathered in the sort's order (a foreign assignment's
    reads row 0), the three products over the held groups (two where
    ``w_gate`` is None: ungated experts, the file's end), then
    :func:`_summed`. With ``c`` the gather, the products and the
    elementwise work between them are ``c`` rows and not N: right when
    no more than ``c`` assignments are held, for the held ones sort
    first; the groups lie at the same offsets, so the result is the one
    ``c = None`` gives, bit for bit."""
    first = order if c is None else order[:c]
    rows = xf[jnp.where(held[first], first // cfg.top_k, 0)]
    experts = functools.partial(matmul, group_sizes=group_sizes)
    if w_gate is None:  # (w_up [count, F, D]: ``init_ungated_experts``)
        return _summed(cfg, c, xf, weights, held, order, experts(relu2(
            experts(rows, w_up, transpose_rhs=True)), w_down))
    gate = experts(rows, w_gate)
    up = experts(rows, w_up)
    y = experts(gated(gate, up, swiglu_limit(cfg)), w_down)
    return _summed(cfg, c, xf, weights, held, order, y)


def relu2(up):
    """The middle of an ungated expert (what the file's end is about):
    ``relu(up)^2``, squared in float32 and rounded once, to ``up``'s
    type. (Defined here, in the lines ``_held_part``'s tail left.)"""
    return jnp.square(jax.nn.relu(up.astype(jnp.float32))).astype(up.dtype)


# (jitted by itself where the branch stands: a program traces the
# kernel's calls once a shape, not twice a layer and bucket; ``matmul``
# is static so that a test's patched dispatch is a trace of its own)
_held_part_once = jax.jit(_held_part, static_argnums=(0, 1, 2))


def moe(cfg, p, x, aux: dict | None = None):
    """The expert layer of a device that holds ``cfg.held`` = (first,
    count) of the experts. x [B, T, D]. Every token is routed over ALL
    experts; an assignment to an expert that is not held is left out:
    in the sort by expert it falls behind the held ones, into rows that
    belong to no group, which the grouped matmul never visits
    (``ops/grouped_matmul.py``: its grid covers the groups' rows only;
    off the TPU ``ragged_dot`` leaves such rows zero), its gather reads
    row 0 and its part of the sum is masked.

    Where the shapes give a capacity C (:func:`compact_rows`: a prompt's
    rows on a device that holds an eighth of the experts or less), the
    device counts its held assignments and, when they are at most C,
    gathers and multiplies the first C rows of the sort alone; with more
    it works at every assignment's row, as it does where there is no C
    (:func:`_held_part`, with C and without). One ``lax.cond`` on the
    count: nothing is dropped, the capacity decides which lines compute
    the result and never what it holds, and either branch gives the
    same bits.

    The shared expert, where
    ``p`` has one, is computed in full. On one device the layer runs without an exchange:
    the other devices' partial sums are not here and nothing stands in
    for them. With ``aux`` the chosen ids [B, T, top_k] are left in
    ``aux["expert_ids"]`` and, where the branch stands, whether this
    call took the compact one in ``aux["compact"]`` (int32 0 or 1)."""
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    b, t, d = x.shape
    kk = cfg.top_k
    xf = x.reshape(b * t, d)
    with jax.named_scope("moe_router"):
        score = jax.nn.softmax if cfg.router_softmax else jax.nn.sigmoid
        scores = score(jnp.dot(
            xf, p["router"], preferred_element_type=jnp.float32))
        # (a router without a bias: the scores choose as they are)
        weights, ids = route(cfg, scores, p.get("router_bias", 0.0))
        if aux is not None:
            aux["expert_ids"] = ids.reshape(b, t, kk)
    with jax.named_scope("moe_experts"):
        held, order, group_sizes = held_first(cfg, ids)
        args = (p.get("w_gate"), p["w_up"], p["w_down"], xf, weights, held,
                order, group_sizes)
        c = compact_rows(cfg, b * t * kk)
        if c is None:
            out = _held_part(cfg, grouped_matmul, None, *args)
        else:
            fits = jnp.sum(group_sizes) <= c
            out = jax.lax.cond(
                fits,
                lambda: _held_part_once(cfg, grouped_matmul, c, *args),
                lambda: _held_part_once(cfg, grouped_matmul, None, *args))
            if aux is not None:
                aux["compact"] = fits.astype(jnp.int32)
    if "shared_up" not in p:
        return out.reshape(b, t, d)
    with jax.named_scope("moe_shared"):
        # (a SwiGLU, or two matrices round relu^2: the file's end)
        out = out + _shared_expert(cfg, p, xf)
    return out.reshape(b, t, d)


def mlp_layer(cfg, sparse: bool, p, h, aux: dict | None = None,
              residual: bool = True):
    """A layer's MLP with its norm, added to ``h`` [B, T, D]: the dense
    SwiGLU (scope ``mlp``) or, where the layer is ``sparse``, the expert
    layer (``moe_router``, the norm with it, ``moe_experts``,
    ``moe_shared``, the residual with the last of them). Without
    ``residual`` the sublayer's output alone: a block whose residual
    path is its own (several streams) adds it itself."""
    if not sparse:
        with jax.named_scope("mlp"):
            x = rms_norm(h, p["mlp_norm"], cfg.rms_eps)
            y = swiglu(x, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                       p["mlp"]["w_down"], swiglu_limit(cfg))
            return h + y if residual else y
    with jax.named_scope("moe_router"):
        x = rms_norm(h, p["mlp_norm"], cfg.rms_eps)
    y = moe(cfg, p["mlp"], x, aux)
    if not residual:
        return y
    with jax.named_scope("moe_shared" if "shared_up" in p["mlp"]
                         else "moe_experts"):
        return h + y


def segment_rows(t: int, whole: int = 1) -> int:
    """The rows of one segment of a ``t``-row prefill: ``t`` itself up to
    :data:`SEGMENT_ROWS`, else the equal segments of at most that many
    rows, each a multiple of ``whole`` (a block's chunk)."""
    n = -(-t // SEGMENT_ROWS)
    if t % n or (n > 1 and (t // n) % whole):
        raise ValueError(
            f"a prefill of {t} rows is run in {n} segments of at most "
            f"{SEGMENT_ROWS} rows: {t} must divide into {n} equal "
            f"segments of whole {whole}-row chunks")
    return t // n


def in_segments(body, carry, xs, seg: int, live=None):
    """``body(carry, (segment's first row, the segment's rows of xs)) ->
    (carry, outputs with a leading [B, seg])`` over the segments of
    ``xs`` (a tree of [B, T, ...] arrays) in order, one ``lax.scan`` ->
    (carry, the outputs [B, T, ...]). One segment is one plain call.

    ``live`` (a traced int32 scalar: the rows of the call's longest
    prompt) leaves out the segments behind the last one that holds a
    real row. A segment whose first row is ``>= live`` is DEAD: every
    row of it is padding, ``body`` is not run for it (the scan's step is
    ``lax.cond(start < live, body, skip)``), the carry passes through
    unchanged and its rows of the outputs are zeros. A live segment's
    arithmetic is the scan's own: on the chip the results are the same
    bits with ``live`` and without (``PERF.md`` §6 PR 51). ``live=None``
    runs ``body`` on every segment: whole sequences (``forward``,
    ``loss_fn``)."""
    b, t = jax.tree_util.tree_leaves(xs)[0].shape[:2]
    n = t // seg
    if n == 1:
        return body(carry, (jnp.int32(0), xs))
    step = body
    if live is not None:
        # ``body`` is traced ONCE, as the scan alone traces it, for the
        # dead branch's shapes and for the live branch, which binds its
        # equations: a second trace of four buckets' layers was 28 s of
        # a cell's set-up (PERF.md §6 PR 51)
        traced, (_, first) = jax.make_jaxpr(body, return_shape=True)(
            carry, (jnp.int32(0), jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct((b, seg, *a.shape[2:]),
                                               a.dtype), xs)))
        shape = jax.tree_util.tree_structure((carry, first))

        def run(carry, x):
            return jax.tree_util.tree_unflatten(shape, jax.core.eval_jaxpr(
                traced.jaxpr, traced.consts,
                *jax.tree_util.tree_leaves((carry, x))))

        def skip(carry, x):
            return carry, jax.tree_util.tree_map(
                lambda y: jnp.zeros(y.shape, y.dtype), first)

        def step(carry, x):
            return jax.lax.cond(x[0] < live, run, skip, carry, x)

    def rows(a):  # [B, T, ...] -> [n, B, seg, ...]
        return jnp.moveaxis(a.reshape(b, n, seg, *a.shape[2:]), 1, 0)

    carry, outs = jax.lax.scan(step, carry, (
        jnp.arange(n, dtype=jnp.int32) * seg,
        jax.tree_util.tree_map(rows, xs)))
    return carry, jax.tree_util.tree_map(
        lambda a: jnp.moveaxis(a, 0, 1).reshape(b, t, *a.shape[3:]), outs)


@jax.named_scope("lm_head")
def logits(cfg, params, h):
    """h [..., D] before the final norm -> float32 logits [..., V]."""
    h = rms_norm(h, params["final_norm"], cfg.rms_eps)
    return jnp.dot(h, params["lm_head"], preferred_element_type=jnp.float32)


def loss_fn(forward):
    """-> ``loss_fn(params, batch, cfg)`` of a block whose whole-sequence
    logits are ``forward(params, tokens, cfg)``: the mean next-token
    cross-entropy over ``batch["tokens"]`` [B, T+1] (or inputs /
    targets). No cell trains these blocks: the forward is the serving
    one, in the serving types."""
    def loss_fn(params, batch, cfg):
        if "inputs" in batch:
            inputs, targets = batch["inputs"], batch["targets"]
        else:
            inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
        loss, n = softmax_cross_entropy(
            forward(params, inputs, cfg), targets, mask=batch.get("mask"))
        return loss, {"loss": loss, "tokens": n}

    return loss_fn


@jax.named_scope("moe_router")
def routing_counts(cfg, ids, active) -> tuple:
    """ids [B, 1, top_k] of one expert layer's decode step -> (distinct
    held experts that got a row from an active slot, the active slots'
    assignments, those of them to held experts), int32 scalars."""
    first, count = cfg.held
    hit = jax.nn.one_hot(ids - first, count, dtype=jnp.bool_)
    hit = hit & active[:, None, None, None]
    return (jnp.sum(jnp.any(hit, axis=(0, 1, 2)), dtype=jnp.int32),
            jnp.sum(active, dtype=jnp.int32) * ids.shape[-1],
            jnp.sum(hit, dtype=jnp.int32))


@jax.named_scope("moe_router")
def compact_calls(auxes: list):
    """What a prefill's expert-layer calls left in their ``aux`` -> [2]
    int32: how many of them had the branch of :func:`moe` (none where
    the shapes give no capacity), how many of those took the compact
    one."""
    took = [aux["compact"] for aux in auxes if "compact" in aux]
    return jnp.stack([jnp.int32(len(took)), sum(took, jnp.int32(0))])


@jax.named_scope("moe_router")
def prefill_counts(layers: list) -> tuple:
    """A segmented prefill's (:func:`prefill_loads` row [count],
    :func:`compact_calls` [2]) of each expert layer, summed over its
    live segments -> what the call hands the read-back: (loads [L_moe,
    count], calls [2])."""
    return (jnp.stack([loads for loads, _ in layers]),
            sum(calls for _, calls in layers))


@jax.named_scope("moe_router")
def prefill_loads(cfg, ids, true_lens):
    """ids [L_moe, F, P, top_k] of a prefill -> [L_moe, count] int32: the
    assignments each HELD expert got in each layer from the REAL
    positions of the prompts (``true_lens`` [F] masks the padding)."""
    first, count = cfg.held
    real = jnp.arange(ids.shape[2])[None, :] < true_lens[:, None]
    hit = jax.nn.one_hot(ids - first, count, dtype=jnp.int32)
    return jnp.sum(hit * real[None, :, :, None, None], axis=(1, 2, 3))


# --------------------------------------------------------------------------
# At the file's END, and what reaches it from above keeps its lines: the
# expert kernel's Mosaic module carries the lines and columns of every
# call on its way, so a line added above moves the compile-cache key of
# every older block's programs (``PERF.md`` section 6, PR 70).
#
# Ungated experts (``models/nemotron.py``): experts of TWO matrices round
# a squared relu, ``W_down relu(W_up x)^2`` (:func:`relu2`) with no gate,
# the shared expert alike. The LEAVES say so (:func:`init_ungated_experts`
# makes no ``w_gate`` / ``shared_gate``): that is what :func:`moe` and
# :func:`_held_part` read, and no field of a configuration.
# --------------------------------------------------------------------------

def _summed(cfg, c, xf, weights, held, order, y):
    """How :func:`_held_part` ends. The products' rows ``y`` (in the
    sort's order, ``c`` of them or every assignment's) -> [tokens, D]:
    each assignment's row put back at its place (a foreign one's masked
    to 0) and a token's ``top_k`` summed in float32. The put-back and the
    sum are the same lines with ``c`` and without."""
    kk = cfg.top_k
    unsort = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    if c is not None:  # (a foreign assignment's place lies behind them)
        unsort = jnp.minimum(unsort, c - 1)
    y = jnp.where(held[:, None], y[unsort], 0).astype(jnp.float32)
    out = jnp.sum(y.reshape(-1, kk, xf.shape[1]) * weights[..., None],
                  axis=1)
    return out.astype(cfg.compute_dtype)


def _shared_expert(cfg, p, xf):
    """The shared expert of an expert layer's leaves ``p`` on xf
    [tokens, D], in the routed experts' form."""
    if "shared_gate" in p:
        return swiglu(xf, p["shared_gate"], p["shared_up"], p["shared_down"],
                      swiglu_limit(cfg))
    return relu2(xf @ p["shared_up"]) @ p["shared_down"]


def init_ungated_experts(cfg, mat, keys) -> dict:
    """:func:`init_experts` for experts of two matrices: the router with
    its selection bias, the held experts' ``w_up`` stored ``[count, F,
    D]``, the way ``w_down`` lies (``grouped_matmul(transpose_rhs=True)``
    reads it in place: a width F that is not whole lane tiles is then
    nowhere a matrix's minor dimension, which on a TPU XLA would keep
    transposed in HBM and copy back for every call of the kernel), and
    the shared expert's two."""
    d, f, fs = cfg.d_model, cfg.d_ff, cfg.shared_d_ff
    _, count = cfg.held
    leaves = {"router": mat(d, cfg.n_experts),
              "router_bias": 0.01 * jax.random.normal(
                  next(keys), (cfg.n_experts,), jnp.float32),
              "w_up": draw(next(keys), (count, f, d), d ** -0.5,
                           cfg.compute_dtype),
              "w_down": mat(count, f, d, out=True)}
    if fs:
        leaves.update({"shared_up": mat(d, fs),
                       "shared_down": mat(fs, d, out=True)})
    return leaves
