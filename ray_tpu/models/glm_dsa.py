"""A decoder whose EVERY layer attends through a latent (MLA) over the
``index_topk`` rows a learned indexer chooses (DeepSeek-V3.2-Exp's
sparse attention), the choice made in some layers and READ by the
layers behind them (IndexShare): a layer of ``indexer_layers[i] == 1``
owns an indexer and an index-key stack and selects; a layer of 0 owns
neither and attends over the selection of the nearest indexer layer
before it. A dense MLP first, then a sigmoid router over experts of
which this device holds a part beside a shared one. The language model
of GLM-5.2 (``model_type`` ``glm_moe_dsa``) as its ``config.json`` gives
it; the ninth block.

``x`` is a layer's normed input, ``N`` a learned RMS norm:

- **A layer's MLA** (``models/dots.py``'s sparse layer at a ``Kind``
  without gate and rescale: ``dots.sparse_segment`` in a prefill, ``dots.
  sparse_step_layer`` in a step): ``c_q = N(x W_qa)``, ``q = c_q W_qb``
  as heads of ``[q_n | q_r]``; ``[c | k_r] = x W_kva``, ``c <- N(c)``;
  ``q_r``, ``k_r`` rotated (interleaved pairs), ``k_r`` one for all
  heads; ``[k_n | v]_h = c W_kvb,h``; scores ``(q_n k_n + q_r k_r) (dn +
  dr)^-1/2`` over the CHOSEN keys, float32 softmax; ``W_o``.
- **The indexer** (indexer layers), the eighth block's and
  ``ops/dsa.py``'s: ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])``
  in float32; query t reads the ``min(index_topk, t + 1)`` positions ``s
  <= t`` of largest ``I``, a tie to the earlier, exactly. Until a stream
  holds more rows than ``index_topk`` every layer is plain causal MLA.
- **A shared layer**: no ``w_iq``, ``w_ik``, ``w_iw``; the set its
  queries read is that of the nearest earlier indexer layer.
- **MLP**: ``models/moe.py``'s, as the eighth block's.

A selection is a value that lives across layers. In a decode step it is
a ``[slots, max_len]`` bfloat16 bias (0 chosen, ``dsa.NEG`` not) that an
indexer layer makes and the layers behind it hand to ``dsa.
decode_attention_masked``. In a **prefill** it is a segment's ``[rows,
P]`` bias, and it decides the loop order: the layers run in GROUPS, an
indexer layer and the shared layers that read it, and a group is ONE
``lax.scan`` over segments of ``moe.SEGMENT_ROWS`` rows whose body runs
all of the group's layers on the segment, carrying each layer's rows so
far (causality allows it: layer l + 1's segment s needs layer l's
segments <= s only). A segment's bias lives for the body and dies with
it; layer after layer, each a scan of its own, it would have to be kept
for every segment at once, a ``[P, P]`` array. Nothing ``[P, P]``
exists; a segment's ``[rows, P]`` float32 scores do.

A slot's state (:data:`SLOTS`), two stacks with DIFFERENT layer counts:
``lat [L, slots, max_len, 640]``, every layer's ``[c | k_r | zeros]``;
``idx [L_index, slots, max_len, index_head_dim]``, the indexer layers'
keys, which an indexer reads and nobody attends.

Types: the eighth block's.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ray_tpu.models import dots, moe
from ray_tpu.ops.norms import rms_norm


@dataclasses.dataclass(frozen=True)
class GlmDsaConfig(moe.HeldExperts):
    vocab_size: int = 154880
    d_model: int = 6144
    n_layers: int = 78
    # 1 = the layer owns an indexer, 0 = it reads the selection of the
    # nearest indexer layer before it; () = the published pattern
    indexer_layers: tuple = ()
    first_k_dense: int = 3
    dense_d_ff: int = 12288
    # mixture of experts: d_ff is ONE expert's width
    d_ff: int = 2048
    shared_d_ff: int = 2048
    n_experts: int = 256
    top_k: int = 8
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.5
    # (first, count): the experts this device holds; None = all of them
    held_experts: tuple | None = None
    n_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 8e6
    index_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    index_norm_eps: float = 1e-6
    rms_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: str = "bfloat16"
    # None: the backend's choice (the kernels on a TPU)
    use_flash: bool | None = None
    # groups of heads a layer's prefill attends one after another, at
    # most (eight of eight: at four of sixteen the 32,768-row call's
    # temporaries are 3.4 GiB beside 10.8 GiB of arguments, at eight 3.0;
    # a model of fewer heads takes their largest common divisor)
    prefill_head_groups: int = 8
    # the depth the weights are initialised for (init_params); 0 =
    # n_layers. A configuration cut in depth names its model's own.
    published_layers: int = 0

    def __post_init__(self):
        n = self.n_layers
        own = tuple(self.indexer_layers) or tuple(
            int(i < 3 or i % 4 == 2) for i in range(n))
        if len(own) != n or set(own) - {0, 1} or not own[0]:
            raise ValueError(
                f"{n} layers need {n} entries of 0 / 1 in indexer_layers, "
                f"the first a 1 (a shared layer reads an EARLIER layer's "
                f"selection), not {own}")
        object.__setattr__(self, "indexer_layers", own)

    def indexes(self, i: int) -> bool:
        return bool(self.indexer_layers[i])

    def sparse(self, i: int) -> bool:
        return i >= self.first_k_dense

    @property
    def mla(self) -> dots.Kind:
        """The widths of a layer's attention: no rescale of the latents,
        no gate a head."""
        return dots.Kind(self.n_heads, self.q_lora_rank, self.kv_lora_rank,
                         self.qk_nope_head_dim, self.qk_rope_head_dim,
                         self.v_head_dim, self.rope_theta, False, False)

    def index_stack(self, i: int) -> int | None:
        """Indexer layer ``i``'s place in the stack of index keys; None
        for a layer that owns no indexer."""
        return sum(self.indexer_layers[:i]) if self.indexes(i) else None

    @property
    def share_groups(self) -> tuple:
        """The layers in groups: an indexer layer and the shared layers
        that read its selection."""
        starts = [i for i, own in enumerate(self.indexer_layers) if own]
        return tuple(tuple(range(a, b)) for a, b in zip(
            starts, [*starts[1:], self.n_layers]))

    @property
    def index_layers(self) -> int:
        return sum(self.indexer_layers)

    @property
    def moe_layers(self) -> int:
        return self.n_layers - min(self.first_k_dense, self.n_layers)

    @property
    def slot_model(self):
        return SLOTS

    @staticmethod
    def tiny(**kw) -> "GlmDsaConfig":
        """Test-size config: the cell's five-layer pattern (a dense
        indexer layer, then an indexer layer and the three that read
        it), a selection that bites (8 rows of the sequences' dozens),
        keys of one and a half times the values' half; runs on the
        CPU."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=5,
            indexer_layers=(1, 1, 0, 0, 0), first_k_dense=1, dense_d_ff=160,
            d_ff=32, shared_d_ff=32, n_experts=16, top_k=4,
            routed_scaling_factor=2.5, n_heads=4, q_lora_rank=32,
            kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
            v_head_dim=32, rope_theta=1e4, index_heads=2, index_head_dim=16,
            index_topk=8, prefill_head_groups=2, max_seq_len=256,
            dtype="float32")
        base.update(kw)
        return GlmDsaConfig(**base)


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def init_params(cfg: GlmDsaConfig, key):
    """The eighth block's initialisation (``dots.init_layers``) without
    its rescale. A SHARED layer's ``attn`` holds no ``w_iq``, ``w_ik``,
    ``ik_norm``, ``ik_bias``, ``w_iw``."""
    return dots.init_layers(cfg, key, [(cfg.mla, bool(own))
                                       for own in cfg.indexer_layers])


# --------------------------------------------------------------------------
# The model: whole sequences, prefill into a slot's state, a ragged step
# --------------------------------------------------------------------------

def prefill(params, tokens, true_lens, cfg: GlmDsaConfig,
            loads: bool = False, live=None):
    """tokens [B, T] from position 0 (right-padded, ``true_lens`` [B]
    real), the layers in their share groups, each group one scan over
    segments of ``moe.segment_rows`` rows (module docstring) -> (h [B,
    T, D] before the final norm, every layer's rows as the cache keeps
    them: (latent rows [B, T, 640],) and from an indexer layer (latent
    rows, index keys [B, T, di]), and with ``loads`` (the held experts'
    assignments from the real positions [L_moe, count], the expert
    layer's calls and compact calls [2]), else None). ``live`` as
    ``mimo.prefill``'s: the dead segments are not run."""
    b, t = tokens.shape
    seg = moe.segment_rows(t)
    cdt = cfg.compute_dtype
    with jax.named_scope("embed"):
        h = params["embed"][tokens]
    rows, counts = [], []
    for layers in cfg.share_groups:
        counted = [i for i in layers if loads and cfg.sparse(i)]

        def group(carry, xs, layers=layers, counted=counted):
            lats, idx_all, count = carry
            start, h_seg = xs
            lats, count = list(lats), list(count)
            rotation = cfg.mla.rotation(dots.segment_positions(h_seg, start))
            bias = None
            for n, i in enumerate(layers):
                p = params["layers"][i]
                with jax.named_scope("qkv"):
                    x = rms_norm(h_seg, p["attn_norm"], cfg.rms_eps)
                a, lats[n], kept, bias = dots.sparse_segment(
                    cfg, cfg.mla, p["attn"], x, rotation, start, lats[n],
                    idx_all if n == 0 else None, bias)
                if n == 0:
                    idx_all = kept
                with jax.named_scope("attn_out"):
                    h_seg = h_seg + a
                aux = {} if i in counted else None
                h_seg = moe.mlp_layer(cfg, cfg.sparse(i), p, h_seg, aux)
                if aux is not None:
                    at = counted.index(i)
                    count[at] = jax.tree_util.tree_map(jnp.add, count[at], (
                        moe.prefill_loads(cfg, aux["expert_ids"][None],
                                          true_lens - start)[0],
                        moe.compact_calls([aux])))
            return (tuple(lats), idx_all, tuple(count)), h_seg

        empty = (tuple(jnp.zeros((b, t, cfg.mla.row_width), cdt)
                       for _ in layers),
                 jnp.zeros((b, t, cfg.index_head_dim), cdt),
                 tuple((jnp.zeros((cfg.held[1],), jnp.int32),
                        jnp.zeros((2,), jnp.int32)) for _ in counted))
        (lats, idx_all, count), h = moe.in_segments(group, empty, h, seg, live)
        rows += [(lat, idx_all) if n == 0 else (lat,)
                 for n, lat in enumerate(lats)]
        counts += count
    return h, rows, moe.prefill_counts(counts) if counts else None


def forward(params, tokens, cfg: GlmDsaConfig):
    """tokens [B, T] -> float32 logits [B, T, V]: whole sequences."""
    b, t = tokens.shape
    h, _, _ = prefill(params, tokens, jnp.full((b,), t, jnp.int32), cfg)
    return moe.logits(cfg, params, h)


loss_fn = moe.loss_fn(forward)


def step(cfg: GlmDsaConfig, params, tok, state, pos, active):
    """One token a slot at PER-SLOT positions. tok, pos, active [B];
    ``state`` the two stacks (:meth:`_Slots.init_state`, without
    ``pos``). Every layer is ``dots.sparse_step_layer``: an indexer
    layer selects, and every layer attends over the latent rows of the
    newest selection alone; an inactive slot attends over nothing. ->
    (float32 logits [B, V], the state updated, three [L_moe] int32
    counters of the ACTIVE slots' routing, and two [1] int32: the rows
    the INDEXER layers selected, summed over active slots and those
    layers, and the chosen rows the attentions of ALL layers were
    handed, summed over active slots and layers)."""
    k = cfg.mla
    with jax.named_scope("embed"):
        h = params["embed"][tok][:, None]  # [B, 1, D]
    plan = dots.step_plan(state["lat"].shape[2], pos, active)
    with jax.named_scope("qkv"):
        rotation = k.rotation(pos[:, None])
    counts, selected, attended = [], jnp.int32(0), jnp.int32(0)
    bias = chosen_rows = None
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("qkv"):
            x = rms_norm(h, p["attn_norm"], cfg.rms_eps)
        a, state, bias, rows = dots.sparse_step_layer(
            cfg, k, p["attn"], x, rotation, plan, state, i,
            cfg.index_stack(i), bias)
        if rows is not None:
            chosen_rows = rows
            with jax.named_scope("attn/attn_index"):
                selected = selected + rows
        with jax.named_scope("attn/attn_sparse"):
            attended = attended + chosen_rows
        with jax.named_scope("attn_out"):
            h = h + a
        aux = {} if cfg.sparse(i) else None
        h = moe.mlp_layer(cfg, cfg.sparse(i), p, h, aux)
        if aux:
            counts.append(moe.routing_counts(cfg, aux["expert_ids"], active))
    counters = tuple(jnp.stack(c) for c in zip(*counts))
    return (moe.logits(cfg, params, h)[:, 0], state, *counters,
            selected[None], attended[None])


# --------------------------------------------------------------------------
# The serving engine's half (the protocol: models/slots.py)
# --------------------------------------------------------------------------

class _Slots(dots.SparseSlots):
    """Two stacks of rows with different layer counts: every layer's
    latent rows, and the indexer layers' keys (read by the indexer,
    attended by nobody)."""

    step_counters = (*dots.SparseSlots.step_counters, "attended_rows")
    STACKS = {"latent": "lat", "index": "idx"}

    @staticmethod
    def row_kinds(cfg: GlmDsaConfig) -> dict:
        return {"latent": (cfg.n_layers, None),
                "index": (cfg.index_layers, None)}

    @staticmethod
    def init_state(cfg: GlmDsaConfig, slots: int, max_len: int) -> dict:
        cdt = cfg.compute_dtype
        return {
            "lat": jnp.zeros((cfg.n_layers, slots, max_len,
                              cfg.mla.row_width), cdt),
            "idx": jnp.zeros((cfg.index_layers, slots, max_len,
                              cfg.index_head_dim), cdt),
            "pos": jnp.zeros((slots,), jnp.int32)}

    @staticmethod
    def step(cfg: GlmDsaConfig, params, prepared, tok, state, pos, active):
        return step(cfg, params, tok, state, pos, active)

    @staticmethod
    def prefill(params, prompts, true_lens, seeds, temps, top_ps,
                cfg: GlmDsaConfig, slot_len: int, prefix=None):
        """Whole prompts from position 0; every layer keeps all of a
        prompt's rows (the bucket's padding among them: a decode step
        overwrites a pad row at its position before a length can expose
        it). -> (the streams' rows by stack, [F] prompt lengths, [F]
        first tokens, [F] their logprobs, the held experts' assignments
        from the real positions [L_moe, count], the expert layer's calls
        and compact calls [2])."""
        _Slots.refuse_prefix(cfg, prefix)
        h, rows, loads = prefill(params, prompts, true_lens, cfg,
                                 loads=cfg.moe_layers > 0,
                                 live=jnp.max(true_lens))
        toks0, logp0 = _Slots.first_token(
            functools.partial(moe.logits, cfg), params, h, true_lens,
            seeds, temps, top_ps)
        # a list a stack, one entry a layer that keeps such rows
        streams = {"lat": [r[0] for r in rows],
                   "idx": [r[1] for r in rows if len(r) > 1]}
        return streams, true_lens, toks0, logp0, *(loads or ())


SLOTS = _Slots
