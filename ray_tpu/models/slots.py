"""The protocol between the serving engine and a block, written once.

``models/decode_engine.py`` carries every slot's state, donates it to
its two programs (``decode_chunk``, ``_prefill_batch_into_slots``) and
reads ``state["pos"]``; what the state IS, how one token a slot moves it
and how a prompt fills it is the block's, found through the
configuration (``cfg.slot_model``). A block subclasses :class:`Slots`
in its own module, states what is its own and binds the class as
``SLOTS``: a new block edits neither the engine nor another block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.models import llama
from ray_tpu.ops.sampling import sample_from_logits


class Slots:
    """A block's half of the engine; every member is reached through
    the class (``SLOTS.init_state(...)``). What a block states:

    - ``init_state(cfg, slots, max_len)``: every slot's state, a dict
      with ``pos`` [slots]; ``max_len(state)``; ``state_bytes(state)``
      by kind (from shapes: the state may be described only);
    - ``step(cfg, params, prepared, tok, state, pos, active)``: one
      token a slot on ``state`` (the dict without ``pos``) -> (float32
      logits [B, V], state, *step counters named by ``step_counters``);
    - ``prefill(params, prompts, true_lens, seeds, temps, top_ps, cfg,
      slot_len, prefix)`` -> (the streams' state, whole prompt lengths,
      first tokens, their logprobs, *counts for the read-back: the
      per-expert assignment counts [L, E] (``expert_load_max`` /
      ``expert_load_mean`` on ``engine.readback``) and, from a block
      whose expert layer has the compact branch (``moe.moe``), [2]
      int32, its expert-layer calls that had the branch and those that
      took it (``moe_expert_calls`` / ``moe_compact_calls``)); a
      state that is no rows starts with :meth:`refuse_prefix`, and
      every one ends with :meth:`first_token`;
    - ``scatter(state, slots, streams, full_lens)``: that state into
      its slots, so that a reused slot shows nothing of its last stream
      (rows a stream can read are its own, ``_prefill_batch_into_slots``
      says why; a state that is no rows is replaced whole).

    And, only where its own differs from what stands here:

    - ``rows_state``: whether a slot's state is rows of positions that
      can be cut, copied and rewound at any position (what the prefix
      cache, speculative decoding and the prefill workers need:
      ``decode_engine.require_rows``). A recurrent state is not: the
      delta rule's ``S`` (``ling.py``, ``solar.py``: ``ops/kda_*.py``)
      and the SSD recurrence's ``H`` (``granite.py``:
      ``ops/ssd_*.py``), each a float32 array a layer that its step
      kernel updates in the buffer it came in, with the last rows of
      the layer's convolution beside it;
    - ``step_counters``: here those of a block that holds a part of its
      experts (``moe.routing_counts``);
    - ``row_kinds(cfg)``: for a model whose layers keep rows of several
      kinds, {kind: (layers, the most rows a slot keeps in one, ``None``
      = ``max_len``)} (what ``_count_rows`` counts by; a recurrent kind
      keeps 0: Solar-Open2's ``{recurrent: (3, 0), full: (1, None)}``,
      Granite's ``{recurrent: (9, 0), full: (1, None)}``). A kind need
      not be attended: dots3's ``{full: (2, None), index: (2, None),
      ring: (3, 513)}`` names the indexer's keys, which an indexer reads
      and nobody attends, beside the latent rows it chooses among. Latent
      rows live in a stack of 640 numbers a position (Instella-MoE, Ling,
      dots3's full layers) or in a ring of 1,152 (dots3's window layers);
    - ``prefill_segments(cfg, bucket)``: into how many segments of rows
      a ``bucket``-row call cuts its tokenwise work. The BUCKET's count:
      a call runs those of them that hold a row of its longest prompt
      and leaves the dead ones behind it out (``moe.in_segments``;
      ``engine.prefill`` carries both, ``segments`` and
      ``live_segments``);
    - ``split(cfg, params)``: what a chunk prepares once, ``step``'s
      ``prepared``; ``reports_routing(cfg)``;
    - ``F32_LEAVES``: the leaves its model paths consume in float32,
      which ``serving_params(cfg, params)`` leaves as they are."""

    rows_state = False
    step_counters = ("experts_touched", "assignments", "held_assignments")
    F32_LEAVES: tuple = ()

    @staticmethod
    def row_kinds(cfg) -> dict:
        return {}

    @staticmethod
    def prefill_segments(cfg, bucket: int) -> int:
        return 1

    @staticmethod
    def split(cfg, params):
        return None

    @staticmethod
    def reports_routing(cfg) -> bool:
        return cfg.moe_layers > 0

    @classmethod
    def serving_params(cls, cfg, params):
        """The tree a serving process holds (``llama.serving_params``
        with the block's float32 leaves): a block's ``init_params``
        makes that tree already, and it comes back itself; a published
        tree of another type is cast once, here."""
        return llama.serving_params(cfg, params, cls.F32_LEAVES)

    @staticmethod
    def refuse_prefix(cfg, prefix) -> None:
        """Raise if a prefill of a state that is no rows was handed a
        ``prefix`` of cached rows."""
        if prefix is not None:
            raise ValueError(
                "a prefix of cached rows cannot seed a slot of "
                f"{type(cfg).__name__}: its slot state is its own "
                "(recurrent or latent layers, or a ring of rows), not "
                "the rows of k and v a prefix carries")

    @staticmethod
    def put_rows(all_, slots, layers):
        """A prefill's rows into their slots' first rows: ``all_`` [L,
        slots, S, C] <- ``layers``, L x [F, P <= S, C], stream f into
        slot ``slots[f]``. A layer and a stream at a time, each an
        update in place (as one scatter over ``slots`` XLA pads and
        selects whole float32 copies of the update)."""
        for layer, new in enumerate(layers):
            for f in range(new.shape[0]):
                all_ = jax.lax.dynamic_update_slice(
                    all_, new[None, f:f + 1].astype(all_.dtype),
                    (layer, slots[f], 0, 0))
        return all_

    @staticmethod
    def first_token(logits_of, params, h, true_lens, seeds, temps, top_ps):
        """How every prefill ends. h [F, P, D]: the stream before the
        final norm, ``true_lens`` [F] of its rows real;
        ``logits_of(params, h)`` the block's head. The head sees the
        last real row alone, and the first token comes from it on the
        (seed, position) lane of the chunk program. -> ([F] first
        tokens, [F] their logprobs)."""
        f = h.shape[0]
        with jax.named_scope("lm_head"):  # (the last real row alone)
            last = logits_of(params, h[jnp.arange(f), true_lens - 1][:, None])
        return sample_from_logits(
            last[:, 0], seeds, true_lens - 1, temps, top_ps)
