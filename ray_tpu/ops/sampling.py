"""The sampler of the serving programs: one token a row of logits, on a
lane that is a function of the request's seed and the position alone."""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("sample")
def sample_from_logits(logits, seeds, pos, temps, top_ps):
    """Per-slot stateless sampling lane: the RNG key for the token
    emitted from position `pos` of a stream is
    fold_in(PRNGKey(seed), pos) — a pure function of (request seed,
    sequence position), independent of slot index, batch composition,
    and admission timing. That independence is what makes seed-replay
    bit-exact: a replica-death failover re-decodes the same prompt with
    the same seed on ANY replica and reproduces the identical token
    sequence, so the pool's emitted-offset dedup survives sampling.

    logits [B, V] f32; seeds [B] uint32; pos/temps/top_ps [B].
    temperature == 0 selects the greedy token (bit-identical to the
    legacy argmax path); its logprob is reported under the unscaled
    distribution. Returns ([B] int32 tokens, [B] f32 logprobs under the
    ACTUAL sampling distribution — temperature-scaled and
    top-p-renormalized — i.e. the behavior policy an RL learner must
    importance-correct against)."""
    keys = jax.vmap(
        lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p)
    )(seeds, pos)

    def one(key, row, temp, top_p):
        greedy = jnp.argmax(row)
        greedy_lp = jax.nn.log_softmax(row)[greedy]
        scaled = row / jnp.maximum(temp, 1e-6)
        order = jnp.argsort(-scaled)
        srt = scaled[order]
        probs = jax.nn.softmax(srt)
        cum = jnp.cumsum(probs)
        # smallest set of tokens whose mass reaches top_p (the exclusive
        # cumsum keeps at least the top token even for tiny top_p)
        keep_sorted = (cum - probs) < top_p
        keep = jnp.zeros_like(keep_sorted).at[order].set(keep_sorted)
        filt = jnp.where(keep, scaled, -jnp.inf)
        # TOKEN-space Gumbel-argmax (categorical's own construction,
        # unsorted): the noise attached to token id v is a pure function
        # of (key, v). The speculative draft (decode_chunk_spec) samples
        # its proposal on the SAME lane key as the verify's token, so
        # shared noise makes them agree whenever the two distributions
        # are close — sampling over the SORTED vector would attach noise
        # to ranks instead and decouple the draft whenever the orderings
        # differ, collapsing the acceptance rate.
        g = jax.random.gumbel(key, filt.shape)
        sampled = jnp.argmax(filt + g)
        lp = jax.nn.log_softmax(filt)[sampled]
        use = temp > 0.0
        return (jnp.where(use, sampled, greedy).astype(jnp.int32),
                jnp.where(use, lp, greedy_lp))

    return jax.vmap(one)(keys, logits, temps, top_ps)
