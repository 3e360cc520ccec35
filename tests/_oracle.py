"""The tests' greedy oracle: the uncached forward, once a token.

It shares nothing with any cached path (no KV cache, no
``_layer_with_cache``, no ``_attend_ragged``): every new token is the
argmax of ``llama.forward`` over the whole sequence so far. Tiny
configurations on a CPU only: a sequence of n tokens costs n forwards
and a compile per length.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import llama


@functools.partial(jax.jit, static_argnames=("cfg",))
def _next_token(params, tokens, cfg):
    return jnp.argmax(llama.forward(params, tokens, cfg)[:, -1], axis=-1)


def greedy_generate(params, prompt, cfg, max_new_tokens: int):
    """prompt: [B, T0] -> [B, T0 + max_new_tokens], prompt included."""
    cfg = dataclasses.replace(cfg, use_flash=False, remat=False)
    out = jnp.asarray(prompt)
    for _ in range(max_new_tokens):
        nxt = _next_token(params, out, cfg).astype(out.dtype)
        out = jnp.concatenate([out, nxt[:, None]], axis=1)
    return out


def greedy_tokens(params, prompt, cfg, max_new_tokens: int):
    """One prompt's new tokens alone, as a numpy vector."""
    prompt = np.asarray(prompt, np.int32)
    return np.asarray(greedy_generate(
        params, prompt[None], cfg, max_new_tokens))[0, len(prompt):]
