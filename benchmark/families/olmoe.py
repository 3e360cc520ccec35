"""The family ``olmoe``: OLMoE's block as the benchmark knows it. It is
the repo's one block (``ray_tpu/models/llama.py``) with two switches on:
q/k normalisation over the whole projection, and a dropless top-k
mixture of SwiGLU experts whose router does not renormalise
(arXiv:2409.02060). What a family file owes is listed in
``manifest.FAMILY_DUTIES``; the arithmetic takes the dict of ``fields``
and never imports the program. A configuration file names this file
with ``"family": "olmoe"``.
"""

from __future__ import annotations

import os

from benchmark import manifest
from benchmark.manifest import ManifestError

_BASE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_llama = manifest.family("llama", _BASE)


def fields(config: dict) -> dict:
    """The published ``config.json`` keys as ``LlamaConfig`` fields. The
    block has no bias, no clipping of q/k/v, no shared expert and no
    other activation than silu: a configuration that needs one is
    refused, not approximated."""
    if config.get("attention_bias") or config.get("bias") \
            or config.get("clip_qkv") is not None \
            or config.get("rope_scaling") is not None \
            or config.get("shared_expert_intermediate_size") \
            or config.get("n_shared_experts") \
            or config.get("hidden_act", "silu") != "silu":
        raise ManifestError(
            "the olmoe block has no bias, no clip_qkv, no rope scaling, "
            "no shared expert and no other activation than silu")
    if config["hidden_size"] % config["num_attention_heads"]:
        raise ManifestError("head size is not hidden / heads")
    if not os.path.isfile(os.path.join(
            os.path.dirname(_BASE), "ray_tpu", "ops", "grouped_matmul.py")):
        # (asked of the files, not by import: the process that
        # orchestrates a run stays off jax)
        raise ManifestError(
            "this checkout's program has no dropless expert layer "
            "(ray_tpu/ops/grouped_matmul.py): it cannot run an olmoe "
            "configuration")
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": int(config["num_hidden_layers"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        # config.json has no key of its own for one expert's width;
        # intermediate_size is read as that width (the catalog's note)
        "d_ff": int(config["intermediate_size"]),
        "rope_theta": float(config["rope_theta"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": bool(config.get("tie_word_embeddings", False)),
        "n_experts": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "norm_topk_prob": bool(config["norm_topk_prob"]),
        "qk_norm": True,  # the model's code; config.json has no key
        "moe_impl": "dropless",
        "dtype": "bfloat16",
    }


TINY_FIELDS = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                   n_kv_heads=4, d_ff=32, rope_theta=1e4, rms_eps=1e-5,
                   tie_embeddings=False, n_experts=8, top_k=2,
                   norm_topk_prob=False, qk_norm=True, moe_impl="dropless",
                   dtype="float32")


def build(m: dict, *, max_seq_len: int, remat: bool):
    """The program's model for fields ``m``: the block is the Llama
    family's with switches on, so its way into the program is that
    family's; the weights' norm scales are this family's own."""
    prog = _llama.build(m, max_seq_len=max_seq_len, remat=remat)
    program_init = prog.init_params

    def init_params(key):
        """The program's initialisation, with every layer's four norm
        scales drawn around 1 instead of set to 1: under scales of one a
        q/k norm left out, or taken per head instead of over the whole
        projection, is within rounding of the right block, and the
        comparison with the reference could not tell."""
        import jax

        params = program_init(key)
        layers = params["layers"]
        for i, name in enumerate(("attn_norm", "mlp_norm", "q_norm",
                                  "k_norm")):
            layers[name] = layers[name] + 0.25 * jax.random.normal(
                jax.random.fold_in(key, 1000 + i), layers[name].shape)
        return params

    prog.init_params = init_params
    return prog


def reference():
    """``families/olmoe.reference.py``, beside this file."""
    return manifest.load_python("families", "olmoe.reference", _BASE)


# ------------------------------------------------ operations and bytes


def _head_dim(m: dict) -> int:
    return m["d_model"] // m["n_heads"]


def attn_params(m: dict) -> int:
    d, hd = m["d_model"], _head_dim(m)
    return 2 * d * m["n_heads"] * hd + 2 * d * m["n_kv_heads"] * hd


def expert_params(m: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * m["d_model"] * m["d_ff"]


def layer_params(m: dict) -> int:
    d, hd = m["d_model"], _head_dim(m)
    qk_norm = (m["n_heads"] + m["n_kv_heads"]) * hd if m.get("qk_norm") \
        else 0
    return (attn_params(m) + qk_norm + d * m["n_experts"]
            + m["n_experts"] * expert_params(m) + 2 * d)


def num_params(m: dict) -> int:
    d, v = m["d_model"], m["vocab_size"]
    head = 0 if m.get("tie_embeddings") else d * v
    return v * d + m["n_layers"] * layer_params(m) + d + head


def matmul_params(m: dict) -> int:
    """Parameters a token meets in a matrix product: attention, the
    router, its ``top_k`` ACTIVE experts and the head (the embedding is a
    gather, the norms are elementwise)."""
    layer = (attn_params(m) + m["d_model"] * m["n_experts"]
             + m["top_k"] * expert_params(m))
    return m["n_layers"] * layer + m["d_model"] * m["vocab_size"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward, recomputation not counted: 6 per active
    matmul parameter, and causal attention's two products over the
    unmasked half of the ``seq`` x ``seq`` square."""
    attn_fwd = m["n_layers"] * 4 * m["d_model"] * seq * 0.5
    return 3.0 * (2 * matmul_params(m) + attn_fwd)


def experts_touched(m: dict, tokens: float) -> float:
    """Experts that get at least one of ``tokens`` tokens' assignments
    when each token's ``top_k`` distinct experts are uniform over the
    ``n_experts``: E x (1 - (1 - k/E)^tokens). A floor on what a layer
    must read: a skewed router touches fewer, never more than E."""
    e, k = m["n_experts"], m["top_k"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def decode_step_bytes(m: dict, slots: int, live_rows_per_slot: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step of ``slots`` streams must read: attention,
    router and head once in the compute type, the experts the slots'
    tokens touch (``experts_touched``: an implementation that reads all
    of them reads more than this floor, so its share reads LOW, never
    over 100%), the slots' embedding rows, and the live k and v rows."""
    d = m["d_model"]
    layer = (attn_params(m) + d * m["n_experts"]
             + experts_touched(m, slots) * expert_params(m))
    weights = (m["n_layers"] * layer + d * m["vocab_size"]
               + slots * d) * itemsize
    cache = (slots * live_rows_per_slot * m["n_layers"] * 2
             * m["n_kv_heads"] * _head_dim(m) * itemsize)
    return weights + cache


flash_calls = _llama.flash_calls  # one call a layer over every head


def gmm_flops(rows: int, k: int, n: int) -> float:
    """One grouped matmul (``moe_gmm``) of ``rows`` assignment rows,
    [rows, k] x [E, k, n]: every row meets one expert's matrix."""
    return 2.0 * rows * k * n


def gmm_bytes(rows: int, k: int, n: int, touched: float,
              itemsize: int = 2) -> float:
    """Bytes that call cannot avoid: the ``touched`` experts' matrices
    once, the rows read and the result written."""
    return (touched * k * n + rows * k + rows * n) * itemsize
