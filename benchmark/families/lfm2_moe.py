"""The family ``lfm2_moe``: the language model of LFM2-8B-A1B as the
benchmark knows it (``ray_tpu/models/lfm2.py``): gated short-convolution
mixers (a depthwise causal convolution of ``conv_L_cache`` taps between
two gates; a stream's whole state is ``conv_L_cache - 1`` rows of the
hidden width) with a GQA layer of ``hidden_size / num_attention_heads``-
wide heads, a norm a head on q and k, where ``layer_types`` says
``full_attention``; ``num_dense_layers`` dense SwiGLU layers first, then
a sigmoid top-k router with a selection bias over experts of which this
chip holds ``held_experts = [first, count]``, with no shared expert; a
tied head. What a family file owes is listed in
``manifest.FAMILY_DUTIES``; the arithmetic takes the dict of ``fields``
and never imports the program. A configuration file names this file
with ``"family": "lfm2_moe"``.
"""

from __future__ import annotations

import os
import types

from benchmark import manifest
from benchmark.manifest import ManifestError

_BASE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# config.json keys the block reads one way only: (key, the value it is
# built for). Another value is refused, not approximated.
_BUILT_FOR = (
    ("model_type", "lfm2_moe"), ("conv_bias", False),
    ("norm_topk_prob", True), ("use_expert_bias", True),
    ("tie_word_embeddings", True),
)
_KINDS = ("conv", "full_attention")


def fields(config: dict) -> dict:
    """The published ``config.json`` keys as ``Lfm2Config`` fields."""
    for key, want in _BUILT_FOR:
        if config.get(key, want) != want:
            raise ManifestError(
                f"the lfm2_moe block is built for {key} = {want!r}, not "
                f"{config[key]!r}")
    if not os.path.isfile(os.path.join(
            os.path.dirname(_BASE), "ray_tpu", "models", "lfm2.py")):
        # (asked of the files, not by import: the process that
        # orchestrates a run stays off jax)
        raise ManifestError(
            "this checkout's program has no block of gated short "
            "convolutions beside GQA layers of 64-wide heads "
            "(ray_tpu/models/lfm2.py): it cannot run an lfm2_moe "
            "configuration")
    n = int(config["num_hidden_layers"])
    kinds = [str(k) for k in config["layer_types"]]
    if len(kinds) != n or set(kinds) - set(_KINDS):
        raise ManifestError(
            f"layer_types must name one of {_KINDS} for each of the {n} "
            "layers: the block derives no period")
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    if d % heads:
        raise ManifestError("hidden_size must divide into the heads")
    held = config.get("held_experts")
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": d,
        "n_layers": n,
        "layer_types": kinds,
        "n_heads": heads,
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": d // heads,
        "conv_kernel": int(config["conv_L_cache"]),
        "n_dense_layers": int(config["num_dense_layers"]),
        "dense_d_ff": int(config["intermediate_size"]),
        "d_ff": int(config["moe_intermediate_size"]),
        "shared_d_ff": 0,
        "n_experts": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "n_group": 1, "topk_group": 1,
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        # (transformers' Lfm2MoeSparseMoeBlock: the chosen scores over
        # their sum + 1e-6; no key of the config names it)
        "norm_topk_eps": 1e-6,
        "held_experts": None if held is None else [int(held[0]),
                                                   int(held[1])],
        "rope_theta": float(config["rope_theta"]),
        "rms_eps": float(config["norm_eps"]),
        "dtype": "bfloat16",
        # the depth the seeded weights are scaled for: the model's own
        "published_layers": int(config.get("published_num_hidden_layers",
                                           n)),
    }


# the published list's head and an uneven tail (two conv layers, a full
# one, a conv layer, a full one, a conv layer), one dense layer, half of
# the experts held, heads x head_dim unequal to the hidden size. Small
# enough that the cell's CPU rehearsal (a decode chunk and four prefill
# buckets to compile) ends inside a minute beside five other test
# processes. Its weights are scaled for its own depth: scaled for the
# published 24 layers, six layers add less than the stream starts with
# and greedy streams fall into cycles of two tokens.
TINY_FIELDS = dict(
    vocab_size=256, d_model=32, n_layers=6,
    layer_types=["conv", "conv", "full_attention", "conv",
                 "full_attention", "conv"],
    n_heads=4, n_kv_heads=2, head_dim=16, conv_kernel=3, n_dense_layers=1,
    dense_d_ff=64, d_ff=16, shared_d_ff=0, n_experts=8, top_k=2, n_group=1,
    topk_group=1, routed_scaling_factor=1.0, norm_topk_eps=1e-6,
    held_experts=[0, 4], rope_theta=1e6, rms_eps=1e-5, dtype="float32",
    published_layers=6)


def build(m: dict, *, max_seq_len: int, remat: bool):
    """The program's model for fields ``m``: the one place that imports
    it. ``init_params`` makes the tree in the SERVING types, leaf by
    leaf (``lfm2.init_params``). ``remat`` has nothing to switch: no
    cell trains this block."""
    import jax

    from ray_tpu.models import lfm2

    held = m.get("held_experts")
    cfg = lfm2.Lfm2Config(**{
        **m, "held_experts": held and tuple(held),
        "layer_types": tuple(m["layer_types"])}, max_seq_len=max_seq_len)

    def init_params(key):
        return lfm2.init_params(cfg, key)

    def param_logical_axes():
        """Every leaf whole on its device: the block is sharded by what
        a chip HOLDS (``held_experts``), not over a mesh."""
        return jax.tree_util.tree_map(
            lambda a: (None,) * a.ndim,
            jax.eval_shape(init_params, jax.random.PRNGKey(0)))

    return types.SimpleNamespace(
        cfg=cfg, init_params=init_params,
        loss_fn=lambda params, batch: lfm2.loss_fn(params, batch, cfg),
        param_logical_axes=param_logical_axes)


def reference():
    """``families/lfm2_moe.reference.py``, beside this file."""
    return manifest.load_python("families", "lfm2_moe.reference", _BASE)


# ------------------------------------------------ operations and bytes


def _held(m: dict) -> int:
    return (m.get("held_experts") or (0, m["n_experts"]))[1]


def layer_counts(m: dict) -> dict:
    """How many layers of each kind the configuration has."""
    full = sum(k == "full_attention" for k in m["layer_types"])
    dense = min(m["n_dense_layers"], m["n_layers"])
    return {"conv": m["n_layers"] - full, "full": full, "dense": dense,
            "moe": m["n_layers"] - dense}


def kv_row_bytes(m: dict, itemsize: int = 2) -> int:
    """One position's k and v of one attention layer."""
    return 2 * m["n_kv_heads"] * m["head_dim"] * itemsize


def conv_params(m: dict) -> int:
    """One gated short convolution: the input product (B | C | x), the
    taps (no bias), the output product."""
    d = m["d_model"]
    return d * 3 * d + m["conv_kernel"] * d + d * d


def gqa_params(m: dict) -> int:
    """One attention: q, k, v and the output projection (no bias) and
    the two head norms."""
    d, hd = m["d_model"], m["head_dim"]
    return d * (m["n_heads"] + 2 * m["n_kv_heads"]) * hd \
        + m["n_heads"] * hd * d + 2 * hd


def dense_params(m: dict) -> int:
    return 3 * m["d_model"] * m["dense_d_ff"]


def expert_params(m: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["d_model"] * m["d_ff"]


def moe_fixed_params(m: dict) -> int:
    """What an expert layer holds beside its routed experts: the router
    with its selection bias (no shared expert)."""
    return m["d_model"] * m["n_experts"] + m["n_experts"]


def _mixers(m: dict) -> int:
    c = layer_counts(m)
    return c["conv"] * conv_params(m) + c["full"] * gqa_params(m)


def num_params(m: dict) -> int:
    """Parameters HELD here: of every expert layer the held experts; the
    embedding once (it is the head too)."""
    d, v, c = m["d_model"], m["vocab_size"], layer_counts(m)
    return (v * d + d + m["n_layers"] * 2 * d + _mixers(m)
            + c["dense"] * dense_params(m)
            + c["moe"] * (moe_fixed_params(m) + _held(m) * expert_params(m)))


def matmul_params(m: dict) -> int:
    """Parameters a token meets in a matrix product here: the mixers'
    products (not the taps, not the head norms), the dense MLPs, the
    router and the held share of its ``top_k`` experts (uniform
    routing), and the head."""
    d, c = m["d_model"], layer_counts(m)
    routed = m["top_k"] * _held(m) / m["n_experts"] * expert_params(m)
    return int(c["conv"] * (conv_params(m) - m["conv_kernel"] * d)
               + c["full"] * (gqa_params(m) - 2 * m["head_dim"])
               + c["dense"] * dense_params(m)
               + c["moe"] * (d * m["n_experts"] + routed)
               + d * m["vocab_size"])


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward, recomputation not counted: 6 per matmul
    parameter a token meets; the full layers' causal attention over the
    unmasked half of ``seq`` x ``seq``; a conv layer's taps and gates, 2
    (K + 2) a channel. (No cell trains this family.)"""
    c = layer_counts(m)
    attn = c["full"] * 2 * m["n_heads"] * seq * 0.5 * 2 * m["head_dim"]
    conv = c["conv"] * 2 * (m["conv_kernel"] + 2) * m["d_model"]
    return 3.0 * (2 * matmul_params(m) + attn + conv)


def experts_touched(m: dict, tokens: float) -> float:
    """HELD experts that get at least one of ``tokens`` tokens'
    assignments when each token's ``top_k`` distinct experts are uniform
    over all ``n_experts``: held x (1 - (1 - k/E)^tokens). A floor on
    what a layer must read."""
    e, k = m["n_experts"], m["top_k"]
    return _held(m) * (1.0 - (1.0 - k / e) ** tokens)


def state_bytes_per_slot(m: dict, max_len: int, itemsize: int = 2) -> dict:
    """What one stream's state takes, by kind: for each conv layer
    ``conv_kernel - 1`` rows of the hidden width; for each attention
    layer ``max_len`` rows of k and v."""
    c = layer_counts(m)
    return {"recurrent": c["conv"] * (m["conv_kernel"] - 1) * m["d_model"]
            * itemsize,
            "full": c["full"] * max_len * kv_row_bytes(m, itemsize)}


def decode_step_bytes(m: dict, slots: int, live_rows_per_slot: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step of ``slots`` streams cannot avoid: every
    weight outside the routed experts once (mixers, dense MLPs, router,
    the head = the embedding), the held experts the slots' tokens touch
    (``experts_touched``), every slot's convolution rows read AND
    written once a conv layer, and the LIVE rows of k and v of the
    attention layers. A floor: an implementation that reads more reads
    LOW, never over 100%."""
    d, c = m["d_model"], layer_counts(m)
    weights = (_mixers(m) + c["dense"] * dense_params(m)
               + c["moe"] * (moe_fixed_params(m)
                             + experts_touched(m, slots) * expert_params(m))
               + d * m["vocab_size"]) * itemsize
    per_slot = state_bytes_per_slot(m, 1, itemsize)
    return weights + slots * (2 * per_slot["recurrent"]
                              + live_rows_per_slot * per_slot["full"])


def flash_calls(m: dict, batch: int, seq: int) -> list:
    """The forward kernel over a ``seq``-row prompt from position 0, all
    of a full layer's calls together (one a segment): once a full layer
    at ``n_heads`` / ``n_kv_heads`` x ``head_dim``, the causal half
    counted once (``model_math.flash_flops``). No cell trains the block:
    a train step has none but these."""
    return [(layer_counts(m)["full"], batch, seq, m["n_heads"],
             m["n_kv_heads"], m["head_dim"])]


def gmm_flops(rows: float, k: int, n: int) -> float:
    """One grouped matmul (``moe_gmm``) over ``rows`` assignment rows OF
    HELD EXPERTS, [rows, k] x [held, k, n] (``families/solar_open2.py``
    says why the rows come from the engine's ``held_assignments``)."""
    return 2.0 * rows * k * n


def gmm_bytes(rows: float, k: int, n: int, touched: float,
              itemsize: int = 2) -> float:
    """Bytes that call cannot avoid: the ``touched`` held experts'
    matrices once, the held rows read and their results written."""
    return (touched * k * n + rows * k + rows * n) * itemsize
