"""The family ``instella_moe``: the language model of
Instella-MoE-16B-A3B-Base (``model_type`` ``deepseek_v3``) as the
benchmark knows it (``ray_tpu/models/instella.py``): latent attention
(MLA) with a sigmoid output gate in every layer, YaRN's rotary on
interleaved pairs, the FarSkip residual path, a leading dense MLP and
then a sigmoid top-k router without a group limit over experts of which
this chip holds ``held_experts = [first, count]`` (the configuration the
benchmark runs holds all 64), with shared experts. What a family file
owes is listed in ``manifest.FAMILY_DUTIES``; the arithmetic takes the
dict of ``fields`` and never imports the program. A configuration file
names this file with ``"family": "instella_moe"``.
"""

from __future__ import annotations

import os
import types

from benchmark import manifest
from benchmark.manifest import ManifestError

_BASE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LANES = 128

# config.json keys the block reads one way only: (key, the value it is
# built for). Another value is refused, not approximated.
_BUILT_FOR = (
    ("model_type", "deepseek_v3"), ("hidden_act", "silu"),
    ("attention_bias", False), ("q_lora_rank", None),
    ("qk_layernorm", True), ("rope_interleave", True),
    ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
    ("norm_topk_prob", True), ("n_group", 1), ("topk_group", 1),
    ("moe_layer_freq", 1), ("ep_size", 1), ("tie_word_embeddings", False),
)


def fields(config: dict) -> dict:
    """The published ``config.json`` keys as ``InstellaConfig`` fields."""
    for key, want in _BUILT_FOR:
        if config.get(key, want) != want:
            raise ManifestError(
                f"the instella_moe block is built for {key} = {want!r}, "
                f"not {config[key]!r}")
    if not os.path.isfile(os.path.join(
            os.path.dirname(_BASE), "ray_tpu", "models", "instella.py")):
        # (asked of the files, not by import: the process that
        # orchestrates a run stays off jax)
        raise ManifestError(
            "this checkout's program has no block with gated latent "
            "attention and the FarSkip residual path "
            "(ray_tpu/models/instella.py): it cannot run an instella_moe "
            "configuration")
    if config["num_key_value_heads"] != config["num_attention_heads"] \
            or config["qk_head_dim"] != config["qk_nope_head_dim"] \
            + config["qk_rope_head_dim"]:
        raise ManifestError(
            "latent attention has a key and a value a query head: "
            "num_key_value_heads = num_attention_heads, qk_head_dim = "
            "qk_nope_head_dim + qk_rope_head_dim")
    rope = config["rope_scaling"]
    if rope.get("type") != "yarn":
        raise ManifestError("only YaRN's rope_scaling is built")
    n = int(config["num_hidden_layers"])
    held = config.get("held_experts")
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": n,
        "n_heads": int(config["num_attention_heads"]),
        "first_k_dense": int(config["first_k_dense_replace"]),
        "dense_d_ff": int(config["intermediate_size"]),
        "d_ff": int(config["moe_intermediate_size"]),
        "shared_d_ff": int(config["moe_intermediate_size"])
        * int(config["n_shared_experts"]),
        "n_experts": int(config["n_routed_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "n_group": int(config["n_group"]),
        "topk_group": int(config["topk_group"]),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "held_experts": None if held is None else [int(held[0]),
                                                   int(held[1])],
        "kv_lora_rank": int(config["kv_lora_rank"]),
        "qk_nope_head_dim": int(config["qk_nope_head_dim"]),
        "qk_rope_head_dim": int(config["qk_rope_head_dim"]),
        "v_head_dim": int(config["v_head_dim"]),
        "gated_attention": bool(config["gated_attention"]),
        "farskip": bool(config["farskip"]),
        "rope_theta": float(config["rope_theta"]),
        "rope_factor": float(rope["factor"]),
        "rope_original_max": int(rope["original_max_position_embeddings"]),
        "rope_beta_fast": float(rope["beta_fast"]),
        "rope_beta_slow": float(rope["beta_slow"]),
        "rope_mscale": float(rope["mscale"]),
        "rope_mscale_all_dim": float(rope["mscale_all_dim"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "dtype": "bfloat16",
        # the depth the seeded weights are scaled for: the model's own
        "published_layers": int(config.get("published_num_hidden_layers",
                                           n)),
    }


# a dense layer and three expert layers with two shared experts side by
# side, every expert held; a trained range of 16 positions stretched
# eightfold, so that the rehearsal's sequences pass YaRN's blend
TINY_FIELDS = dict(
    vocab_size=256, d_model=64, n_layers=4, n_heads=4, first_k_dense=1,
    dense_d_ff=160, d_ff=32, shared_d_ff=64, n_experts=16, top_k=4,
    n_group=1, topk_group=1, routed_scaling_factor=2.5, held_experts=[0, 16],
    kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32,
    gated_attention=True, farskip=True, rope_theta=1e4, rope_factor=8.0,
    rope_original_max=16, rope_beta_fast=4.0, rope_beta_slow=1.0,
    rope_mscale=1.0, rope_mscale_all_dim=1.0, rms_eps=1e-6,
    dtype="float32", published_layers=27)


def build(m: dict, *, max_seq_len: int, remat: bool):
    """The program's model for fields ``m``: the one place that imports
    it. ``init_params`` makes the tree in the SERVING types, leaf by
    leaf (``instella.init_params``): as float32 masters the
    configuration the benchmark serves would be 16.5 GB. ``remat`` has
    nothing to switch: no cell trains this block."""
    import jax

    from ray_tpu.models import instella

    held = m.get("held_experts")
    cfg = instella.InstellaConfig(
        **{**m, "held_experts": held and tuple(held)},
        max_seq_len=max_seq_len)

    def init_params(key):
        return instella.init_params(cfg, key)

    def param_logical_axes():
        """Every leaf whole on its device: the block is sharded by what
        a chip HOLDS (depth, ``held_experts``), not over a mesh."""
        return jax.tree_util.tree_map(
            lambda a: (None,) * a.ndim,
            jax.eval_shape(init_params, jax.random.PRNGKey(0)))

    return types.SimpleNamespace(
        cfg=cfg, init_params=init_params,
        loss_fn=lambda params, batch: instella.loss_fn(params, batch, cfg),
        param_logical_axes=param_logical_axes)


def reference():
    """``families/instella_moe.reference.py``, beside this file."""
    return manifest.load_python("families", "instella_moe.reference", _BASE)


# ------------------------------------------------ operations and bytes


def _held(m: dict) -> int:
    return (m.get("held_experts") or (0, m["n_experts"]))[1]


def layer_counts(m: dict) -> dict:
    """How many layers of each kind the configuration has."""
    dense = min(m["first_k_dense"], m["n_layers"])
    return {"latent": m["n_layers"], "dense": dense,
            "moe": m["n_layers"] - dense}


def attn_params(m: dict) -> int:
    """One attention: q, the latent and rotated key, the latent's
    expansion, the output gate, the output projection, the two norms."""
    d, h = m["d_model"], m["n_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    r, dv = m["kv_lora_rank"], m["v_head_dim"]
    return (d * h * qk + d * (r + m["qk_rope_head_dim"])
            + r * h * (m["qk_nope_head_dim"] + dv)
            + (d * h * dv if m["gated_attention"] else 0)
            + h * dv * d + qk + r)


def expert_params(m: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["d_model"] * m["d_ff"]


def moe_fixed_params(m: dict) -> int:
    """What an expert layer holds beside its routed experts: the router
    with its bias, and the shared experts."""
    d = m["d_model"]
    return d * m["n_experts"] + m["n_experts"] + 3 * d * m["shared_d_ff"]


def num_params(m: dict) -> int:
    """Parameters HELD here: of every expert layer the held experts."""
    d, v, c = m["d_model"], m["vocab_size"], layer_counts(m)
    return (2 * v * d + d + m["n_layers"] * (2 * d + attn_params(m))
            + c["dense"] * 3 * d * m["dense_d_ff"]
            + c["moe"] * (moe_fixed_params(m) + _held(m) * expert_params(m)))


def matmul_params(m: dict) -> int:
    """Parameters a token meets in a matrix product here: attention,
    the dense MLP or the router, the shared experts and the held share
    of its ``top_k`` experts (uniform routing), and the head."""
    d, c = m["d_model"], layer_counts(m)
    routed = m["top_k"] * _held(m) / m["n_experts"] * expert_params(m)
    return int(m["n_layers"] * attn_params(m)
               + c["dense"] * 3 * d * m["dense_d_ff"]
               + c["moe"] * (moe_fixed_params(m) + routed)
               + d * m["vocab_size"])


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward, recomputation not counted: 6 per matmul
    parameter a token meets; every layer's causal attention over the
    unmasked half of ``seq`` x ``seq`` (scores ``qk`` wide, values
    ``v_head_dim`` wide). (No cell trains this family.)"""
    attn = m["n_layers"] * 2 * m["n_heads"] * seq * 0.5 * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])
    return 3.0 * (2 * matmul_params(m) + attn)


def experts_touched(m: dict, tokens: float) -> float:
    """HELD experts that get at least one of ``tokens`` tokens'
    assignments when each token's ``top_k`` distinct experts are uniform
    over all ``n_experts``: held x (1 - (1 - k/E)^tokens). A floor on
    what a layer must read."""
    e, k = m["n_experts"], m["top_k"]
    return _held(m) * (1.0 - (1.0 - k / e) ** tokens)


def latent_row_bytes(m: dict, itemsize: int = 2) -> int:
    """One position's row of one layer as the algorithm needs it: the
    latent and the one rotated key (1,088 B at 512 + 32 in bf16)."""
    return (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * itemsize


def stored_row_bytes(m: dict, itemsize: int = 2) -> int:
    """The same row AS STORED: padded to whole lanes (1,280 B), what a
    slot costs and what the decode kernel's blocks bring in."""
    width = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    return -(-width // _LANES) * _LANES * itemsize


def state_bytes_per_slot(m: dict, max_len: int, itemsize: int = 2) -> dict:
    """What one stream's state takes: ``max_len`` stored rows a layer."""
    return {"latent": m["n_layers"] * max_len * stored_row_bytes(m,
                                                                 itemsize)}


def decode_step_bytes(m: dict, slots: int, live_rows_per_slot: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step of ``slots`` streams cannot avoid: every
    weight outside the routed experts once (attention, dense MLP,
    router, shared experts, head), the held experts the slots' tokens
    touch (``experts_touched``), the slots' embedding rows, and the
    LIVE latent rows at what the algorithm needs of them
    (``latent_row_bytes``, not the stored padding). A floor: an
    implementation that reads more reads LOW, never over 100%."""
    d, c = m["d_model"], layer_counts(m)
    weights = (m["n_layers"] * attn_params(m)
               + c["dense"] * 3 * d * m["dense_d_ff"]
               + c["moe"] * (moe_fixed_params(m)
                             + experts_touched(m, slots) * expert_params(m))
               + d * m["vocab_size"] + slots * d) * itemsize
    return weights + slots * live_rows_per_slot * m["n_layers"] \
        * latent_row_bytes(m, itemsize)


def flash_calls(m: dict, batch: int, seq: int) -> list:
    """The unabsorbed attention of a whole sequence: one forward call a
    layer, ``n_heads`` query and key heads of ``qk`` (= ``v_head_dim``)
    width. (Serving's prefill; no cell trains this family.)"""
    return [(m["n_layers"], batch, seq, m["n_heads"], m["n_heads"],
             m["qk_nope_head_dim"] + m["qk_rope_head_dim"])]


def gmm_flops(rows: float, k: int, n: int) -> float:
    """One grouped matmul (``moe_gmm``) over ``rows`` assignment rows,
    [rows, k] x [experts, k, n]. With every expert held the operand's
    rows (tokens x top_k) ARE the rows the kernel's grid visits."""
    return 2.0 * rows * k * n


def gmm_bytes(rows: float, k: int, n: int, touched: float,
              itemsize: int = 2) -> float:
    """Bytes that call cannot avoid: the ``touched`` experts' matrices
    once, the rows read and their results written."""
    return (touched * k * n + rows * k + rows * n) * itemsize


def latent_attn_flops(live_rows: float, m: dict) -> float:
    """One ``decode_attn_latent`` call: every head's query against the
    live rows (scores over latent ‖ rotated key) and the probabilities
    against their latents."""
    r = m["kv_lora_rank"]
    return 2.0 * m["n_heads"] * live_rows * (
        r + m["qk_rope_head_dim"] + r)


def latent_attn_bytes(live_rows: float, m: dict, itemsize: int = 2) -> float:
    """Bytes that call cannot avoid, AS STORED: each live row once (it
    is key and value)."""
    return live_rows * stored_row_bytes(m, itemsize)
