"""The family ``ling``: the language model of Ling-3.0-flash-VL as the
benchmark knows it (``ray_tpu/models/ling.py``): KDA layers with one MLA
layer every ``layer_group_size``, a leading dense MLP and then a
group-limited sigmoid router over experts of which this chip holds
``held_experts = [first, count]``, with a shared expert. What a family
file owes is listed in ``manifest.FAMILY_DUTIES``; the arithmetic takes
the dict of ``fields`` and never imports the program. A configuration
file names this file with ``"family": "ling"``.
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
    ("q_lora_rank", None), ("score_function", "sigmoid"),
    ("moe_router_enable_expert_bias", True), ("norm_topk_prob", True),
    ("use_qk_norm", True), ("use_mla_nope", False), ("linear_silu", True),
    ("kda_safe_gate", True), ("no_kda_lora", True), ("use_kda_lora", False),
    ("gated_attention_proj_granularity_type", "head_wise"),
    ("use_nGPT", False), ("scale_router_input", False),
    ("value_norm", False), ("up_proj_norm", False), ("group_norm_size", 1),
    ("num_kv_heads_for_linear_attn", 0),
)


def fields(config: dict) -> dict:
    """The published ``config.json`` keys as ``LingConfig`` fields."""
    for key, want in _BUILT_FOR:
        if config.get(key, want) != want:
            raise ManifestError(
                f"the ling block is built for {key} = {want!r}, not "
                f"{config[key]!r}")
    if not os.path.isfile(os.path.join(
            os.path.dirname(_BASE), "ray_tpu", "models", "ling.py")):
        # (asked of the files, not by import: the process that
        # orchestrates a run stays off jax)
        raise ManifestError(
            "this checkout's program has no hybrid KDA / MLA block "
            "(ray_tpu/models/ling.py): it cannot run a ling configuration")
    n = int(config["num_hidden_layers"])
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(config.get(key, [])[:n]):
            raise ManifestError(
                f"{key} clamps a layer that is kept: the clamp is not built")
    if config["head_dim"] != config["qk_nope_head_dim"] \
            or config["rotary_dim"] != config["qk_rope_head_dim"]:
        raise ManifestError(
            "head_dim is read as KDA's key and value width and as MLA's "
            "unrotated width, rotary_dim as MLA's rotated width")
    held = config.get("held_experts")
    per_group = config["num_experts"] // config["n_group"]
    if held is not None and (held[0] % per_group or held[1] % per_group):
        raise ManifestError("held_experts must be whole groups")
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": n,
        "n_heads": int(config["num_attention_heads"]),
        "first_k_dense": int(config["first_k_dense_replace"]),
        "layer_group_size": int(config["layer_group_size"]),
        "dense_d_ff": int(config["intermediate_size"]),
        "d_ff": int(config["moe_intermediate_size"]),
        "shared_d_ff": int(config["moe_shared_expert_intermediate_size"]),
        "n_experts": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "n_group": int(config["n_group"]),
        "topk_group": int(config["topk_group"]),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "held_experts": None if held is None else [int(held[0]),
                                                   int(held[1])],
        "kda_head_dim": int(config["head_dim"]),
        "conv_kernel": int(config["short_conv_kernel_size"]),
        "kda_lower_bound": float(config["kda_lower_bound"]),
        "kv_lora_rank": int(config["kv_lora_rank"]),
        "qk_nope_head_dim": int(config["qk_nope_head_dim"]),
        "qk_rope_head_dim": int(config["qk_rope_head_dim"]),
        "v_head_dim": int(config["v_head_dim"]),
        "rope_theta": float(config["rope_theta"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "dtype": "bfloat16",
        # the depth the seeded weights are scaled for: the model's own
        "published_layers": int(config.get("published_num_hidden_layers",
                                           n)),
    }


# every kind of layer (KDA + dense, KDA + MoE, MLA + MoE), one of four
# groups of experts held
TINY_FIELDS = dict(
    vocab_size=256, d_model=64, n_layers=7, n_heads=4, first_k_dense=1,
    layer_group_size=6, dense_d_ff=128, d_ff=32, shared_d_ff=32,
    n_experts=32, top_k=4, n_group=4, topk_group=2,
    routed_scaling_factor=2.5, held_experts=[0, 8], kda_head_dim=16,
    conv_kernel=4, kda_lower_bound=-5.0, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e4,
    rms_eps=1e-6, dtype="float32", published_layers=42)


def build(m: dict, *, max_seq_len: int, remat: bool):
    """The program's model for fields ``m``: the one place that imports
    it. ``init_params`` makes the tree in the SERVING types, leaf by
    leaf (``ling.init_params``): as float32 masters the configuration
    the benchmark serves would be 21 GB. ``remat`` has nothing to switch:
    no cell trains this block."""
    import jax

    from ray_tpu.models import ling

    held = m.get("held_experts")
    cfg = ling.LingConfig(**{**m, "held_experts": held and tuple(held)},
                          max_seq_len=max_seq_len)

    def init_params(key):
        return ling.init_params(cfg, key)

    def param_logical_axes():
        """Every leaf whole on its device: the block is sharded by what
        a chip HOLDS (``held_experts``), not over a mesh."""
        return jax.tree_util.tree_map(
            lambda a: (None,) * a.ndim,
            jax.eval_shape(init_params, jax.random.PRNGKey(0)))

    return types.SimpleNamespace(
        cfg=cfg, init_params=init_params,
        loss_fn=lambda params, batch: ling.loss_fn(params, batch, cfg),
        param_logical_axes=param_logical_axes)


def reference():
    """``families/ling.reference.py``, beside this file."""
    return manifest.load_python("families", "ling.reference", _BASE)


# ------------------------------------------------ operations and bytes


def _held(m: dict) -> int:
    return (m.get("held_experts") or (0, m["n_experts"]))[1]


def _is_mla(m: dict, i: int) -> bool:
    return (i + 1) % m["layer_group_size"] == 0


def layer_counts(m: dict) -> dict:
    """How many layers of each kind the configuration has."""
    n = m["n_layers"]
    mla = sum(_is_mla(m, i) for i in range(n))
    dense = min(m["first_k_dense"], n)
    return {"kda": n - mla, "mla": mla, "dense": dense, "moe": n - dense}


def kda_params(m: dict) -> int:
    """One KDA attention: q, k, v, decay, output gate and output
    projections, beta, the convolution taps, A_log, dt_bias, the head
    norm."""
    d, w = m["d_model"], m["n_heads"] * m["kda_head_dim"]
    return (6 * d * w + d * m["n_heads"] + 3 * w * m["conv_kernel"]
            + m["n_heads"] + w + m["kda_head_dim"])


def mla_params(m: dict) -> int:
    """One MLA attention: q, the latent and rotated key, the latent's
    expansion, the head gate, the output projection, the two norms."""
    d, h = m["d_model"], m["n_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    r = m["kv_lora_rank"]
    return (d * h * qk + d * (r + m["qk_rope_head_dim"])
            + r * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + d * h + h * m["v_head_dim"] * d + qk + r)


def expert_params(m: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["d_model"] * m["d_ff"]


def moe_fixed_params(m: dict) -> int:
    """What an expert layer holds beside its routed experts: the router
    with its bias, and the shared expert."""
    d = m["d_model"]
    return d * m["n_experts"] + m["n_experts"] + 3 * d * m["shared_d_ff"]


def num_params(m: dict) -> int:
    """Parameters HELD here: of every expert layer the held experts."""
    d, v, c = m["d_model"], m["vocab_size"], layer_counts(m)
    return (2 * v * d + d + m["n_layers"] * 2 * d
            + c["kda"] * kda_params(m) + c["mla"] * mla_params(m)
            + c["dense"] * 3 * d * m["dense_d_ff"]
            + c["moe"] * (moe_fixed_params(m) + _held(m) * expert_params(m)))


def matmul_params(m: dict) -> int:
    """Parameters a token meets in a matrix product here: attention,
    the dense MLP or the router, the shared expert and the held share of
    its ``top_k`` experts (uniform routing), and the head."""
    d, c = m["d_model"], layer_counts(m)
    routed = m["top_k"] * _held(m) / m["n_experts"] * expert_params(m)
    return int(c["kda"] * kda_params(m) + c["mla"] * mla_params(m)
               + c["dense"] * 3 * d * m["dense_d_ff"]
               + c["moe"] * (moe_fixed_params(m) + routed)
               + d * m["vocab_size"])


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward, recomputation not counted: 6 per matmul
    parameter a token meets; the MLA layers' causal attention over the
    unmasked half of ``seq`` x ``seq`` (192-wide scores, 128-wide
    values); the KDA layers' state update and read, 4 products of
    dk x dv a head and token. (No cell trains this family.)"""
    c, h = layer_counts(m), m["n_heads"]
    mla = c["mla"] * 2 * h * seq * 0.5 * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])
    kda = c["kda"] * 2 * 4 * h * m["kda_head_dim"] ** 2
    return 3.0 * (2 * matmul_params(m) + mla + kda)


def experts_touched(m: dict, tokens: float) -> float:
    """HELD experts that get at least one of ``tokens`` tokens'
    assignments when each token's ``top_k`` distinct experts are uniform
    over all ``n_experts`` (the group limit keeps that symmetry):
    held x (1 - (1 - k/E)^tokens). A floor on what a layer must read."""
    e, k = m["n_experts"], m["top_k"]
    return _held(m) * (1.0 - (1.0 - k / e) ** tokens)


def state_bytes_per_slot(m: dict, max_len: int, itemsize: int = 2) -> dict:
    """What one stream's state takes, by kind: for each KDA layer the
    float32 matrix ``[H, dk, dk]`` and ``conv_kernel - 1`` rows of
    convolution input; for each MLA layer ``max_len`` rows of the latent
    and the rotated key."""
    c, h, dk = layer_counts(m), m["n_heads"], m["kda_head_dim"]
    return {
        "recurrent": c["kda"] * (
            h * dk * dk * 4 + (m["conv_kernel"] - 1) * 3 * h * dk * itemsize),
        "latent": c["mla"] * max_len * (
            m["kv_lora_rank"] + m["qk_rope_head_dim"]) * itemsize}


def decode_step_bytes(m: dict, slots: int, live_rows_per_slot: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step of ``slots`` streams cannot avoid: every
    weight outside the routed experts once (attention, dense MLP,
    router, shared expert, head), the held experts the slots' tokens
    touch (``experts_touched``), the slots' embedding rows, every slot's
    KDA state read AND written, and the latent rows that are live. A
    floor: an implementation that reads more reads LOW, never over
    100%."""
    d, c = m["d_model"], layer_counts(m)
    weights = (c["kda"] * kda_params(m) + c["mla"] * mla_params(m)
               + c["dense"] * 3 * d * m["dense_d_ff"]
               + c["moe"] * (moe_fixed_params(m)
                             + experts_touched(m, slots) * expert_params(m))
               + d * m["vocab_size"] + slots * d) * itemsize
    per_slot = state_bytes_per_slot(m, 1, itemsize)
    state = slots * (2 * per_slot["recurrent"]
                     + live_rows_per_slot * per_slot["latent"])
    return weights + state


def flash_calls(m: dict, batch: int, seq: int) -> list:
    """None: the block calls no flash kernel (KDA has no softmax, the
    MLA layers attend with plain products)."""
    return []


def gmm_flops(rows: float, k: int, n: int) -> float:
    """One grouped matmul (``moe_gmm``) over ``rows`` assignment rows OF
    HELD EXPERTS, [rows, k] x [held, k, n]: the rows the kernel's grid
    visits. Its operand is padded to every assignment (tokens x top_k),
    so a count from the operand's shape is ``n_experts / held`` times
    the work: take the rows from the engine's ``held_assignments``."""
    return 2.0 * rows * k * n


def gmm_bytes(rows: float, k: int, n: int, touched: float,
              itemsize: int = 2) -> float:
    """Bytes that call cannot avoid: the ``touched`` held experts'
    matrices once, the held rows read and their results written."""
    return (touched * k * n + rows * k + rows * n) * itemsize
