"""The family ``solar_open2``: the language model of Solar-Open2-250B as
the benchmark knows it (``ray_tpu/models/solar.py``): KDA layers (delta
rule, negative eigenvalues allowed, low-rank decay and gate) with one
gated softmax GQA layer without positions every ``gqa_interval + 1``,
and in every layer a sigmoid router over experts of which this chip
holds ``held_experts = [first, count]``, with a shared expert. What a
family file owes is listed in ``manifest.FAMILY_DUTIES``; the arithmetic
takes the dict of ``fields`` and never imports the program. A
configuration file names this file with ``"family": "solar_open2"``.
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
    ("use_rope", False), ("use_gqa_gate", True),
    ("kda_use_full_proj", False), ("kda_allow_neg_eigval", True),
    ("norm_topk_prob", True), ("first_k_dense_replace", 0),
    ("n_shared_experts", 1), ("tie_word_embeddings", False),
)


def fields(config: dict) -> dict:
    """The published ``config.json`` keys as ``SolarConfig`` fields."""
    for key, want in _BUILT_FOR:
        if config.get(key, want) != want:
            raise ManifestError(
                f"the solar_open2 block is built for {key} = {want!r}, "
                f"not {config[key]!r}")
    if not os.path.isfile(os.path.join(
            os.path.dirname(_BASE), "ray_tpu", "models", "solar.py")):
        # (asked of the files, not by import: the process that
        # orchestrates a run stays off jax)
        raise ManifestError(
            "this checkout's program has no KDA / gated NoPE GQA block "
            "(ray_tpu/models/solar.py): it cannot run a solar_open2 "
            "configuration")
    lin = config["linear_attn_config"]
    if lin["num_heads"] != config["num_attention_heads"] \
            or lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise ManifestError(
            "the block is built for as many KDA heads as query heads "
            "(n_heads is both), each with its own key and value")
    n = int(config["num_hidden_layers"])
    # (a configuration cut in depth keeps the published list whole: the
    # layers it names past the cut are on other pipeline stages)
    gqa = [int(i) for i in config["gqa_layers"] if i < n]
    held = config.get("held_experts")
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": n,
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "gqa_layers": gqa,
        "gqa_interval": int(config["gqa_interval"]),
        "kda_head_dim": int(lin["head_dim"]),
        "conv_kernel": int(lin["short_conv_kernel_size"]),
        # kda_use_full_proj false: Kimi Linear's low rank, the head's width
        "kda_rank": int(lin["head_dim"]),
        "d_ff": int(config["moe_intermediate_size"]),
        "shared_d_ff": int(config["moe_intermediate_size"])
        * int(config["n_shared_experts"]),
        "n_experts": int(config["n_routed_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "n_group": 1, "topk_group": 1,
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "held_experts": None if held is None else [int(held[0]),
                                                   int(held[1])],
        "rms_eps": float(config["rms_norm_eps"]),
        "dtype": "bfloat16",
        # the depth the seeded weights are scaled for: the model's own
        "published_layers": int(config.get("published_num_hidden_layers",
                                           n)),
    }


# one period and a layer of the next (GQA, KDA x 3, GQA), a quarter of
# the experts held, heads x head_dim unequal to the hidden size
TINY_FIELDS = dict(
    vocab_size=256, d_model=48, n_layers=5, n_heads=4, n_kv_heads=2,
    head_dim=16, gqa_layers=[0, 4], gqa_interval=3, kda_head_dim=16,
    conv_kernel=4, kda_rank=8, d_ff=32, shared_d_ff=32, n_experts=16,
    top_k=4, n_group=1, topk_group=1, routed_scaling_factor=1.0,
    held_experts=[0, 4], rms_eps=1e-5, dtype="float32", published_layers=48)


def build(m: dict, *, max_seq_len: int, remat: bool):
    """The program's model for fields ``m``: the one place that imports
    it. ``init_params`` makes the tree in the SERVING types, leaf by
    leaf (``solar.init_params``). ``remat`` has nothing to switch: no
    cell trains this block."""
    import jax

    from ray_tpu.models import solar

    held = m.get("held_experts")
    cfg = solar.SolarConfig(**{
        **m, "held_experts": held and tuple(held),
        "gqa_layers": tuple(m["gqa_layers"])}, max_seq_len=max_seq_len)

    def init_params(key):
        return solar.init_params(cfg, key)

    def param_logical_axes():
        """Every leaf whole on its device: the block is sharded by what
        a chip HOLDS (``held_experts``), not over a mesh."""
        return jax.tree_util.tree_map(
            lambda a: (None,) * a.ndim,
            jax.eval_shape(init_params, jax.random.PRNGKey(0)))

    return types.SimpleNamespace(
        cfg=cfg, init_params=init_params,
        loss_fn=lambda params, batch: solar.loss_fn(params, batch, cfg),
        param_logical_axes=param_logical_axes)


def reference():
    """``families/solar_open2.reference.py``, beside this file."""
    return manifest.load_python("families", "solar_open2.reference", _BASE)


# ------------------------------------------------ operations and bytes


def _held(m: dict) -> int:
    return (m.get("held_experts") or (0, m["n_experts"]))[1]


def layer_counts(m: dict) -> dict:
    """How many layers of each kind the configuration has."""
    full = len(m["gqa_layers"])
    return {"kda": m["n_layers"] - full, "full": full, "moe": m["n_layers"]}


def kv_row_bytes(m: dict, itemsize: int = 2) -> int:
    """One position's k and v of one GQA layer."""
    return 2 * m["n_kv_heads"] * m["head_dim"] * itemsize


def kda_params(m: dict) -> int:
    """One KDA attention: q, k, v and output projections, the decay's
    and the gate's low-rank pairs, beta, the convolution taps, A_log,
    dt_bias, the head norm."""
    d, w, r = m["d_model"], m["n_heads"] * m["kda_head_dim"], m["kda_rank"]
    return (4 * d * w + 2 * (d * r + r * w) + d * m["n_heads"]
            + 3 * w * m["conv_kernel"] + m["n_heads"] + w
            + m["kda_head_dim"])


def gqa_params(m: dict) -> int:
    """One GQA attention: q, k, v, the elementwise gate and the output
    projection (no norm, no bias)."""
    d, hd = m["d_model"], m["head_dim"]
    return d * (m["n_heads"] + 2 * m["n_kv_heads"]) * hd \
        + 2 * d * m["n_heads"] * hd


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
            + c["kda"] * kda_params(m) + c["full"] * gqa_params(m)
            + c["moe"] * (moe_fixed_params(m) + _held(m) * expert_params(m)))


def matmul_params(m: dict) -> int:
    """Parameters a token meets in a matrix product here: attention, the
    router, the shared expert and the held share of its ``top_k``
    experts (uniform routing), and the head."""
    d, c = m["d_model"], layer_counts(m)
    routed = m["top_k"] * _held(m) / m["n_experts"] * expert_params(m)
    return int(c["kda"] * kda_params(m) + c["full"] * gqa_params(m)
               + c["moe"] * (moe_fixed_params(m) + routed)
               + d * m["vocab_size"])


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward, recomputation not counted: 6 per matmul
    parameter a token meets; the GQA layers' causal attention over the
    unmasked half of ``seq`` x ``seq`` (scores and values, ``head_dim``
    wide); the KDA layers' state update and read, 4 products of dk x dv
    a head and token. (No cell trains this family.)"""
    c, h = layer_counts(m), m["n_heads"]
    attn = c["full"] * 2 * h * seq * 0.5 * 2 * m["head_dim"]
    kda = c["kda"] * 2 * 4 * h * m["kda_head_dim"] ** 2
    return 3.0 * (2 * matmul_params(m) + attn + kda)


def experts_touched(m: dict, tokens: float) -> float:
    """HELD experts that get at least one of ``tokens`` tokens'
    assignments when each token's ``top_k`` distinct experts are uniform
    over all ``n_experts``: held x (1 - (1 - k/E)^tokens). A floor on
    what a layer must read."""
    e, k = m["n_experts"], m["top_k"]
    return _held(m) * (1.0 - (1.0 - k / e) ** tokens)


def kda_state_bytes(m: dict, slots: int) -> int:
    """One KDA layer's float32 state ``S`` over ``slots`` slots: what a
    call of the ``kda_step`` kernel reads once and writes once."""
    return slots * m["n_heads"] * m["kda_head_dim"] ** 2 * 4


def state_bytes_per_slot(m: dict, max_len: int, itemsize: int = 2) -> dict:
    """What one stream's state takes, by kind: for each KDA layer the
    float32 matrix ``[H, dk, dk]`` and ``conv_kernel - 1`` rows of
    convolution input; for each GQA layer ``max_len`` rows of k and v."""
    c, h, dk = layer_counts(m), m["n_heads"], m["kda_head_dim"]
    return {
        "recurrent": c["kda"] * (
            kda_state_bytes(m, 1)
            + (m["conv_kernel"] - 1) * 3 * h * dk * itemsize),
        "full": c["full"] * max_len * kv_row_bytes(m, itemsize)}


def decode_step_bytes(m: dict, slots: int, live_rows_per_slot: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step of ``slots`` streams cannot avoid: every
    weight outside the routed experts once (attention, router, shared
    expert, head), the held experts the slots' tokens touch
    (``experts_touched``), the slots' embedding rows, every slot's KDA
    state read AND written once a KDA layer, and the LIVE rows of k and
    v of the GQA layers. A floor: an implementation that reads more
    reads LOW, never over 100%."""
    d, c = m["d_model"], layer_counts(m)
    weights = (c["kda"] * kda_params(m) + c["full"] * gqa_params(m)
               + c["moe"] * (moe_fixed_params(m)
                             + experts_touched(m, slots) * expert_params(m))
               + d * m["vocab_size"] + slots * d) * itemsize
    per_slot = state_bytes_per_slot(m, 1, itemsize)
    return weights + slots * (2 * per_slot["recurrent"]
                              + live_rows_per_slot * per_slot["full"])


def flash_calls(m: dict, batch: int, seq: int) -> list:
    """The forward kernel once a GQA layer in a prefill; no cell trains
    the block, so a train step's list is empty."""
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
