"""The family ``nemotron_h``: the language model of
NVIDIA-Nemotron-3-Nano-30B-A3B as the benchmark knows it
(``ray_tpu/models/nemotron.py``): ``hybrid_override_pattern`` names one
sublayer a block, a Mamba-2 mixer (``M``: a float32 state of
``mamba_head_dim`` x ``ssm_state_size`` a head, B and C in ``n_groups``
groups of heads, the gated norm by group), an expert layer (``E``: a
sigmoid top-k router with a selection bias over experts of which this
chip holds ``held_experts = [first, count]``, each two matrices round a
squared relu, with a shared expert of the same form) or a GQA attention
without positions (``*``); an untied head, no multiplier. What a family
file owes is listed in ``manifest.FAMILY_DUTIES``; the arithmetic takes
the dict of ``fields`` and never imports the program. A configuration
file names this file with ``"family": "nemotron_h"``.
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
    ("model_type", "nemotron_h"), ("mlp_hidden_act", "relu2"),
    ("mamba_hidden_act", "silu"), ("attention_bias", False),
    ("mamba_proj_bias", False), ("mlp_bias", False), ("use_bias", False),
    ("use_conv_bias", True), ("tie_word_embeddings", False),
    ("norm_topk_prob", True), ("n_shared_experts", 1),
    ("residual_in_fp32", False), ("sliding_window", None),
)
_KINDS = "ME*"


def fields(config: dict) -> dict:
    """The published ``config.json`` keys as ``NemotronConfig`` fields.
    ``n_groups`` is the MIXER's (groups of heads that share a B and a
    C); ``n_group`` and ``topk_group`` are the ROUTER's: each is read by
    its own key."""
    for key, want in _BUILT_FOR:
        if config.get(key, want) != want:
            raise ManifestError(
                f"the nemotron_h block is built for {key} = {want!r}, not "
                f"{config[key]!r}")
    if not os.path.isfile(os.path.join(
            os.path.dirname(_BASE), "ray_tpu", "models", "nemotron.py")):
        # (asked of the files, not by import: the process that
        # orchestrates a run stays off jax)
        raise ManifestError(
            "this checkout's program has no block of one sublayer a layer "
            "with grouped Mamba-2 mixers and two-matrix relu2 experts "
            "(ray_tpu/models/nemotron.py): it cannot run a nemotron_h "
            "configuration")
    pattern = str(config["hybrid_override_pattern"])
    if set(pattern) - set(_KINDS):
        # ("-" is the published dense MLP block: this model has none)
        raise ManifestError(
            f"hybrid_override_pattern {pattern!r} must name one of "
            f"{_KINDS!r} for each block: the block has no dense MLP ('-')")
    if len(pattern) != int(config["num_hidden_layers"]):
        raise ManifestError(
            "hybrid_override_pattern must name each of the "
            f"{config['num_hidden_layers']} blocks")
    if int(config["n_group"]) != 1 or int(config["topk_group"]) != 1:
        raise ManifestError(
            "the router is proven for one group (n_group 1, topk_group "
            f"1), not {config['n_group']} / {config['topk_group']}")
    heads, groups = int(config["mamba_num_heads"]), int(config["n_groups"])
    if heads % groups:
        raise ManifestError(
            f"mamba_num_heads {heads} must be a multiple of n_groups "
            f"{groups}: a group's heads share its B and C")
    held = config.get("held_experts")
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "pattern": pattern,
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "ssm_heads": heads,
        "ssm_head_dim": int(config["mamba_head_dim"]),
        "ssm_state": int(config["ssm_state_size"]),
        "ssm_groups": groups,
        "conv_kernel": int(config["conv_kernel"]),
        # (the published ``chunk_size`` 128 is its kernel's tile and
        # changes no result; the program's scan runs chunks of 256 rows,
        # what Granite's cell read best: ``assumed.chunk_size``)
        "ssm_chunk": 256,
        "d_ff": int(config["moe_intermediate_size"]),
        "shared_d_ff": int(config["moe_shared_expert_intermediate_size"]),
        "n_experts": int(config["n_routed_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "n_group": int(config["n_group"]),
        "topk_group": int(config["topk_group"]),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "held_experts": None if held is None else [int(held[0]),
                                                   int(held[1])],
        "rms_eps": float(config["layer_norm_epsilon"]),
        "dtype": "bfloat16",
        # the depth the seeded weights are scaled for: the model's own
        "published_layers": int(config.get("published_num_hidden_layers",
                                           len(pattern))),
    }


# the published string's head and an uneven tail (M E M * E M E M E),
# two groups of two heads, a quarter of the experts held, a shared expert
# wider than an expert, heads x head_dim unequal to the hidden size.
# Small enough that the cell's CPU rehearsal (a decode chunk and three
# prefill buckets to compile) ends inside a minute beside five other test
# processes. Its weights are scaled for its own depth.
TINY_FIELDS = dict(
    vocab_size=256, d_model=32, pattern="MEM*EMEME", n_heads=4,
    n_kv_heads=2, head_dim=16, ssm_heads=4, ssm_head_dim=8, ssm_state=16,
    ssm_groups=2, conv_kernel=4, ssm_chunk=8, d_ff=24, shared_d_ff=48,
    n_experts=8, top_k=2, n_group=1, topk_group=1,
    routed_scaling_factor=2.5, held_experts=[0, 2], rms_eps=1e-5,
    dtype="float32", published_layers=9)


def build(m: dict, *, max_seq_len: int, remat: bool):
    """The program's model for fields ``m``: the one place that imports
    it. ``init_params`` makes the tree in the SERVING types, leaf by
    leaf (``nemotron.init_params``). ``remat`` has nothing to switch: no
    cell trains this block."""
    import jax

    from ray_tpu.models import nemotron

    held = m.get("held_experts")
    cfg = nemotron.NemotronConfig(**{
        **m, "held_experts": held and tuple(held)}, max_seq_len=max_seq_len)

    def init_params(key):
        return nemotron.init_params(cfg, key)

    def param_logical_axes():
        """Every leaf whole on its device: the block is sharded by what
        a chip HOLDS (``held_experts``), not over a mesh."""
        return jax.tree_util.tree_map(
            lambda a: (None,) * a.ndim,
            jax.eval_shape(init_params, jax.random.PRNGKey(0)))

    return types.SimpleNamespace(
        cfg=cfg, init_params=init_params,
        loss_fn=lambda params, batch: nemotron.loss_fn(params, batch, cfg),
        param_logical_axes=param_logical_axes)


def reference():
    """``families/nemotron_h.reference.py``, beside this file."""
    return manifest.load_python("families", "nemotron_h.reference", _BASE)


# ------------------------------------------------ operations and bytes


def _held(m: dict) -> int:
    return (m.get("held_experts") or (0, m["n_experts"]))[1]


def layer_counts(m: dict) -> dict:
    """How many blocks of each kind the configuration has."""
    return {"ssm": m["pattern"].count("M"), "full": m["pattern"].count("*"),
            "moe": m["pattern"].count("E")}


def _inner(m: dict) -> int:
    return m["ssm_heads"] * m["ssm_head_dim"]


def _conv_width(m: dict) -> int:
    return _inner(m) + 2 * m["ssm_groups"] * m["ssm_state"]


def kv_row_bytes(m: dict, itemsize: int = 2) -> int:
    """One position's k and v of one attention block."""
    return 2 * m["n_kv_heads"] * m["head_dim"] * itemsize


def ssm_params(m: dict) -> int:
    """One Mamba-2 mixer: the input projection (gate | x | every group's
    B | every group's C | dt), the convolution's taps and bias, A_log, D
    and dt_bias a head, the gated norm, the output projection."""
    d, inner, h = m["d_model"], _inner(m), m["ssm_heads"]
    conv = _conv_width(m)
    return (d * (inner + conv + h) + conv * m["conv_kernel"] + conv + 3 * h
            + inner + inner * d)


def gqa_params(m: dict) -> int:
    """One attention: q, k, v and the output projection (no norm, no
    bias)."""
    d, hd = m["d_model"], m["head_dim"]
    return d * (m["n_heads"] + 2 * m["n_kv_heads"]) * hd \
        + m["n_heads"] * hd * d


def expert_params(m: dict) -> int:
    """One routed expert: up and down (no gate)."""
    return 2 * m["d_model"] * m["d_ff"]


def moe_fixed_params(m: dict) -> int:
    """What an expert block holds beside its routed experts: the router
    with its selection bias and the shared expert (two matrices)."""
    d = m["d_model"]
    return d * m["n_experts"] + m["n_experts"] + 2 * d * m["shared_d_ff"]


def num_params(m: dict) -> int:
    """Parameters HELD here: of every expert block the held experts; the
    embedding and the head (untied); a norm a block and the final one."""
    d, v, c = m["d_model"], m["vocab_size"], layer_counts(m)
    return (2 * v * d + d + len(m["pattern"]) * d
            + c["ssm"] * ssm_params(m) + c["full"] * gqa_params(m)
            + c["moe"] * (moe_fixed_params(m) + _held(m) * expert_params(m)))


def matmul_params(m: dict) -> int:
    """Parameters a token meets in a matrix product here: the mixers'
    two projections, attention, the router, the shared expert and the
    held share of its ``top_k`` experts (uniform routing), and the
    head."""
    d, c = m["d_model"], layer_counts(m)
    inner = _inner(m)
    mixer = d * (inner + _conv_width(m) + m["ssm_heads"]) + inner * d
    routed = m["top_k"] * _held(m) / m["n_experts"] * expert_params(m)
    return int(c["ssm"] * mixer + c["full"] * gqa_params(m)
               + c["moe"] * (d * m["n_experts"] + 2 * d * m["shared_d_ff"]
                             + routed)
               + d * m["vocab_size"])


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward, recomputation not counted: 6 per matmul
    parameter a token meets; the attention blocks' causal attention over
    the unmasked half of ``seq`` x ``seq``; the mixers' state update and
    read, 2 products of P x N a head and token. (No cell trains this
    family.)"""
    c = layer_counts(m)
    attn = c["full"] * 2 * m["n_heads"] * seq * 0.5 * 2 * m["head_dim"]
    ssm = c["ssm"] * 2 * 2 * _inner(m) * m["ssm_state"]
    return 3.0 * (2 * matmul_params(m) + attn + ssm)


def experts_touched(m: dict, tokens: float) -> float:
    """HELD experts that get at least one of ``tokens`` tokens'
    assignments when each token's ``top_k`` distinct experts are uniform
    over all ``n_experts``: held x (1 - (1 - k/E)^tokens). A floor on
    what a block must read."""
    e, k = m["n_experts"], m["top_k"]
    return _held(m) * (1.0 - (1.0 - k / e) ** tokens)


def ssm_state_bytes(m: dict, slots: int) -> int:
    """One M block's float32 state ``H`` over ``slots`` slots."""
    return slots * _inner(m) * m["ssm_state"] * 4


def ssd_step_bytes(m: dict, slots: int) -> int:
    """What one call of the ``ssd_step`` kernel cannot avoid: every
    slot's state of one block read once and written once (the groups' B
    and C columns, 2 G N numbers a slot, are under a thousandth of it)."""
    return 2 * ssm_state_bytes(m, slots)


def state_bytes_per_slot(m: dict, max_len: int, itemsize: int = 2) -> dict:
    """What one stream's state takes, by kind: for each M block the
    float32 state ``[H, P, N]`` and ``conv_kernel - 1`` rows of
    convolution input; for each attention block ``max_len`` rows of k
    and v."""
    c = layer_counts(m)
    return {
        "recurrent": c["ssm"] * (
            ssm_state_bytes(m, 1) + (m["conv_kernel"] - 1)
            * _conv_width(m) * itemsize),
        "full": c["full"] * max_len * kv_row_bytes(m, itemsize)}


def decode_step_bytes(m: dict, slots: int, live_rows_per_slot: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step of ``slots`` streams cannot avoid: every
    weight outside the routed experts once (mixers, attention, router,
    shared expert, the head; of the embedding a row a slot: nothing),
    the held experts the slots' tokens touch (``experts_touched``),
    every slot's recurrent state read AND written once an M block, and
    the LIVE rows of k and v of the attention blocks. A floor: an
    implementation that reads more reads LOW, never over 100%."""
    d, c = m["d_model"], layer_counts(m)
    weights = (c["ssm"] * ssm_params(m) + c["full"] * gqa_params(m)
               + c["moe"] * (moe_fixed_params(m)
                             + experts_touched(m, slots) * expert_params(m))
               + d * m["vocab_size"]) * itemsize
    per_slot = state_bytes_per_slot(m, 1, itemsize)
    return weights + slots * (2 * per_slot["recurrent"]
                              + live_rows_per_slot * per_slot["full"])


def flash_calls(m: dict, batch: int, seq: int) -> list:
    """The forward kernel once an attention block in a prefill; no cell
    trains the block, so a train step's list is empty."""
    return []


def gmm_flops(rows: float, k: int, n: int) -> float:
    """One grouped matmul (``moe_gmm``) over ``rows`` assignment rows OF
    HELD EXPERTS, [rows, k] x [held, k, n] (``families/solar_open2.py``
    says why the rows come from the engine's ``held_assignments``). An
    expert block calls it TWICE a step here (up, down): the reader tells
    them apart by the columns."""
    return 2.0 * rows * k * n


def gmm_bytes(rows: float, k: int, n: int, touched: float,
              itemsize: int = 2) -> float:
    """Bytes that call cannot avoid: the ``touched`` held experts'
    matrices once, the held rows read and their results written."""
    return (touched * k * n + rows * k + rows * n) * itemsize
