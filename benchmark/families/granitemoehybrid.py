"""The family ``granitemoehybrid``: the language model of
Granite-4.0-H-Small as the benchmark knows it
(``ray_tpu/models/granite.py``): Mamba-2 mixers (a float32 state of
``mamba_d_head`` x ``mamba_d_state`` a head, one B and one C row for
all heads) with a softmax GQA layer without positions where
``layer_types`` says ``attention``, and in every layer a softmax router
over experts of which this chip holds ``held_experts = [first, count]``,
with a shared expert of its own width; a tied head and the model's four
multipliers. What a family file owes is listed in
``manifest.FAMILY_DUTIES``; the arithmetic takes the dict of ``fields``
and never imports the program. A configuration file names this file
with ``"family": "granitemoehybrid"``.
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
    ("position_embedding_type", "nope"), ("mamba_n_groups", 1),
    ("mamba_conv_bias", True), ("mamba_proj_bias", False),
    ("attention_bias", False), ("hidden_act", "silu"),
    ("normalization_function", "rmsnorm"), ("tie_word_embeddings", True),
)


def fields(config: dict) -> dict:
    """The published ``config.json`` keys as ``GraniteConfig`` fields."""
    for key, want in _BUILT_FOR:
        if config.get(key, want) != want:
            raise ManifestError(
                f"the granitemoehybrid block is built for {key} = "
                f"{want!r}, not {config[key]!r}")
    if not os.path.isfile(os.path.join(
            os.path.dirname(_BASE), "ray_tpu", "models", "granite.py")):
        # (asked of the files, not by import: the process that
        # orchestrates a run stays off jax)
        raise ManifestError(
            "this checkout's program has no Mamba-2 / NoPE GQA block "
            "(ray_tpu/models/granite.py): it cannot run a "
            "granitemoehybrid configuration")
    d, heads = int(config["hidden_size"]), int(config["mamba_n_heads"])
    if heads * int(config["mamba_d_head"]) != int(config["mamba_expand"]) * d:
        raise ManifestError(
            "mamba_n_heads x mamba_d_head must be mamba_expand x "
            "hidden_size: the mixer's inner width")
    n = int(config["num_hidden_layers"])
    held = config.get("held_experts")
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": d,
        "n_layers": n,
        # (a configuration cut in depth keeps the published list whole:
        # the layers it names past the cut are on other pipeline stages)
        "layer_types": [str(k) for k in config["layer_types"][:n]],
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": d // int(config["num_attention_heads"]),
        "ssm_heads": heads,
        "ssm_head_dim": int(config["mamba_d_head"]),
        "ssm_state": int(config["mamba_d_state"]),
        "conv_kernel": int(config["mamba_d_conv"]),
        # (the published kernel's tile: the program's chunk too)
        "ssm_chunk": int(config["mamba_chunk_size"]),
        "d_ff": int(config["intermediate_size"]),
        "shared_d_ff": int(config["shared_intermediate_size"]),
        "n_experts": int(config["num_local_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "n_group": 1, "topk_group": 1, "routed_scaling_factor": 1.0,
        "held_experts": None if held is None else [int(held[0]),
                                                   int(held[1])],
        "embedding_multiplier": float(config["embedding_multiplier"]),
        "residual_multiplier": float(config["residual_multiplier"]),
        "attention_multiplier": float(config["attention_multiplier"]),
        "logits_scaling": float(config["logits_scaling"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "dtype": "bfloat16",
        # the depth the seeded weights are scaled for: the model's own
        "published_layers": int(config.get("published_num_hidden_layers",
                                           n)),
    }


# two Mamba layers, an attention layer, a Mamba layer; a quarter of the
# experts held, a shared expert wider than an expert, heads x head_dim
# unequal to the hidden size. Small enough that the cell's CPU rehearsal
# (a decode chunk and two prefill buckets to compile) ends inside a
# minute beside five other test processes. The embedding's multiplier
# and the logits' divisor are chosen so that the logits spread as at the
# published width (sqrt(32) / (16 x 6) / 2 = 0.029 for sqrt(4096) / (16
# x 12) / 16 = 0.021): the reference's ``SERVE_TOP2_GAP`` is a number of
# that scale, and the rehearsal's served tokens are held to it.
TINY_FIELDS = dict(
    vocab_size=256, d_model=32, n_layers=4,
    layer_types=["mamba", "mamba", "attention", "mamba"], n_heads=4,
    n_kv_heads=2, head_dim=16, ssm_heads=4, ssm_head_dim=8, ssm_state=16,
    conv_kernel=4, ssm_chunk=8, d_ff=16, shared_d_ff=32, n_experts=8,
    top_k=2, n_group=1, topk_group=1, routed_scaling_factor=1.0,
    held_experts=[0, 2], embedding_multiplier=6.0, residual_multiplier=0.22,
    attention_multiplier=0.0625, logits_scaling=2.0, rms_eps=1e-5,
    dtype="float32", published_layers=40)


def build(m: dict, *, max_seq_len: int, remat: bool):
    """The program's model for fields ``m``: the one place that imports
    it. ``init_params`` makes the tree in the SERVING types, leaf by
    leaf (``granite.init_params``). ``remat`` has nothing to switch: no
    cell trains this block."""
    import jax

    from ray_tpu.models import granite

    held = m.get("held_experts")
    cfg = granite.GraniteConfig(**{
        **m, "held_experts": held and tuple(held),
        "layer_types": tuple(m["layer_types"])}, max_seq_len=max_seq_len)

    def init_params(key):
        return granite.init_params(cfg, key)

    def param_logical_axes():
        """Every leaf whole on its device: the block is sharded by what
        a chip HOLDS (``held_experts``), not over a mesh."""
        return jax.tree_util.tree_map(
            lambda a: (None,) * a.ndim,
            jax.eval_shape(init_params, jax.random.PRNGKey(0)))

    return types.SimpleNamespace(
        cfg=cfg, init_params=init_params,
        loss_fn=lambda params, batch: granite.loss_fn(params, batch, cfg),
        param_logical_axes=param_logical_axes)


def reference():
    """``families/granitemoehybrid.reference.py``, beside this file."""
    return manifest.load_python("families", "granitemoehybrid.reference",
                                _BASE)


# ------------------------------------------------ operations and bytes


def _held(m: dict) -> int:
    return (m.get("held_experts") or (0, m["n_experts"]))[1]


def layer_counts(m: dict) -> dict:
    """How many layers of each kind the configuration has."""
    full = sum(k == "attention" for k in m["layer_types"])
    return {"ssm": m["n_layers"] - full, "full": full, "moe": m["n_layers"]}


def _inner(m: dict) -> int:
    return m["ssm_heads"] * m["ssm_head_dim"]


def kv_row_bytes(m: dict, itemsize: int = 2) -> int:
    """One position's k and v of one attention layer."""
    return 2 * m["n_kv_heads"] * m["head_dim"] * itemsize


def ssm_params(m: dict) -> int:
    """One Mamba-2 mixer: the input projection (gate | x | B | C | dt),
    the convolution's taps and bias, A_log, D and dt_bias a head, the
    gated norm, the output projection."""
    d, inner, n, h = m["d_model"], _inner(m), m["ssm_state"], m["ssm_heads"]
    conv = inner + 2 * n
    return (d * (inner + conv + h) + conv * m["conv_kernel"] + conv + 3 * h
            + inner + inner * d)


def gqa_params(m: dict) -> int:
    """One attention: q, k, v and the output projection (no norm, no
    bias, no gate)."""
    d, hd = m["d_model"], m["head_dim"]
    return d * (m["n_heads"] + 2 * m["n_kv_heads"]) * hd \
        + m["n_heads"] * hd * d


def expert_params(m: dict) -> int:
    """One routed expert: gate and up (the published ``input_linear``'s
    halves) and down."""
    return 3 * m["d_model"] * m["d_ff"]


def moe_fixed_params(m: dict) -> int:
    """What an expert layer holds beside its routed experts: the router
    (no bias) and the shared expert."""
    d = m["d_model"]
    return d * m["n_experts"] + 3 * d * m["shared_d_ff"]


def num_params(m: dict) -> int:
    """Parameters HELD here: of every expert layer the held experts; the
    embedding once (it is the head too)."""
    d, v, c = m["d_model"], m["vocab_size"], layer_counts(m)
    return (v * d + d + m["n_layers"] * 2 * d
            + c["ssm"] * ssm_params(m) + c["full"] * gqa_params(m)
            + c["moe"] * (moe_fixed_params(m) + _held(m) * expert_params(m)))


def matmul_params(m: dict) -> int:
    """Parameters a token meets in a matrix product here: the mixers'
    two projections, attention, the router, the shared expert and the
    held share of its ``top_k`` experts (uniform routing), and the
    head."""
    d, c = m["d_model"], layer_counts(m)
    inner = _inner(m)
    mixer = d * (2 * inner + 2 * m["ssm_state"] + m["ssm_heads"]) + inner * d
    routed = m["top_k"] * _held(m) / m["n_experts"] * expert_params(m)
    return int(c["ssm"] * mixer + c["full"] * gqa_params(m)
               + c["moe"] * (moe_fixed_params(m) + routed)
               + d * m["vocab_size"])


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward, recomputation not counted: 6 per matmul
    parameter a token meets; the attention layers' causal attention over
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
    what a layer must read."""
    e, k = m["n_experts"], m["top_k"]
    return _held(m) * (1.0 - (1.0 - k / e) ** tokens)


def ssm_state_bytes(m: dict, slots: int) -> int:
    """One Mamba layer's float32 state ``H`` over ``slots`` slots."""
    return slots * _inner(m) * m["ssm_state"] * 4


def ssd_step_bytes(m: dict, slots: int) -> int:
    """What one call of the ``ssd_step`` kernel cannot avoid: every
    slot's state of one layer read once and written once."""
    return 2 * ssm_state_bytes(m, slots)


def ssd_chunk_work(m: dict, rows: int, chunk: int) -> dict:
    """The chunked scan of one Mamba layer over ``rows`` rows of one
    prompt in chunks of ``chunk``, from shapes alone (the XLA body of
    ``ops/ssd_chunk.py``; no kernel yet reads it): ``flops`` = the
    products (C B^T once for all heads; a head's masked scores against
    its inputs; the chunk's outer products into the state; C against
    the state at the chunk's start), ``bytes`` = x, B, C, dt read, y
    written (float32) and the state read and written once."""
    h, p, n = m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"]
    flops = 2.0 * rows * (chunk * n + h * chunk * p + 2 * h * p * n)
    moved = 4.0 * (rows * (2 * h * p + 2 * n + h) + 2 * h * p * n)
    return {"flops": flops, "bytes": moved}


def state_bytes_per_slot(m: dict, max_len: int, itemsize: int = 2) -> dict:
    """What one stream's state takes, by kind: for each Mamba layer the
    float32 state ``[H, P, N]`` and ``conv_kernel - 1`` rows of
    convolution input; for each attention layer ``max_len`` rows of k
    and v."""
    c = layer_counts(m)
    return {
        "recurrent": c["ssm"] * (
            ssm_state_bytes(m, 1) + (m["conv_kernel"] - 1)
            * (_inner(m) + 2 * m["ssm_state"]) * itemsize),
        "full": c["full"] * max_len * kv_row_bytes(m, itemsize)}


def decode_step_bytes(m: dict, slots: int, live_rows_per_slot: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step of ``slots`` streams cannot avoid: every
    weight outside the routed experts once (mixers, attention, router,
    shared expert, the head = the embedding), the held experts the
    slots' tokens touch (``experts_touched``), every slot's recurrent
    state read AND written once a Mamba layer, and the LIVE rows of k
    and v of the attention layers. A floor: an implementation that reads
    more reads LOW, never over 100%."""
    d, c = m["d_model"], layer_counts(m)
    weights = (c["ssm"] * ssm_params(m) + c["full"] * gqa_params(m)
               + c["moe"] * (moe_fixed_params(m)
                             + experts_touched(m, slots) * expert_params(m))
               + d * m["vocab_size"]) * itemsize
    per_slot = state_bytes_per_slot(m, 1, itemsize)
    return weights + slots * (2 * per_slot["recurrent"]
                              + live_rows_per_slot * per_slot["full"])


def flash_calls(m: dict, batch: int, seq: int) -> list:
    """The forward kernel once an attention layer in a prefill; no cell
    trains the block, so a train step's list is empty."""
    return []


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
