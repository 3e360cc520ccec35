"""The family ``exaone_moe``: the language model of K-EXAONE-236B-A23B as
the benchmark knows it (``ray_tpu/models/exaone.py``): sliding-window
attention layers (a ring of ``sliding_window`` rows a slot) beside full
ones as ``layer_types`` says, GQA with a head width of its own, a
leading dense MLP and then a sigmoid top-k router without a group limit
over experts of which this chip holds ``held_experts = [first, count]``,
with a shared expert. What a family file owes is listed in
``manifest.FAMILY_DUTIES``; the arithmetic takes the dict of ``fields``
and never imports the program. A configuration file names this file with
``"family": "exaone_moe"``.
"""

from __future__ import annotations

import os
import types

from benchmark import manifest
from benchmark.manifest import ManifestError

_BASE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"

# config.json keys the block reads one way only: (key, the value it is
# built for). Another value is refused, not approximated.
_BUILT_FOR = (
    ("model_type", "exaone_moe"), ("hidden_act", "silu"),
    ("scoring_func", "sigmoid"), ("norm_topk_prob", True),
    ("n_group", 1), ("topk_group", 1), ("tie_word_embeddings", False),
)


def fields(config: dict) -> dict:
    """The published ``config.json`` keys as ``ExaoneConfig`` fields."""
    for key, want in _BUILT_FOR:
        if config.get(key, want) != want:
            raise ManifestError(
                f"the exaone_moe block is built for {key} = {want!r}, not "
                f"{config[key]!r}")
    if not os.path.isfile(os.path.join(
            os.path.dirname(_BASE), "ray_tpu", "models", "exaone.py")):
        # (asked of the files, not by import: the process that
        # orchestrates a run stays off jax)
        raise ManifestError(
            "this checkout's program has no block with sliding-window "
            "layers beside full ones (ray_tpu/models/exaone.py): it cannot "
            "run an exaone_moe configuration")
    n = int(config["num_hidden_layers"])
    attn, mlp = list(config["layer_types"]), list(config["mlp_layer_types"])
    window = int(config["sliding_window"])
    if len(attn) != n or len(mlp) != n or set(attn) - {SLIDING, FULL} \
            or set(mlp) - {DENSE, SPARSE}:
        raise ManifestError(
            f"layer_types and mlp_layer_types must name the kind of each "
            f"of the {n} layers")
    if list(config["sliding_windows"]) != [
            window if kind == SLIDING else 0 for kind in attn]:
        raise ManifestError(
            "sliding_windows must give sliding_window for each sliding "
            "layer and 0 for each full one: one ring width is built")
    dense = int(config["first_k_dense_replace"])
    if mlp != [DENSE] * dense + [SPARSE] * (n - dense):
        raise ManifestError(
            "mlp_layer_types must be first_k_dense_replace dense layers "
            "and then sparse ones")
    if config["rope_parameters"].get("rope_type", "default") != "default":
        raise ManifestError("only the default rope_type is built")
    held = config.get("held_experts")
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": n,
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "layer_types": attn,
        "mlp_layer_types": mlp,
        "sliding_window": window,
        "dense_d_ff": int(config["intermediate_size"]),
        "d_ff": int(config["moe_intermediate_size"]),
        "shared_d_ff": int(config["moe_intermediate_size"])
        * int(config["num_shared_experts"]),
        "n_experts": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "n_group": int(config["n_group"]),
        "topk_group": int(config["topk_group"]),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "held_experts": None if held is None else [int(held[0]),
                                                   int(held[1])],
        "rope_theta": float(config["rope_parameters"]["rope_theta"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "dtype": "bfloat16",
        # the depth the seeded weights are scaled for: the model's own
        "published_layers": int(config.get("published_num_hidden_layers",
                                           n)),
    }


# both kinds of layer three to one and both MLPs, a window smaller than
# the rehearsal's sequences, 8 heads x 16 unequal to the hidden 48, a
# quarter of the experts held
TINY_FIELDS = dict(
    vocab_size=256, d_model=48, n_layers=5, n_heads=8, n_kv_heads=2,
    head_dim=16,
    layer_types=[SLIDING, SLIDING, SLIDING, FULL, SLIDING],
    mlp_layer_types=[DENSE, SPARSE, SPARSE, SPARSE, SPARSE],
    sliding_window=8, dense_d_ff=96, d_ff=32, shared_d_ff=32, n_experts=16,
    top_k=4, n_group=1, topk_group=1, routed_scaling_factor=2.5,
    held_experts=[0, 4], rope_theta=1e4, rms_eps=1e-5, dtype="float32",
    published_layers=48)


def build(m: dict, *, max_seq_len: int, remat: bool):
    """The program's model for fields ``m``: the one place that imports
    it. ``init_params`` makes the tree in the SERVING types, leaf by
    leaf (``exaone.init_params``): as float32 masters the configuration
    the benchmark serves would be 15 GB. ``remat`` has nothing to
    switch: no cell trains this block."""
    import jax

    from ray_tpu.models import exaone

    held = m.get("held_experts")
    cfg = exaone.ExaoneConfig(**{
        **m, "held_experts": held and tuple(held),
        "layer_types": tuple(m["layer_types"]),
        "mlp_layer_types": tuple(m["mlp_layer_types"])},
        max_seq_len=max_seq_len)

    def init_params(key):
        return exaone.init_params(cfg, key)

    def param_logical_axes():
        """Every leaf whole on its device: the block is sharded by what
        a chip HOLDS (``held_experts``), not over a mesh."""
        return jax.tree_util.tree_map(
            lambda a: (None,) * a.ndim,
            jax.eval_shape(init_params, jax.random.PRNGKey(0)))

    return types.SimpleNamespace(
        cfg=cfg, init_params=init_params,
        loss_fn=lambda params, batch: exaone.loss_fn(params, batch, cfg),
        param_logical_axes=param_logical_axes)


def reference():
    """``families/exaone_moe.reference.py``, beside this file."""
    return manifest.load_python("families", "exaone_moe.reference", _BASE)


# ------------------------------------------------ operations and bytes


def _held(m: dict) -> int:
    return (m.get("held_experts") or (0, m["n_experts"]))[1]


def layer_counts(m: dict) -> dict:
    """How many layers of each kind the configuration has."""
    return {"window": m["layer_types"].count(SLIDING),
            "full": m["layer_types"].count(FULL),
            "dense": m["mlp_layer_types"].count(DENSE),
            "moe": m["mlp_layer_types"].count(SPARSE)}


def kv_row_bytes(m: dict, itemsize: int = 2) -> int:
    """One position's k and v of one layer."""
    return 2 * m["n_kv_heads"] * m["head_dim"] * itemsize


def attn_params(m: dict) -> int:
    """One attention, of either kind: the q, k and v projections, the
    output projection, the two head-wise norms."""
    d, hd = m["d_model"], m["head_dim"]
    return (d * (m["n_heads"] + 2 * m["n_kv_heads"]) * hd
            + m["n_heads"] * hd * d + 2 * hd)


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
    return (2 * v * d + d + m["n_layers"] * (2 * d + attn_params(m))
            + c["dense"] * 3 * d * m["dense_d_ff"]
            + c["moe"] * (moe_fixed_params(m) + _held(m) * expert_params(m)))


def matmul_params(m: dict) -> int:
    """Parameters a token meets in a matrix product here: attention,
    the dense MLP or the router, the shared expert and the held share of
    its ``top_k`` experts (uniform routing), and the head."""
    d, c = m["d_model"], layer_counts(m)
    routed = m["top_k"] * _held(m) / m["n_experts"] * expert_params(m)
    return int(m["n_layers"] * attn_params(m)
               + c["dense"] * 3 * d * m["dense_d_ff"]
               + c["moe"] * (moe_fixed_params(m) + routed)
               + d * m["vocab_size"])


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward, recomputation not counted: 6 per matmul
    parameter a token meets; causal attention over the unmasked half of
    ``seq`` x ``seq`` in the full layers and over ``sliding_window``
    keys a query in the sliding ones (scores and values, ``head_dim``
    wide). (No cell trains this family.)"""
    c = layer_counts(m)
    keys = c["full"] * seq * 0.5 + c["window"] * min(
        m["sliding_window"], seq * 0.5)
    attn = 2 * m["n_heads"] * keys * 2 * m["head_dim"]
    return 3.0 * (2 * matmul_params(m) + attn)


def experts_touched(m: dict, tokens: float) -> float:
    """HELD experts that get at least one of ``tokens`` tokens'
    assignments when each token's ``top_k`` distinct experts are uniform
    over all ``n_experts``: held x (1 - (1 - k/E)^tokens). A floor on
    what a layer must read."""
    e, k = m["n_experts"], m["top_k"]
    return _held(m) * (1.0 - (1.0 - k / e) ** tokens)


def state_bytes_per_slot(m: dict, max_len: int, itemsize: int = 2) -> dict:
    """What one stream's state takes, by kind: ``sliding_window`` rows
    of k and v for each sliding layer, ``max_len`` for each full one."""
    c, row = layer_counts(m), kv_row_bytes(m, itemsize)
    return {"window": c["window"] * m["sliding_window"] * row,
            "full": c["full"] * max_len * row}


def live_row_bytes(m: dict, live_rows_per_slot: float,
                   itemsize: int = 2) -> dict:
    """The k and v bytes a decode step must read for ONE slot that holds
    ``live_rows_per_slot`` positions, by kind: every full layer all of
    them, every sliding layer at most its window."""
    c, row = layer_counts(m), kv_row_bytes(m, itemsize)
    return {"window": c["window"] * row * min(live_rows_per_slot,
                                              m["sliding_window"]),
            "full": c["full"] * row * live_rows_per_slot}


def decode_step_bytes(m: dict, slots: int, live_rows_per_slot: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step of ``slots`` streams cannot avoid: every
    weight outside the routed experts once (attention, dense MLP,
    router, shared expert, head), the held experts the slots' tokens
    touch (``experts_touched``), the slots' embedding rows, and the
    rows of k and v that are live: all of a slot's in the full layers,
    its window's in the sliding ones. A floor: an implementation that
    reads more reads LOW, never over 100%."""
    d, c = m["d_model"], layer_counts(m)
    weights = (m["n_layers"] * attn_params(m)
               + c["dense"] * 3 * d * m["dense_d_ff"]
               + c["moe"] * (moe_fixed_params(m)
                             + experts_touched(m, slots) * expert_params(m))
               + d * m["vocab_size"] + slots * d) * itemsize
    return weights + slots * sum(
        live_row_bytes(m, live_rows_per_slot, itemsize).values())


def flash_calls(m: dict, batch: int, seq: int) -> list:
    """None: the block calls no flash kernel (prefill attends with plain
    products and a band mask; no cell trains it)."""
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
