"""The family ``mimo_v2``: the language model of MiMo-V2.5 as the
benchmark knows it (``ray_tpu/models/mimo.py``): sliding-window layers
with a learned sink (a ring of ``sliding_window`` rows a slot, kv heads
of their own number) beside full GQA layers as ``hybrid_layer_pattern``
says, keys of ``head_dim`` beside values of ``v_head_dim``, the leading
``partial_rotary_factor`` of a head rotated at the kind's own theta, a
leading dense MLP and then a sigmoid top-k router over experts of which
this chip holds ``held_experts = [first, count]``, with NO shared
expert. What a family file owes is listed in ``manifest.FAMILY_DUTIES``;
the arithmetic takes the dict of ``fields`` and never imports the
program. A configuration file names this file with ``"family":
"mimo_v2"``.
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
    ("model_type", "mimo_v2"), ("hidden_act", "silu"),
    ("scoring_func", "sigmoid"), ("norm_topk_prob", True),
    ("topk_method", "noaux_tc"), ("n_group", 1), ("topk_group", 1),
    ("n_shared_experts", None), ("tie_word_embeddings", False),
    ("attention_bias", False), ("add_full_attention_sink_bias", False),
    ("add_swa_attention_sink_bias", True),
    ("attention_projection_layout", "fused_qkv"),
)
# keys of the window layers that the block takes from the full layers'
_SAME = (("swa_num_attention_heads", "num_attention_heads"),
         ("swa_head_dim", "head_dim"), ("swa_v_head_dim", "v_head_dim"),
         ("sliding_window_size", "sliding_window"))


def fields(config: dict) -> dict:
    """The published ``config.json`` keys as ``MimoConfig`` fields."""
    for key, want in _BUILT_FOR:
        if config.get(key, want) != want:
            raise ManifestError(
                f"the mimo_v2 block is built for {key} = {want!r}, not "
                f"{config[key]!r}")
    for key, same in _SAME:
        if config.get(key, config[same]) != config[same]:
            raise ManifestError(
                f"the mimo_v2 block is built for {key} = {same} "
                f"({config[same]!r}), not {config[key]!r}")
    if (config.get("rope_scaling") or {}).get("rope_type", "default") \
            != "default":
        raise ManifestError("only the default rope_type is built")
    if not os.path.isfile(os.path.join(
            os.path.dirname(_BASE), "ray_tpu", "models", "mimo.py")):
        # (asked of the files, not by import: the process that
        # orchestrates a run stays off jax)
        raise ManifestError(
            "this checkout's program has no block with window layers "
            "with a sink beside full ones, keys wider than values "
            "(ray_tpu/models/mimo.py): it cannot run a mimo_v2 "
            "configuration")
    n = int(config["num_hidden_layers"])
    attn = [int(x) for x in config["hybrid_layer_pattern"]]
    mlp = [int(x) for x in config["moe_layer_freq"]]
    if len(attn) != n or len(mlp) != n or set(attn + mlp) - {0, 1}:
        raise ManifestError(
            f"hybrid_layer_pattern and moe_layer_freq must give 0 or 1 "
            f"for each of the {n} layers")
    head = int(config["head_dim"])
    rotary = int(float(config["partial_rotary_factor"]) * head)  # floor
    if rotary % 2:
        raise ManifestError(f"{rotary} rotated numbers a head are no pairs")
    held = config.get("held_experts")
    scaling = config.get("routed_scaling_factor")
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": n,
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "window_kv_heads": int(config["swa_num_key_value_heads"]),
        "head_dim": head,
        "v_head_dim": int(config["v_head_dim"]),
        "rotary_dim": rotary,
        "layer_pattern": attn,
        "moe_pattern": mlp,
        "sliding_window": int(config["sliding_window"]),
        "rope_theta": float(config["rope_theta"]),
        "window_rope_theta": float(config["swa_rope_theta"]),
        "value_scale": float(config["attention_value_scale"]),
        "dense_d_ff": int(config["intermediate_size"]),
        "d_ff": int(config["moe_intermediate_size"]),
        "shared_d_ff": 0,
        "n_experts": int(config["n_routed_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "n_group": int(config["n_group"]),
        "topk_group": int(config["topk_group"]),
        "routed_scaling_factor": 1.0 if scaling is None else float(scaling),
        "held_experts": None if held is None else [int(held[0]),
                                                   int(held[1])],
        "rms_eps": float(config["layernorm_epsilon"]),
        "dtype": "bfloat16",
        # the depth the seeded weights are scaled for: the model's own
        "published_layers": int(config.get("published_num_hidden_layers",
                                           n)),
    }


# both kinds of layer (two and four kv heads) and both MLPs, keys of 24
# beside values of 16 with 8 numbers rotated, a window smaller than the
# rehearsal's sequences, a quarter of the experts held
TINY_FIELDS = dict(
    vocab_size=256, d_model=48, n_layers=5, n_heads=8, n_kv_heads=2,
    window_kv_heads=4, head_dim=24, v_head_dim=16, rotary_dim=8,
    layer_pattern=[0, 1, 1, 0, 1], moe_pattern=[0, 1, 1, 1, 1],
    sliding_window=8, rope_theta=1e7, window_rope_theta=1e4,
    value_scale=0.707, dense_d_ff=96, d_ff=32, shared_d_ff=0, n_experts=16,
    top_k=4, n_group=1, topk_group=1, routed_scaling_factor=1.0,
    held_experts=[0, 4], rms_eps=1e-5, dtype="float32", published_layers=48)


def build(m: dict, *, max_seq_len: int, remat: bool):
    """The program's model for fields ``m``: the one place that imports
    it. ``init_params`` makes the tree in the SERVING types, leaf by
    leaf (``mimo.init_params``). ``remat`` has nothing to switch: no
    cell trains this block."""
    import jax

    from ray_tpu.models import mimo

    held = m.get("held_experts")
    cfg = mimo.MimoConfig(**{
        **m, "held_experts": held and tuple(held),
        "layer_pattern": tuple(m["layer_pattern"]),
        "moe_pattern": tuple(m["moe_pattern"])}, max_seq_len=max_seq_len)

    def init_params(key):
        return mimo.init_params(cfg, key)

    def param_logical_axes():
        """Every leaf whole on its device: the block is sharded by what
        a chip HOLDS (``held_experts``), not over a mesh."""
        return jax.tree_util.tree_map(
            lambda a: (None,) * a.ndim,
            jax.eval_shape(init_params, jax.random.PRNGKey(0)))

    return types.SimpleNamespace(
        cfg=cfg, init_params=init_params,
        loss_fn=lambda params, batch: mimo.loss_fn(params, batch, cfg),
        param_logical_axes=param_logical_axes)


def reference():
    """``families/mimo_v2.reference.py``, beside this file."""
    return manifest.load_python("families", "mimo_v2.reference", _BASE)


# ------------------------------------------------ operations and bytes


def _held(m: dict) -> int:
    return (m.get("held_experts") or (0, m["n_experts"]))[1]


def layer_counts(m: dict) -> dict:
    """How many layers of each kind the configuration has."""
    window, moe = sum(m["layer_pattern"]), sum(m["moe_pattern"])
    return {"window": window, "full": m["n_layers"] - window,
            "dense": m["n_layers"] - moe, "moe": moe}


def kv_heads(m: dict) -> dict:
    return {"window": m["window_kv_heads"], "full": m["n_kv_heads"]}


def kv_row_bytes(m: dict, itemsize: int = 2) -> dict:
    """One position's k and v of one layer, by the layer's kind: its kv
    heads x (``head_dim`` + ``v_head_dim``). Nothing is padded: the
    cache lays a key row's heads so that 192 fills whole lanes
    (``decode_attention.pack_heads``)."""
    return {kind: h * (m["head_dim"] + m["v_head_dim"]) * itemsize
            for kind, h in kv_heads(m).items()}


def attn_params(m: dict) -> dict:
    """One attention, by kind: the fused q, k and v projection, the
    output projection and, in a window layer, a sink a query head."""
    d, hq = m["d_model"], m["n_heads"]
    return {kind: d * (hq * m["head_dim"]
                       + h * (m["head_dim"] + m["v_head_dim"]))
            + hq * m["v_head_dim"] * d + (hq if kind == "window" else 0)
            for kind, h in kv_heads(m).items()}


def expert_params(m: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["d_model"] * m["d_ff"]


def moe_fixed_params(m: dict) -> int:
    """What an expert layer holds beside its routed experts: the router
    with its bias (no shared expert)."""
    return m["d_model"] * m["n_experts"] + m["n_experts"]


def _attn_total(m: dict) -> int:
    c, a = layer_counts(m), attn_params(m)
    return c["window"] * a["window"] + c["full"] * a["full"]


def num_params(m: dict) -> int:
    """Parameters HELD here: of every expert layer the held experts."""
    d, v, c = m["d_model"], m["vocab_size"], layer_counts(m)
    return (2 * v * d + d + m["n_layers"] * 2 * d + _attn_total(m)
            + c["dense"] * 3 * d * m["dense_d_ff"]
            + c["moe"] * (moe_fixed_params(m) + _held(m) * expert_params(m)))


def matmul_params(m: dict) -> int:
    """Parameters a token meets in a matrix product here: attention,
    the dense MLP or the router and the held share of its ``top_k``
    experts (uniform routing), and the head."""
    d, c = m["d_model"], layer_counts(m)
    routed = m["top_k"] * _held(m) / m["n_experts"] * expert_params(m)
    return int(_attn_total(m) - c["window"] * m["n_heads"]
               + c["dense"] * 3 * d * m["dense_d_ff"]
               + c["moe"] * (moe_fixed_params(m) - m["n_experts"] + routed)
               + d * m["vocab_size"])


def band_keys(rows: int, window: int, first: int = 0) -> int:
    """(query, key) pairs that rows ``first`` .. ``first + rows - 1`` of
    a window layer see: row p its last ``min(p + 1, window)`` keys."""
    return sum(min(p + 1, window) for p in range(first, min(first + rows,
                                                            window))) \
        + max(0, first + rows - max(first, window)) * window


def causal_keys(rows: int, first: int = 0) -> int:
    """(query, key) pairs that rows ``first`` .. of a full layer see."""
    return rows * first + rows * (rows + 1) // 2


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward, recomputation not counted: 6 per matmul
    parameter a token meets; attention over the pairs a layer's mask
    leaves (scores ``head_dim`` wide, values ``v_head_dim``). (No cell
    trains this family: its flash kernel is forward only.)"""
    c = layer_counts(m)
    pairs = c["full"] * causal_keys(seq) + c["window"] * band_keys(
        seq, m["sliding_window"])
    attn = 2 * m["n_heads"] * pairs / seq * (m["head_dim"] + m["v_head_dim"])
    return 3.0 * (2 * matmul_params(m) + attn)


def experts_touched(m: dict, tokens: float) -> float:
    """HELD experts that get at least one of ``tokens`` tokens'
    assignments when each token's ``top_k`` distinct experts are uniform
    over all ``n_experts``: held x (1 - (1 - k/E)^tokens). A floor on
    what a layer must read."""
    e, k = m["n_experts"], m["top_k"]
    return _held(m) * (1.0 - (1.0 - k / e) ** tokens)


def state_bytes_per_slot(m: dict, max_len: int, itemsize: int = 2) -> dict:
    """What one stream's state takes, by kind: ``sliding_window`` ring
    rows for each window layer, ``max_len`` rows for each full one, each
    at its kind's own row width."""
    c, row = layer_counts(m), kv_row_bytes(m, itemsize)
    return {"window": c["window"] * m["sliding_window"] * row["window"],
            "full": c["full"] * max_len * row["full"]}


def live_row_bytes(m: dict, live_rows_per_slot: float,
                   itemsize: int = 2) -> dict:
    """The k and v bytes a decode step must read for ONE slot that holds
    ``live_rows_per_slot`` positions, by kind: every full layer all of
    them, every window layer at most its window, each at its own row
    width."""
    c, row = layer_counts(m), kv_row_bytes(m, itemsize)
    return {"window": c["window"] * row["window"] * min(
                live_rows_per_slot, m["sliding_window"]),
            "full": c["full"] * row["full"] * live_rows_per_slot}


def decode_step_bytes(m: dict, slots: int, live_rows_per_slot: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step of ``slots`` streams cannot avoid: every
    weight outside the routed experts once (attention, dense MLP,
    router, head), the held experts the slots' tokens touch
    (``experts_touched``), the slots' embedding rows, and the rows of k
    and v that are live: all of a slot's in the full layers, its
    window's in the window layers (a band's work is the band's). A
    floor: an implementation that reads more reads LOW, never over
    100%."""
    d, c = m["d_model"], layer_counts(m)
    weights = (_attn_total(m) + c["dense"] * 3 * d * m["dense_d_ff"]
               + c["moe"] * (moe_fixed_params(m)
                             + experts_touched(m, slots) * expert_params(m))
               + d * m["vocab_size"] + slots * d) * itemsize
    return weights + slots * sum(
        live_row_bytes(m, live_rows_per_slot, itemsize).values())


def flash_calls(m: dict, batch: int, seq: int) -> list:
    """None a train step: the block's flash kernel is forward only
    (``ops/flash_attention.py: flash_fwd``) and no cell trains it. A
    prefill's calls are :func:`prefill_flash_work`'s, whose tuples can
    say what the duty's six numbers cannot (keys wider than values, a
    band)."""
    return []


def prefill_flash_work(m: dict, rows: int, kind: str,
                       itemsize: int = 2) -> tuple:
    """(operations, bytes) one layer of ``kind`` cannot avoid in the
    attention of a ``rows``-row prompt from position 0, all of its flash
    calls together (``flash_fwd`` of a full layer, ``flash_fwd_window``
    of a window layer; one a segment): two products a (query, key) pair
    that the mask leaves, ``head_dim`` and ``v_head_dim`` wide, a query
    head; a BAND's pairs in a window layer, never the triangle's (a
    kernel that walks more reads low). Bytes: q read and o written once,
    the layer's k and v read once."""
    pairs = band_keys(rows, m["sliding_window"]) if kind == "window" \
        else causal_keys(rows)
    hq, dk, dv = m["n_heads"], m["head_dim"], m["v_head_dim"]
    flops = 2.0 * hq * pairs * (dk + dv)
    nbytes = rows * (hq * (dk + dv) + kv_heads(m)[kind] * (dk + dv)) \
        * itemsize
    return flops, nbytes


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
