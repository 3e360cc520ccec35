"""The family ``glm_moe_dsa``: the language model of GLM-5.2 as the
benchmark knows it (``ray_tpu/models/glm_dsa.py``): latent attention
(MLA) in EVERY layer over the ``index_topk`` rows a learned indexer
chooses (DeepSeek-V3.2-Exp's sparse attention: ``index_n_heads`` heads of
``index_head_dim`` over a key cache of its own), the choice made in the
layers whose ``indexer_types`` entry is ``"full"`` and read by the
``"shared"`` layers behind them (IndexShare); leading dense MLPs and
then a sigmoid top-k router over experts of which this chip holds
``held_experts = [first, count]``, with a shared expert. What a family
file owes is listed in ``manifest.FAMILY_DUTIES``; the arithmetic takes
the dict of ``fields`` and never imports the program. A configuration
file names this file with ``"family": "glm_moe_dsa"``.
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
    ("model_type", "glm_moe_dsa"), ("hidden_act", "silu"),
    ("scoring_func", "sigmoid"), ("norm_topk_prob", True),
    ("topk_method", "noaux_tc"), ("moe_layer_freq", 1),
    ("n_shared_experts", 1), ("tie_word_embeddings", False),
    ("attention_bias", False), ("rope_interleave", True),
    ("indexer_rope_interleave", True), ("n_group", 1), ("topk_group", 1),
    ("ep_size", 1), ("index_topk_pattern", None),
)
_INDEXER = {"full": 1, "shared": 0}


def fields(config: dict) -> dict:
    """The published ``config.json`` keys as ``GlmDsaConfig`` fields.
    ``indexer_types`` is what says which layers own an indexer
    (``index_topk_freq`` / ``index_skip_topk_offset`` stay in the file
    as published and are not read); the prediction layer's keys
    (``num_nextn_predict_layers``, ``index_share_for_mtp_iteration``)
    are not read either: ``left_out.mtp``."""
    for key, want in _BUILT_FOR:
        if config.get(key, want) != want:
            raise ManifestError(
                f"the glm_moe_dsa block is built for {key} = {want!r}, not "
                f"{config[key]!r}")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ManifestError(
            "latent attention has a key a query head: num_key_value_heads "
            f"must be {config['num_attention_heads']!r}, not "
            f"{config['num_key_value_heads']!r}")
    rope = config["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ManifestError("the glm_moe_dsa block is built for "
                            f"rope_type 'default', not {rope['rope_type']!r}")
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    if config.get("qk_head_dim", dn + dr) != dn + dr:
        raise ManifestError(f"qk_head_dim must be {dn} + {dr}")
    if not os.path.isfile(os.path.join(
            os.path.dirname(_BASE), "ray_tpu", "models", "glm_dsa.py")):
        # (asked of the files, not by import: the process that
        # orchestrates a run stays off jax)
        raise ManifestError(
            "this checkout's program has no block of latent attention "
            "whose layers share an indexer's selection "
            "(ray_tpu/models/glm_dsa.py): it cannot run a glm_moe_dsa "
            "configuration")
    n = int(config["num_hidden_layers"])
    own = list(config["indexer_types"])
    if len(own) != n or set(own) - set(_INDEXER) or own[0] != "full":
        raise ManifestError(
            f"indexer_types must name one of {sorted(_INDEXER)} for each "
            f"of the {n} layers, the first 'full'")
    dense = int(config["first_k_dense_replace"])
    if list(config["mlp_layer_types"]) != \
            ["dense"] * min(dense, n) + ["sparse"] * max(0, n - dense):
        raise ManifestError(
            "mlp_layer_types must be first_k_dense_replace dense layers "
            "and then sparse ones")
    held = config.get("held_experts")
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": n,
        "indexer_layers": [_INDEXER[k] for k in own],
        "first_k_dense": dense,
        "dense_d_ff": int(config["intermediate_size"]),
        "d_ff": int(config["moe_intermediate_size"]),
        "shared_d_ff": int(config["n_shared_experts"])
        * int(config["moe_intermediate_size"]),
        "n_experts": int(config["n_routed_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "n_group": 1, "topk_group": 1,
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "held_experts": None if held is None else [int(held[0]),
                                                   int(held[1])],
        "n_heads": int(config["num_attention_heads"]),
        "q_lora_rank": int(config["q_lora_rank"]),
        "kv_lora_rank": int(config["kv_lora_rank"]),
        "qk_nope_head_dim": dn,
        "qk_rope_head_dim": dr,
        "v_head_dim": int(config["v_head_dim"]),
        "rope_theta": float(rope["rope_theta"]),
        "index_heads": int(config["index_n_heads"]),
        "index_head_dim": int(config["index_head_dim"]),
        "index_topk": int(config["index_topk"]),
        "index_norm_eps": 1e-6,
        "rms_eps": float(config["rms_norm_eps"]),
        "dtype": "bfloat16",
        # the depth the seeded weights are scaled for: the model's own
        "published_layers": int(config.get("published_num_hidden_layers",
                                           n)),
    }


# the cell's pattern: an indexer layer with the dense MLP, then an
# indexer layer and the three shared layers that read it; a selection
# that bites (8 rows of the rehearsal's sequences); keys 24 + 8 wide
# beside values of 32; a quarter of the experts held
TINY_FIELDS = dict(
    vocab_size=256, d_model=64, n_layers=5, indexer_layers=[1, 1, 0, 0, 0],
    first_k_dense=1, dense_d_ff=160, d_ff=32, shared_d_ff=32, n_experts=16,
    top_k=4, n_group=1, topk_group=1, routed_scaling_factor=2.5,
    held_experts=[0, 4], n_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32, rope_theta=1e4,
    index_heads=2, index_head_dim=16, index_topk=8, index_norm_eps=1e-6,
    rms_eps=1e-5, dtype="float32", published_layers=78)


def build(m: dict, *, max_seq_len: int, remat: bool):
    """The program's model for fields ``m``: the one place that imports
    it. ``init_params`` makes the tree in the SERVING types, leaf by
    leaf (``glm_dsa.init_params``). ``remat`` has nothing to switch: no
    cell trains this block."""
    import jax

    from ray_tpu.models import glm_dsa

    held = m.get("held_experts")
    cfg = glm_dsa.GlmDsaConfig(**{
        **m, "held_experts": held and tuple(held),
        "indexer_layers": tuple(m["indexer_layers"])},
        max_seq_len=max_seq_len)

    def init_params(key):
        return glm_dsa.init_params(cfg, key)

    def param_logical_axes():
        """Every leaf whole on its device: the block is sharded by what
        a chip HOLDS (``held_experts``), not over a mesh."""
        return jax.tree_util.tree_map(
            lambda a: (None,) * a.ndim,
            jax.eval_shape(init_params, jax.random.PRNGKey(0)))

    return types.SimpleNamespace(
        cfg=cfg, init_params=init_params,
        loss_fn=lambda params, batch: glm_dsa.loss_fn(params, batch, cfg),
        param_logical_axes=param_logical_axes)


def reference():
    """``families/glm_moe_dsa.reference.py``, beside this file."""
    return manifest.load_python("families", "glm_moe_dsa.reference", _BASE)


# ------------------------------------------------ operations and bytes


def _held(m: dict) -> int:
    return (m.get("held_experts") or (0, m["n_experts"]))[1]


def layer_counts(m: dict) -> dict:
    """How many layers of each kind the configuration has: ``index``
    layers own an indexer and select, ``sparse`` layers attend over a
    selection (every layer does: an index layer over its own)."""
    dense = min(m["first_k_dense"], m["n_layers"])
    return {"index": sum(m["indexer_layers"]), "sparse": m["n_layers"],
            "dense": dense, "moe": m["n_layers"] - dense}


def attn_params(m: dict) -> int:
    """One attention: the two low-rank query products with the norm
    between, the latent product with its norm, the product out of the
    latent and the output product."""
    d, h = m["d_model"], m["n_heads"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    return (d * m["q_lora_rank"] + m["q_lora_rank"]
            + m["q_lora_rank"] * h * (dn + dr)
            + d * (m["kv_lora_rank"] + dr) + m["kv_lora_rank"]
            + m["kv_lora_rank"] * h * (dn + dv) + h * dv * d)


def index_params(m: dict) -> int:
    """An index layer's indexer: the index queries out of the query
    latent, the key with its LayerNorm's scale and bias, the weights."""
    hi, di = m["index_heads"], m["index_head_dim"]
    return m["q_lora_rank"] * hi * di + m["d_model"] * di + 2 * di \
        + m["d_model"] * hi
def expert_params(m: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["d_model"] * m["d_ff"]


def moe_fixed_params(m: dict) -> int:
    """What an expert layer holds beside its routed experts: the router
    with its bias and the shared expert."""
    return m["d_model"] * m["n_experts"] + m["n_experts"] \
        + 3 * m["d_model"] * m["shared_d_ff"]


def _attn_total(m: dict) -> int:
    c = layer_counts(m)
    return c["sparse"] * attn_params(m) + c["index"] * index_params(m)


def num_params(m: dict) -> int:
    """Parameters HELD here: of every expert layer the held experts."""
    d, v, c = m["d_model"], m["vocab_size"], layer_counts(m)
    return (2 * v * d + d + m["n_layers"] * 2 * d + _attn_total(m)
            + c["dense"] * 3 * d * m["dense_d_ff"]
            + c["moe"] * (moe_fixed_params(m) + _held(m) * expert_params(m)))


def _norm_vectors(m: dict) -> int:
    """The attention's leaves that meet no matrix product."""
    c = layer_counts(m)
    return c["sparse"] * (m["q_lora_rank"] + m["kv_lora_rank"]) \
        + c["index"] * 2 * m["index_head_dim"]


def matmul_params(m: dict) -> int:
    """Parameters a token meets in a matrix product here: attention and
    the indexers, the dense MLP or the router, the shared expert and the
    held share of its ``top_k`` experts (uniform routing), the head."""
    d, c = m["d_model"], layer_counts(m)
    routed = m["top_k"] * _held(m) / m["n_experts"] * expert_params(m)
    return int(_attn_total(m) - _norm_vectors(m)
               + c["dense"] * 3 * d * m["dense_d_ff"]
               + c["moe"] * (moe_fixed_params(m) - m["n_experts"] + routed)
               + d * m["vocab_size"])


def causal_keys(rows: int) -> int:
    """(query, key) pairs that rows 0 .. see causally."""
    return rows * (rows + 1) // 2


def chosen_keys(rows: int, topk: int) -> int:
    """(query, key) pairs a layer ATTENDS: row p its ``min(p + 1,
    index_topk)`` chosen rows."""
    return sum(min(p + 1, topk) for p in range(min(rows, topk))) \
        + max(0, rows - topk) * topk


def _qkv_width(m: dict) -> int:
    return m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward, recomputation not counted: 6 per matmul
    parameter a token meets; the indexers' products over the causal
    pairs, attention over the pairs the model asks for (the chosen
    rows), unabsorbed widths. (No cell trains this family.)"""
    c = layer_counts(m)
    attn = 2.0 / seq * (
        c["sparse"] * m["n_heads"] * chosen_keys(seq, m["index_topk"])
        * _qkv_width(m)
        + c["index"] * m["index_heads"] * causal_keys(seq)
        * m["index_head_dim"])
    return 3.0 * (2 * matmul_params(m) + attn)


def experts_touched(m: dict, tokens: float) -> float:
    """HELD experts that get at least one of ``tokens`` tokens'
    assignments when each token's ``top_k`` distinct experts are uniform
    over all ``n_experts``: held x (1 - (1 - k/E)^tokens). A floor on
    what a layer must read."""
    e, k = m["n_experts"], m["top_k"]
    return _held(m) * (1.0 - (1.0 - k / e) ** tokens)


def row_bytes(m: dict, itemsize: int = 2) -> dict:
    """One position's bytes in one layer's stack, by kind of row, AS
    STORED: latent | rotated key padded to whole lanes (640 numbers),
    the index key as it is."""
    return {"latent": -(-(m["kv_lora_rank"] + m["qk_rope_head_dim"])
                        // _LANES) * _LANES * itemsize,
            "index": m["index_head_dim"] * itemsize}


def state_bytes_per_slot(m: dict, max_len: int, itemsize: int = 2) -> dict:
    """What one stream's state takes, by kind: ``max_len`` latent rows
    EVERY layer, ``max_len`` index keys an index layer."""
    c, row = layer_counts(m), row_bytes(m, itemsize)
    return {"latent": c["sparse"] * max_len * row["latent"],
            "index": c["index"] * max_len * row["index"]}


def decode_step_bytes(m: dict, slots: int, live_rows_per_slot: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step of ``slots`` streams cannot avoid: every
    weight outside the routed experts once (attention, indexers, dense
    MLP, router, shared expert, head), the held experts the slots'
    tokens touch (``experts_touched``), the slots' embedding rows; in an
    index layer the index keys of EVERY live row (the indexer scores
    them all), in every layer the latent rows the model asks it to read
    (the ``min(live, index_topk)`` chosen, at latent | rotated key
    without the padding). A floor: an implementation that reads more
    reads LOW, never over 100%."""
    d, c = m["d_model"], layer_counts(m)
    weights = (_attn_total(m) + c["dense"] * 3 * d * m["dense_d_ff"]
               + c["moe"] * (moe_fixed_params(m)
                             + experts_touched(m, slots) * expert_params(m))
               + d * m["vocab_size"] + slots * d) * itemsize
    rows = c["index"] * live_rows_per_slot * m["index_head_dim"] \
        + c["sparse"] * min(live_rows_per_slot, m["index_topk"]) \
        * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
    return weights + slots * rows * itemsize


def flash_calls(m: dict, batch: int, seq: int) -> list:
    """None a train step: the block's attention kernels are forward only
    and no cell trains it. A prefill's are :func:`dsa_index_work` and
    :func:`dsa_attn_work`."""
    return []


def dsa_index_work(m: dict, rows: int, bucket: int = 0,
                   itemsize: int = 2) -> tuple:
    """(operations, bytes) one INDEX layer's index scores of a
    ``rows``-row prompt from position 0 cannot avoid, all of its
    ``dsa_index`` calls together: one product ``index_head_dim`` wide an
    index head a causal (query, key) pair; the index queries and the
    weights read once, the keys once, the float32 scores of the causal
    pairs written."""
    hi, di = m["index_heads"], m["index_head_dim"]
    pairs = causal_keys(rows)
    return (2.0 * hi * di * pairs,
            rows * (hi * di * itemsize + hi * 4 + di * itemsize)
            + 4.0 * pairs)


def dsa_attn_work(m: dict, rows: int, bucket: int = 0,
                  itemsize: int = 2) -> tuple:
    """(operations, bytes) one layer's attention of a ``rows``-row
    prompt from position 0 cannot avoid, all of its ``dsa_attn`` calls
    together, counting the work the MODEL asks: two products a CHOSEN
    (query, key) pair a head, ``qk_nope + qk_rope`` and ``v_head_dim``
    wide (a kernel that walks every causal pair reads low, by the
    chosen pairs' share of them); q read and o written once, the rows'
    k and v of every head read once."""
    h = m["n_heads"]
    return (2.0 * h * chosen_keys(rows, m["index_topk"]) * _qkv_width(m),
            2.0 * rows * h * _qkv_width(m) * itemsize)


def dsa_kth_work(m: dict, rows: int, bucket: int) -> tuple:
    """(operations, bytes) one INDEX layer's selections of a prompt
    whose ``rows`` rows ran in a ``bucket``-row call cannot avoid, all of
    its ``dsa_kth`` calls together: every row's ``bucket`` int32 keys
    read ONCE (the 32 counting passes are the vector unit's and are not
    counted: the share reads low where they bind)."""
    return 0.0, 4.0 * rows * bucket


def decode_attn_work(m: dict, chosen_rows: float,
                     itemsize: int = 2) -> tuple:
    """(operations, bytes) one ``dsa_decode_attn`` call cannot avoid for
    the work the MODEL asks: every head's query against the
    ``chosen_rows`` latent rows the call was handed as chosen (scores
    over latent | rotated key, the probabilities against the latents),
    each such row read once AS STORED. A kernel that reads every live
    row and masks reads low, by the chosen rows' share of the live
    ones."""
    r = m["kv_lora_rank"]
    return (2.0 * m["n_heads"] * chosen_rows
            * (r + m["qk_rope_head_dim"] + r),
            chosen_rows * row_bytes(m, itemsize)["latent"])


def gmm_flops(rows: float, k: int, n: int) -> float:
    """One grouped matmul (``moe_gmm``) over ``rows`` assignment rows OF
    HELD EXPERTS, [rows, k] x [held, k, n]: the rows the kernel's grid
    visits (take them from the engine's ``held_assignments``)."""
    return 2.0 * rows * k * n


def gmm_bytes(rows: float, k: int, n: int, touched: float,
              itemsize: int = 2) -> float:
    """Bytes that call cannot avoid: the ``touched`` held experts'
    matrices once, the held rows read and their results written."""
    return (touched * k * n + rows * k + rows * n) * itemsize


# what the program does with the model's request, for the readers: a
# decode step reads every LIVE latent row of a slot and masks the
# unchosen (one that gathered its chosen rows would say "chosen"), and
# a layer's prefill attends its heads in this many groups a segment
STEP_READS = "live"
PREFILL_HEAD_GROUPS = 8
