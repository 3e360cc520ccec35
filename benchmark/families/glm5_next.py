"""The family ``glm5_next``: the language model of GLM-5.3-Flash as the
benchmark knows it (``ray_tpu/models/glm_next.py``): KDA layers (delta
rule, a decay bounded below, low-rank decay and gate) three in four
beside latent attention without positions (NoPE MLA) over the BLOCKS of
``index_kpool`` rows that a learned indexer with pooled keys chooses
(``index_topk / index_kpool`` whole blocks and the open block behind
them); ``hc_mult`` residual streams mixed round every sublayer (mHC);
leading dense MLPs and then a sigmoid top-k router over experts of which
this chip holds ``held_experts = [first, count]``, with a shared expert;
every SwiGLU clamped at ``swiglu_limit``. What a family file owes is
listed in ``manifest.FAMILY_DUTIES``; the arithmetic takes the dict of
``fields`` and never imports the program. A configuration file names
this file with ``"family": "glm5_next"``.
"""

from __future__ import annotations

import functools
import os
import types

from benchmark import manifest
from benchmark.manifest import ManifestError

_BASE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LANES = 128

# config.json keys the block reads one way only: (key, the value it is
# built for). Another value is refused, not approximated.
_BUILT_FOR = (
    ("model_type", "glm5_next_text"), ("hidden_act", "silu"),
    ("scoring_func", "sigmoid"), ("norm_topk_prob", True),
    ("topk_method", "noaux_tc"), ("n_shared_experts", 1),
    ("tie_word_embeddings", False), ("attention_bias", False),
    ("n_group", 1), ("topk_group", 1), ("ep_size", 1), ("mhc", True),
    ("mla_use_nope", True), ("qk_rope_head_dim", 0),
    ("index_kpool_compress", True), ("index_kpool_always_select_tail", True),
)
_KINDS = {"linear_attention": 0, "deepseek_sparse_attention": 1}


def fields(config: dict) -> dict:
    """The published ``config.json`` keys as ``GlmNextConfig`` fields.
    ``layer_types`` says which layers attend sparsely;
    ``indexer_types`` is read in those layers alone and must say
    ``"full"`` there; the prediction layer's keys
    (``num_nextn_predict_layers``, ``index_share_for_mtp_iteration``) and
    ``indexer_rope_interleave`` (the layout of a rotated part of no
    width) are not read."""
    for key, want in _BUILT_FOR:
        if config.get(key, want) != want:
            raise ManifestError(
                f"the glm5_next block is built for {key} = {want!r}, not "
                f"{config[key]!r}")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ManifestError(
            "latent attention has a key a query head: num_key_value_heads "
            f"must be {config['num_attention_heads']!r}")
    if config.get("qk_head_dim", config["qk_nope_head_dim"]) \
            != config["qk_nope_head_dim"]:
        raise ManifestError("qk_head_dim must be qk_nope_head_dim + 0")
    if not os.path.isfile(os.path.join(
            os.path.dirname(_BASE), "ray_tpu", "models", "glm_next.py")):
        # (asked of the files, not by import: the process that
        # orchestrates a run stays off jax)
        raise ManifestError(
            "this checkout's program has no block of several residual "
            "streams round KDA and block-sparse latent attention "
            "(ray_tpu/models/glm_next.py): it cannot run a glm5_next "
            "configuration")
    n = int(config["num_hidden_layers"])
    kinds = list(config["layer_types"])
    if len(kinds) != n or set(kinds) - set(_KINDS):
        raise ManifestError(
            f"layer_types must name one of {sorted(_KINDS)} for each of "
            f"the {n} layers")
    own = list(config["indexer_types"])
    if len(own) != n or any(own[i] != "full" for i in range(n)
                            if _KINDS[kinds[i]]):
        raise ManifestError("indexer_types must say 'full' for every "
                            "sparse attention layer")
    lin = config["linear_attn_config"]
    sparse_at = [i for i, k in enumerate(kinds) if _KINDS[k]]
    if lin["num_heads"] != config["num_attention_heads"] \
            or [i for i in lin["full_attn_layers"]] != sparse_at \
            or [i for i in lin["kda_layers"]] \
            != [i for i in range(n) if i not in sparse_at]:
        raise ManifestError(
            "linear_attn_config must give the KDA layers as many heads as "
            "the query heads and list the layers as layer_types has them")
    dense = int(config["first_k_dense_replace"])
    if list(config["mlp_layer_types"]) != \
            ["dense"] * min(dense, n) + ["sparse"] * max(0, n - dense):
        raise ManifestError(
            "mlp_layer_types must be first_k_dense_replace dense layers "
            "and then sparse ones")
    pool = int(config["index_kpool"])
    if int(config["index_topk"]) % pool:
        raise ManifestError("index_topk must be whole blocks of index_kpool")
    held = config.get("held_experts")
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": n,
        "layer_types": [_KINDS[k] for k in kinds],
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
        "swiglu_limit": float(config["swiglu_limit"]),
        "n_heads": int(config["num_attention_heads"]),
        "q_lora_rank": int(config["q_lora_rank"]),
        "kv_lora_rank": int(config["kv_lora_rank"]),
        "qk_nope_head_dim": int(config["qk_nope_head_dim"]),
        "qk_rope_head_dim": 0,
        "v_head_dim": int(config["v_head_dim"]),
        "index_heads": int(config["index_n_heads"]),
        "index_head_dim": int(config["index_head_dim"]),
        "index_topk": int(config["index_topk"]),
        "index_pool": pool,
        "index_norm_eps": 1e-6,
        "kda_head_dim": int(lin["head_dim"]),
        "conv_kernel": int(lin["short_conv_kernel_size"]),
        # Kimi Linear's low rank, the head's width (assumed.kda_low_rank)
        "kda_rank": int(lin["head_dim"]),
        "kda_lower_bound": float(lin["gate_lower_bound"]),
        "hc_mult": int(config["hc_mult"]),
        "hc_sinkhorn_iters": int(config["hc_sinkhorn_iters"]),
        "hc_eps": float(config["hc_eps"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "dtype": "bfloat16",
        # the depth the seeded weights are scaled for: the model's own
        "published_layers": int(config.get("published_num_hidden_layers",
                                           n)),
    }


# the cell's pattern: a dense KDA layer, then a sparse attention layer
# and three KDA layers with experts; a selection that bites (2 blocks of
# 4 rows of the rehearsal's sequences); a quarter of the experts held
TINY_FIELDS = dict(
    vocab_size=256, d_model=48, n_layers=5, layer_types=[0, 1, 0, 0, 0],
    first_k_dense=1, dense_d_ff=96, d_ff=32, shared_d_ff=32, n_experts=16,
    top_k=4, n_group=1, topk_group=1, routed_scaling_factor=2.5,
    held_experts=[0, 4], swiglu_limit=10.0, n_heads=4, q_lora_rank=32,
    kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=0, v_head_dim=24,
    index_heads=2, index_head_dim=16, index_topk=8, index_pool=4,
    index_norm_eps=1e-6, kda_head_dim=16, conv_kernel=4, kda_rank=8,
    kda_lower_bound=-5.0, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    rms_eps=1e-5, dtype="float32", published_layers=45)


def build(m: dict, *, max_seq_len: int, remat: bool):
    """The program's model for fields ``m``: the one place that imports
    it. ``init_params`` makes the tree in the SERVING types, leaf by
    leaf (``glm_next.init_params``). ``remat`` has nothing to switch: no
    cell trains this block."""
    import jax

    from ray_tpu.models import glm_next

    held = m.get("held_experts")
    cfg = glm_next.GlmNextConfig(**{
        **m, "held_experts": held and tuple(held),
        "layer_types": tuple(m["layer_types"])}, max_seq_len=max_seq_len)

    def init_params(key):
        return glm_next.init_params(cfg, key)

    def param_logical_axes():
        """Every leaf whole on its device: the block is sharded by what
        a chip HOLDS (``held_experts``), not over a mesh."""
        return jax.tree_util.tree_map(
            lambda a: (None,) * a.ndim,
            jax.eval_shape(init_params, jax.random.PRNGKey(0)))

    return types.SimpleNamespace(
        cfg=cfg, init_params=init_params,
        loss_fn=lambda params, batch: glm_next.loss_fn(params, batch, cfg),
        param_logical_axes=param_logical_axes)


def reference():
    """``families/glm5_next.reference.py``, beside this file."""
    return manifest.load_python("families", "glm5_next.reference", _BASE)


# ------------------------------------------------ operations and bytes


def _held(m: dict) -> int:
    return (m.get("held_experts") or (0, m["n_experts"]))[1]


def layer_counts(m: dict) -> dict:
    """How many layers of each kind the configuration has: ``linear``
    (KDA), ``sparse`` (latent attention over chosen blocks), each of
    which owns an indexer (``index``), ``dense`` and ``moe`` MLPs."""
    sparse = sum(m["layer_types"])
    dense = min(m["first_k_dense"], m["n_layers"])
    return {"linear": m["n_layers"] - sparse, "sparse": sparse,
            "index": sparse, "dense": dense, "moe": m["n_layers"] - dense}


def kda_params(m: dict) -> int:
    """One KDA layer's attention: q, k, v and their taps, the low-rank
    decay with its bias and ``A_log``, beta, the low-rank gate, the
    head-wise norm and the output product (Solar-Open2's count)."""
    d, w, r = m["d_model"], m["n_heads"] * m["kda_head_dim"], m["kda_rank"]
    return (d * 3 * w + m["conv_kernel"] * 3 * w + 2 * (d * r + r * w) + w
            + m["n_heads"] + d * m["n_heads"] + m["kda_head_dim"] + w * d)


def attn_params(m: dict) -> int:
    """One sparse layer's attention without its indexer: the two
    low-rank query products with the norm between, the latent product
    with its norm, the product out of the latent, the output product."""
    d, h = m["d_model"], m["n_heads"]
    dn, dv = m["qk_nope_head_dim"], m["v_head_dim"]
    return (d * m["q_lora_rank"] + m["q_lora_rank"]
            + m["q_lora_rank"] * h * dn + d * m["kv_lora_rank"]
            + m["kv_lora_rank"] + m["kv_lora_rank"] * h * (dn + dv)
            + h * dv * d)


def index_params(m: dict) -> int:
    """A sparse layer's indexer: the index queries out of the query
    latent, the key with its LayerNorm's scale and bias, the weights."""
    hi, di = m["index_heads"], m["index_head_dim"]
    return m["q_lora_rank"] * hi * di + m["d_model"] * di + 2 * di \
        + m["d_model"] * hi


def hc_params(m: dict) -> int:
    """One SUBLAYER's stream coefficients: ``phi`` (the streams' numbers
    by 2 n + n^2), as many biases, three scales."""
    n = m["hc_mult"]
    return (n * m["d_model"] + 1) * (2 * n + n * n) + 3


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
    return c["linear"] * kda_params(m) + c["sparse"] * attn_params(m) \
        + c["index"] * index_params(m)


def num_params(m: dict) -> int:
    """Parameters HELD here: of every expert layer the held experts;
    every layer two norms and two sublayers' stream coefficients."""
    d, v, c = m["d_model"], m["vocab_size"], layer_counts(m)
    return (2 * v * d + d + m["n_layers"] * 2 * (d + hc_params(m))
            + _attn_total(m) + c["dense"] * 3 * d * m["dense_d_ff"]
            + c["moe"] * (moe_fixed_params(m) + _held(m) * expert_params(m)))


def _no_product(m: dict) -> int:
    """The attention's leaves that meet no matrix product."""
    c = layer_counts(m)
    w = m["n_heads"] * m["kda_head_dim"]
    return c["linear"] * (m["conv_kernel"] * 3 * w + w + m["n_heads"]
                          + m["kda_head_dim"]) \
        + c["sparse"] * (m["q_lora_rank"] + m["kv_lora_rank"]) \
        + c["index"] * 2 * m["index_head_dim"]


def matmul_params(m: dict) -> int:
    """Parameters a token meets in a matrix product here: attention, the
    indexers and the streams' ``phi``, the dense MLP or the router, the
    shared expert and the held share of its ``top_k`` experts (uniform
    routing), the head."""
    d, c, n = m["d_model"], layer_counts(m), m["hc_mult"]
    routed = m["top_k"] * _held(m) / m["n_experts"] * expert_params(m)
    return int(_attn_total(m) - _no_product(m)
               + m["n_layers"] * 2 * n * d * (2 * n + n * n)
               + c["dense"] * 3 * d * m["dense_d_ff"]
               + c["moe"] * (moe_fixed_params(m) - m["n_experts"] + routed)
               + d * m["vocab_size"])


def pooled_keys(m: dict, rows: int) -> int:
    """The pooled index keys ``rows`` positions leave: one a whole or
    open block of ``index_pool`` rows."""
    return -(-rows // m["index_pool"])


def scored_pairs(m: dict, rows: int) -> int:
    """(query, pooled key) pairs an indexer scores for rows 0 ..: row t
    its ``(t + 1) // pool`` whole blocks (the sum in closed form)."""
    q, r = divmod(rows, m["index_pool"])
    return m["index_pool"] * q * (q - 1) // 2 + q * (r + 1)


def chosen_keys(m: dict, rows: int) -> int:
    """(query, key) pairs a sparse layer ATTENDS: row t the rows of its
    ``min(index_topk / pool, (t + 1) // pool)`` chosen blocks and the
    ``(t + 1) % pool`` rows of the open block."""
    return _chosen_keys(m["index_pool"], m["index_topk"] // m["index_pool"],
                        rows)


@functools.lru_cache(maxsize=None)
def _chosen_keys(pool: int, blocks: int, rows: int) -> int:
    return sum(pool * min(blocks, u // pool) + u % pool
               for u in range(1, rows + 1))


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward, recomputation not counted: 6 per matmul
    parameter a token meets; the indexers' products over the scored
    pairs, attention over the pairs the model asks for, unabsorbed
    widths; the delta rule's state passes. (No cell trains this
    family.)"""
    c = layer_counts(m)
    attn = 2.0 / seq * (
        c["sparse"] * m["n_heads"] * chosen_keys(m, seq)
        * (m["qk_nope_head_dim"] + m["v_head_dim"])
        + c["index"] * m["index_heads"] * scored_pairs(m, seq)
        * m["index_head_dim"])
    kda = c["linear"] * 2 * 4 * m["n_heads"] * m["kda_head_dim"] ** 2
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


def row_bytes(m: dict, itemsize: int = 2) -> dict:
    """One position's bytes in one sparse layer, by kind of row, AS
    STORED: the latent alone (no rotated key, no padding lanes); a
    pooled index key, one for ``index_pool`` positions."""
    return {"latent": -(-m["kv_lora_rank"] // _LANES) * _LANES * itemsize,
            "index": m["index_head_dim"] * itemsize}


def state_bytes_per_slot(m: dict, max_len: int, itemsize: int = 2) -> dict:
    """What one stream's state takes, by kind: for each KDA layer the
    float32 matrix ``[H, dk, dk]`` and ``conv_kernel - 1`` rows of
    convolution input; for each sparse layer ``max_len`` latent rows,
    ``ceil(max_len / pool)`` pooled keys and the open block's ``pool -
    1`` raw keys."""
    c, row = layer_counts(m), row_bytes(m, itemsize)
    h, dk = m["n_heads"], m["kda_head_dim"]
    return {
        "recurrent": c["linear"] * (
            kda_state_bytes(m, 1)
            + (m["conv_kernel"] - 1) * 3 * h * dk * itemsize),
        "latent": c["sparse"] * max_len * row["latent"],
        "index": c["index"] * (pooled_keys(m, max_len) + m["index_pool"] - 1)
        * row["index"]}


def decode_step_bytes(m: dict, slots: int, live_rows_per_slot: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step of ``slots`` streams cannot avoid: every
    weight outside the routed experts once, the held experts the slots'
    tokens touch (``experts_touched``), the slots' embedding rows, every
    slot's KDA state read AND written once a KDA layer; in a sparse
    layer the pooled keys of every whole block (the indexer scores them
    all) and the latent rows the model asks it to read (the
    ``min(live, index_topk)`` chosen). A floor: an implementation that
    reads more reads LOW, never over 100%."""
    d, c = m["d_model"], layer_counts(m)
    weights = (_attn_total(m) + m["n_layers"] * 2 * hc_params(m)
               + c["dense"] * 3 * d * m["dense_d_ff"]
               + c["moe"] * (moe_fixed_params(m)
                             + experts_touched(m, slots) * expert_params(m))
               + d * m["vocab_size"] + slots * d) * itemsize
    per_slot = state_bytes_per_slot(m, 1, itemsize)
    rows = c["index"] * live_rows_per_slot / m["index_pool"] \
        * m["index_head_dim"] \
        + c["sparse"] * min(live_rows_per_slot, m["index_topk"]) \
        * m["kv_lora_rank"]
    return weights + slots * (2 * per_slot["recurrent"] + rows * itemsize)


def flash_calls(m: dict, batch: int, seq: int) -> list:
    """None a train step: the block's attention kernels are forward only
    and no cell trains it."""
    return []


def dsa_index_work(m: dict, rows: int, bucket: int = 0,
                   itemsize: int = 2) -> tuple:
    """(operations, bytes) one sparse layer's index scores of a
    ``rows``-row prompt from position 0 cannot avoid, all of its
    ``dsa_index`` calls together: one product ``index_head_dim`` wide an
    index head a (query, WHOLE pooled key) pair; the index queries and
    the weights read once, the pooled keys once (a quarter of the rows),
    the float32 scores of those pairs written."""
    hi, di = m["index_heads"], m["index_head_dim"]
    pairs = scored_pairs(m, rows)
    return (2.0 * hi * di * pairs,
            rows * (hi * di * itemsize + hi * 4)
            + pooled_keys(m, rows) * di * itemsize + 4.0 * pairs)


def dsa_attn_work(m: dict, rows: int, bucket: int = 0,
                  itemsize: int = 2) -> tuple:
    """(operations, bytes) one sparse layer's attention of a ``rows``-row
    prompt from position 0 cannot avoid, all of its ``dsa_attn`` calls
    together, counting the work the MODEL asks: two products a CHOSEN
    (query, key) pair a head, ``qk_nope`` and ``v_head_dim`` wide (a
    kernel that walks every causal pair reads low, by the chosen pairs'
    share of them); q read and o written once, the rows' k and v of
    every head read once."""
    h, w = m["n_heads"], m["qk_nope_head_dim"] + m["v_head_dim"]
    return (2.0 * h * chosen_keys(m, rows) * w,
            2.0 * rows * h * w * itemsize)


def dsa_kth_work(m: dict, rows: int, bucket: int) -> tuple:
    """(operations, bytes) one sparse layer's selections of a prompt
    whose ``rows`` rows ran in a ``bucket``-row call cannot avoid, all of
    its ``dsa_kth`` calls together: every row's ``bucket / pool`` int32
    keys read ONCE (the 32 counting passes are the vector unit's and are
    not counted: the share reads low where they bind)."""
    return 0.0, 4.0 * rows * pooled_keys(m, bucket)


def decode_attn_work(m: dict, chosen_rows: float,
                     itemsize: int = 2) -> tuple:
    """(operations, bytes) one ``dsa_decode_attn`` call cannot avoid for
    the work the MODEL asks: every head's query against the
    ``chosen_rows`` latent rows the call was handed as chosen (scores
    and values over the latent), each such row read once AS STORED. A
    kernel that reads every live row and masks reads low, by the chosen
    rows' share of the live ones."""
    r = m["kv_lora_rank"]
    return (2.0 * m["n_heads"] * chosen_rows * 2 * r,
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
# a sparse layer's prefill attends its heads in this many groups a
# segment
STEP_READS = "live"
PREFILL_HEAD_GROUPS = 8
