"""The family ``dots3_note``: the language model of dots3-note-prev as the
benchmark knows it (``ray_tpu/models/dots.py``): latent attention (MLA)
with a gate a head in every layer, in FULL layers over the
``index_topk`` rows a learned indexer chooses (DeepSeek-V3.2-Exp's
sparse attention: ``index_n_heads`` heads of ``index_head_dim`` over a
key cache of its own) and in WINDOW layers with latents, heads and
theta of their own over the last ``sliding_window_size`` rows, as
``layer_types`` says; a leading dense MLP and then a sigmoid top-k
router over experts of which this chip holds ``held_experts = [first,
count]``, with a shared expert. What a family file owes is listed in
``manifest.FAMILY_DUTIES``; the arithmetic takes the dict of ``fields``
and never imports the program. A configuration file names this file with
``"family": "dots3_note"``.
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
    ("model_type", "dots3_note"), ("hidden_act", "silu"),
    ("scoring_func", "sigmoid"), ("norm_topk_prob", True),
    ("topk_method", "noaux_tc"), ("moe_layer_freq", 1),
    ("n_shared_experts", 1), ("tie_word_embeddings", False),
    ("attention_bias", False), ("attention_gate_type", "headwise"),
    ("swa_attention_gate_type", "headwise"), ("rope_scaling", None),
    ("apply_mla_qkv_lora_rescale", True),
)
_KINDS = {"full_attention": 0, "sliding_attention": 1}


def fields(config: dict) -> dict:
    """The published ``config.json`` keys as ``DotsConfig`` fields."""
    for key, want in _BUILT_FOR:
        if config.get(key, want) != want:
            raise ManifestError(
                f"the dots3_note block is built for {key} = {want!r}, not "
                f"{config[key]!r}")
    for key, same in (("num_key_value_heads", "num_attention_heads"),
                      ("swa_num_key_value_heads",
                       "swa_num_attention_heads")):
        if config[key] != config[same]:
            raise ManifestError(
                f"latent attention has a key a query head: {key} must be "
                f"{same} ({config[same]!r}), not {config[key]!r}")
    if not os.path.isfile(os.path.join(
            os.path.dirname(_BASE), "ray_tpu", "models", "dots.py")):
        # (asked of the files, not by import: the process that
        # orchestrates a run stays off jax)
        raise ManifestError(
            "this checkout's program has no block of latent attention "
            "over rows an indexer chooses beside window layers "
            "(ray_tpu/models/dots.py): it cannot run a dots3_note "
            "configuration")
    n = int(config["num_hidden_layers"])
    kinds = list(config["layer_types"])
    if len(kinds) != n or set(kinds) - set(_KINDS):
        raise ManifestError(
            f"layer_types must name one of {sorted(_KINDS)} for each of "
            f"the {n} layers")
    held = config.get("held_experts")
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": n,
        "layer_pattern": [_KINDS[k] for k in kinds],
        "first_k_dense": int(config["first_k_dense_replace"]),
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
        "qk_nope_head_dim": int(config["qk_nope_head_dim"]),
        "qk_rope_head_dim": int(config["qk_rope_head_dim"]),
        "v_head_dim": int(config["v_head_dim"]),
        "rope_theta": float(config["rope_theta"]),
        "index_heads": int(config["index_n_heads"]),
        "index_head_dim": int(config["index_head_dim"]),
        "index_topk": int(config["index_topk"]),
        "index_norm_eps": 1e-6,
        "window_heads": int(config["swa_num_attention_heads"]),
        "window_q_lora_rank": int(config["swa_q_lora_rank"]),
        "window_kv_lora_rank": int(config["swa_kv_lora_rank"]),
        "window_qk_nope_head_dim": int(config["swa_qk_nope_head_dim"]),
        "window_qk_rope_head_dim": int(config["swa_qk_rope_head_dim"]),
        "window_v_head_dim": int(config["swa_v_head_dim"]),
        "window_rope_theta": float(config["swa_rope_theta"]),
        "sliding_window": int(config["sliding_window_size"]),
        "lora_rescale": bool(config["apply_mla_qkv_lora_rescale"]),
        "gated_attention": True,
        "rms_eps": float(config["rms_norm_eps"]),
        "dtype": "bfloat16",
        # the depth the seeded weights are scaled for: the model's own
        "published_layers": int(config.get("published_num_hidden_layers",
                                           n)),
    }


# two full layers (the first with the dense MLP) and three window
# layers; a selection that bites (8 rows of the rehearsal's sequences)
# and a window smaller than them; a quarter of the experts held
TINY_FIELDS = dict(
    vocab_size=256, d_model=64, n_layers=5, layer_pattern=[0, 0, 1, 1, 1],
    first_k_dense=1, dense_d_ff=160, d_ff=32, shared_d_ff=32, n_experts=16,
    top_k=4, n_group=1, topk_group=1, routed_scaling_factor=1.0,
    held_experts=[0, 4], n_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e4,
    index_heads=4, index_head_dim=16, index_topk=8, index_norm_eps=1e-6,
    window_heads=2, window_q_lora_rank=32, window_kv_lora_rank=32,
    window_qk_nope_head_dim=24, window_qk_rope_head_dim=8,
    window_v_head_dim=16, window_rope_theta=1e3, sliding_window=9,
    lora_rescale=True, gated_attention=True, rms_eps=1e-5, dtype="float32",
    published_layers=46)


def build(m: dict, *, max_seq_len: int, remat: bool):
    """The program's model for fields ``m``: the one place that imports
    it. ``init_params`` makes the tree in the SERVING types, leaf by
    leaf (``dots.init_params``). ``remat`` has nothing to switch: no
    cell trains this block."""
    import jax

    from ray_tpu.models import dots

    held = m.get("held_experts")
    cfg = dots.DotsConfig(**{
        **m, "held_experts": held and tuple(held),
        "layer_pattern": tuple(m["layer_pattern"])},
        max_seq_len=max_seq_len)

    def init_params(key):
        return dots.init_params(cfg, key)

    def param_logical_axes():
        """Every leaf whole on its device: the block is sharded by what
        a chip HOLDS (``held_experts``), not over a mesh."""
        return jax.tree_util.tree_map(
            lambda a: (None,) * a.ndim,
            jax.eval_shape(init_params, jax.random.PRNGKey(0)))

    return types.SimpleNamespace(
        cfg=cfg, init_params=init_params,
        loss_fn=lambda params, batch: dots.loss_fn(params, batch, cfg),
        param_logical_axes=param_logical_axes)


def reference():
    """``families/dots3_note.reference.py``, beside this file."""
    return manifest.load_python("families", "dots3_note.reference", _BASE)


# ------------------------------------------------ operations and bytes


def _held(m: dict) -> int:
    return (m.get("held_experts") or (0, m["n_experts"]))[1]


def layer_counts(m: dict) -> dict:
    """How many layers of each kind the configuration has."""
    window = sum(m["layer_pattern"])
    dense = min(m["first_k_dense"], m["n_layers"])
    return {"window": window, "full": m["n_layers"] - window,
            "dense": dense, "moe": m["n_layers"] - dense}


def _kind(m: dict, kind: str) -> dict:
    pre = "window_" if kind == "window" else ""
    return {"heads": m["window_heads" if pre else "n_heads"],
            **{k: m[pre + k] for k in (
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim")}}


def attn_params(m: dict) -> dict:
    """One attention, by kind: the two low-rank query products with the
    norm between, the latent product with its norm, the product out of
    the latent, the output product and the gate a head."""
    d, out = m["d_model"], {}
    for kind in ("full", "window"):
        k = _kind(m, kind)
        h, dn, dr, dv = (k["heads"], k["qk_nope_head_dim"],
                         k["qk_rope_head_dim"], k["v_head_dim"])
        out[kind] = (d * k["q_lora_rank"] + k["q_lora_rank"]
                     + k["q_lora_rank"] * h * (dn + dr)
                     + d * (k["kv_lora_rank"] + dr) + k["kv_lora_rank"]
                     + k["kv_lora_rank"] * h * (dn + dv) + h * dv * d
                     + d * h)
    return out


def index_params(m: dict) -> int:
    """A full layer's indexer: the index queries out of the query
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
    c, a = layer_counts(m), attn_params(m)
    return c["window"] * a["window"] + c["full"] * (a["full"]
                                                    + index_params(m))


def num_params(m: dict) -> int:
    """Parameters HELD here: of every expert layer the held experts."""
    d, v, c = m["d_model"], m["vocab_size"], layer_counts(m)
    return (2 * v * d + d + m["n_layers"] * 2 * d + _attn_total(m)
            + c["dense"] * 3 * d * m["dense_d_ff"]
            + c["moe"] * (moe_fixed_params(m) + _held(m) * expert_params(m)))


def _norm_vectors(m: dict) -> int:
    """The attention's leaves that meet no matrix product."""
    c = layer_counts(m)
    return sum(c[kind] * (_kind(m, kind)["q_lora_rank"]
                          + _kind(m, kind)["kv_lora_rank"])
               for kind in ("full", "window")) \
        + c["full"] * 2 * m["index_head_dim"]


def matmul_params(m: dict) -> int:
    """Parameters a token meets in a matrix product here: attention and
    the indexer, the dense MLP or the router, the shared expert and the
    held share of its ``top_k`` experts (uniform routing), the head."""
    d, c = m["d_model"], layer_counts(m)
    routed = m["top_k"] * _held(m) / m["n_experts"] * expert_params(m)
    return int(_attn_total(m) - _norm_vectors(m)
               + c["dense"] * 3 * d * m["dense_d_ff"]
               + c["moe"] * (moe_fixed_params(m) - m["n_experts"] + routed)
               + d * m["vocab_size"])


def band_keys(rows: int, window: int) -> int:
    """(query, key) pairs that rows 0 .. of a window layer see."""
    return sum(min(p + 1, window) for p in range(min(rows, window))) \
        + max(0, rows - window) * window


def causal_keys(rows: int) -> int:
    """(query, key) pairs that rows 0 .. see causally."""
    return rows * (rows + 1) // 2


def chosen_keys(rows: int, topk: int) -> int:
    """(query, key) pairs a full layer ATTENDS: row p its ``min(p + 1,
    index_topk)`` chosen rows."""
    return band_keys(rows, topk)


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward, recomputation not counted: 6 per matmul
    parameter a token meets; the indexer's products over the causal
    pairs, attention over the pairs the model asks for (the chosen rows,
    the band), unabsorbed widths. (No cell trains this family.)"""
    c = layer_counts(m)
    f, w = _kind(m, "full"), _kind(m, "window")

    def width(k):
        return k["qk_nope_head_dim"] + k["qk_rope_head_dim"] + k["v_head_dim"]

    attn = 2.0 / seq * (
        c["full"] * (f["heads"] * chosen_keys(seq, m["index_topk"])
                     * width(f) + m["index_heads"] * causal_keys(seq)
                     * m["index_head_dim"])
        + c["window"] * w["heads"] * band_keys(seq, m["sliding_window"])
        * width(w))
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
    STORED: latent | rotated key padded to whole lanes (a full layer's
    640 numbers, a ring's 1,152), the index key as it is."""
    def stored(kind):
        k = _kind(m, kind)
        return -(-(k["kv_lora_rank"] + k["qk_rope_head_dim"])
                 // _LANES) * _LANES * itemsize

    return {"full": stored("full"),
            "index": m["index_head_dim"] * itemsize,
            "ring": stored("window")}


def state_bytes_per_slot(m: dict, max_len: int, itemsize: int = 2) -> dict:
    """What one stream's state takes, by kind: ``max_len`` latent rows
    and index keys a full layer, ``sliding_window`` ring rows a window
    layer."""
    c, row = layer_counts(m), row_bytes(m, itemsize)
    return {"full": c["full"] * max_len * row["full"],
            "index": c["full"] * max_len * row["index"],
            "ring": c["window"] * m["sliding_window"] * row["ring"]}


def decode_step_bytes(m: dict, slots: int, live_rows_per_slot: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step of ``slots`` streams cannot avoid: every
    weight outside the routed experts once (attention, indexer, dense
    MLP, router, shared expert, head), the held experts the slots'
    tokens touch (``experts_touched``), the slots' embedding rows; the
    index keys of EVERY live row (the indexer scores them all), the
    latent rows the model asks a full layer to read (the ``min(live,
    index_topk)`` chosen, at latent | rotated key without the padding)
    and the window's ring rows. A floor: an implementation that reads
    more reads LOW, never over 100%."""
    d, c = m["d_model"], layer_counts(m)
    f, w = _kind(m, "full"), _kind(m, "window")
    weights = (_attn_total(m) + c["dense"] * 3 * d * m["dense_d_ff"]
               + c["moe"] * (moe_fixed_params(m)
                             + experts_touched(m, slots) * expert_params(m))
               + d * m["vocab_size"] + slots * d) * itemsize
    rows = c["full"] * (
        live_rows_per_slot * m["index_head_dim"]
        + min(live_rows_per_slot, m["index_topk"])
        * (f["kv_lora_rank"] + f["qk_rope_head_dim"])) \
        + c["window"] * min(live_rows_per_slot, m["sliding_window"]) \
        * (w["kv_lora_rank"] + w["qk_rope_head_dim"])
    return weights + slots * rows * itemsize


def flash_calls(m: dict, batch: int, seq: int) -> list:
    """None a train step: the block's attention kernels are forward only
    and no cell trains it. A prefill's are :func:`dsa_index_work`,
    :func:`dsa_attn_work` and the window layers' band."""
    return []


def dsa_index_work(m: dict, rows: int, bucket: int = 0,
                   itemsize: int = 2) -> tuple:
    """(operations, bytes) one full layer's index scores of a
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
    """(operations, bytes) one full layer's attention of a ``rows``-row
    prompt from position 0 cannot avoid, all of its ``dsa_attn`` calls
    together, counting the work the MODEL asks: two products a CHOSEN
    (query, key) pair a head, ``qk_nope + qk_rope`` and ``v_head_dim``
    wide (a kernel that walks every causal pair reads low, by the
    chosen pairs' share of them); q read and o written once, the rows'
    k and v of every head read once."""
    k = _kind(m, "full")
    h = k["heads"]
    dk, dv = k["qk_nope_head_dim"] + k["qk_rope_head_dim"], k["v_head_dim"]
    return (2.0 * h * chosen_keys(rows, m["index_topk"]) * (dk + dv),
            2.0 * rows * h * (dk + dv) * itemsize)


def dsa_kth_work(m: dict, rows: int, bucket: int) -> tuple:
    """(operations, bytes) one full layer's selections of a prompt whose
    ``rows`` rows ran in a ``bucket``-row call cannot avoid, all of its
    ``dsa_kth`` calls together: every row's ``bucket`` int32 keys read
    ONCE (the 32 counting passes are the vector unit's and are not
    counted: the share reads low where they bind)."""
    return 0.0, 4.0 * rows * bucket


def decode_attn_work(m: dict, chosen_rows: float,
                     itemsize: int = 2) -> tuple:
    """(operations, bytes) one ``dsa_decode_attn`` call cannot avoid for
    the work the MODEL asks: every head's query against the
    ``chosen_rows`` latent rows the slots' indexers chose (scores over
    latent | rotated key, the probabilities against the latents), each
    such row read once AS STORED. A kernel that reads every live row and
    masks reads low, by the chosen rows' share of the live ones."""
    k = _kind(m, "full")
    r = k["kv_lora_rank"]
    return (2.0 * k["heads"] * chosen_rows * (r + k["qk_rope_head_dim"] + r),
            chosen_rows * row_bytes(m, itemsize)["full"])


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
# a full layer's prefill attends its heads in this many groups a segment
STEP_READS = "live"
PREFILL_HEAD_GROUPS = 4
