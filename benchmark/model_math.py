"""Operations and bytes the algorithm needs, from shapes alone, and the
table of peaks. Takes ``LlamaConfig`` fields as a plain dict (see
``manifest.llama_fields``); never imports the program."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       "to benchmark/peaks.json with its source")
    return table[device_kind]


def layer_params(m: dict) -> int:
    d, f = m["d_model"], m["d_ff"]
    hd = d // m["n_heads"]
    attn = 2 * d * m["n_heads"] * hd + 2 * d * m["n_kv_heads"] * hd
    return attn + 3 * d * f + 2 * d


def num_params(m: dict) -> int:
    d, v = m["d_model"], m["vocab_size"]
    head = 0 if m.get("tie_embeddings") else d * v
    return v * d + m["n_layers"] * layer_params(m) + d + head


def matmul_params(m: dict) -> int:
    """Parameters that meet every token in a matrix product (the
    embedding is a gather, the norms are elementwise)."""
    d = m["d_model"]
    return m["n_layers"] * (layer_params(m) - 2 * d) + d * m["vocab_size"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward, recomputation not counted: 6 per matmul
    parameter, and causal attention's two products over the half of the
    ``seq`` x ``seq`` square that is not masked."""
    attn_fwd = m["n_layers"] * 4 * m["d_model"] * seq * 0.5
    return 3.0 * (2 * matmul_params(m) + attn_fwd)


def flash_flops(batch: int, seq: int, heads: int, head_dim: int, *,
                backward: bool) -> float:
    """Causal flash attention on [batch, seq, heads, head_dim]: forward
    is QK^T and PV; the fused backward recomputes S and forms dV, dP, dQ
    and dK (five products). Masked-out half not counted."""
    products = 5 if backward else 2
    return products * 2.0 * batch * heads * seq * seq * head_dim * 0.5


def flash_bytes(batch: int, seq: int, heads: int, kv_heads: int,
                head_dim: int, *, backward: bool, itemsize: int = 2) -> float:
    """HBM traffic the kernel cannot avoid: q, k, v read and o written
    once (forward); backward reads q, k, v, o, do and writes dq, dk, dv."""
    q = batch * seq * heads * head_dim * itemsize
    kv = batch * seq * kv_heads * head_dim * itemsize
    return (3 * q + 4 * kv) if backward else (2 * q + 2 * kv)


def roofline_seconds(flops: float, nbytes: float, peak: dict):
    """-> (least seconds the chip could take, which bound it is)."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def decode_step_bytes(m: dict, slots: int, live_rows_per_slot: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step of ``slots`` streams must read: every
    matmul weight once in the compute type, the slots' embedding rows,
    and the k and v rows that are live (positions already written)."""
    hd = m["d_model"] // m["n_heads"]
    weights = (matmul_params(m) + slots * m["d_model"]) * itemsize
    cache = (slots * live_rows_per_slot * m["n_layers"] * 2
             * m["n_kv_heads"] * hd * itemsize)
    return weights + cache


def mean_live_rows(shapes) -> float:
    """Time-weighted mean context of a slot that is always occupied: a
    request of (prompt, out) holds its slot for about ``out`` steps
    while its context grows from ``prompt`` to ``prompt + out``."""
    num = sum(o * (p + o / 2.0) for p, o in shapes)
    return num / sum(o for _, o in shapes)
