"""Minimal MLP classifier — the MNIST-class smoke-test workload
(reference anchor: Ray Train TorchTrainer MNIST MLP).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ray_tpu.ops.losses import softmax_cross_entropy


@dataclasses.dataclass(frozen=True)
class MLPConfig:
    d_in: int = 784
    d_hidden: int = 512
    n_hidden: int = 2
    d_out: int = 10
    dtype: str = "float32"


def init_params(cfg: MLPConfig, key):
    dims = [cfg.d_in] + [cfg.d_hidden] * cfg.n_hidden + [cfg.d_out]
    keys = jax.random.split(key, len(dims) - 1)
    return {
        f"layer{i}": {
            "w": jax.random.normal(keys[i], (dims[i], dims[i + 1]), jnp.float32)
            / math.sqrt(dims[i]),
            "b": jnp.zeros((dims[i + 1],), jnp.float32),
        }
        for i in range(len(dims) - 1)
    }


def param_logical_axes(cfg: MLPConfig):
    n = cfg.n_hidden + 1
    return {
        f"layer{i}": {"w": ("embed", "mlp"), "b": ("norm",)} for i in range(n)
    }


def forward(params, x, cfg: MLPConfig):
    n = cfg.n_hidden + 1
    h = x.astype(jnp.dtype(cfg.dtype))
    for i in range(n):
        p = params[f"layer{i}"]
        h = h @ p["w"].astype(h.dtype) + p["b"].astype(h.dtype)
        if i < n - 1:
            h = jax.nn.relu(h)
    return h.astype(jnp.float32)


def loss_fn(params, batch, cfg: MLPConfig):
    logits = forward(params, batch["x"], cfg)
    labels = batch["y"]
    loss = softmax_cross_entropy(logits, labels).mean()
    acc = (logits.argmax(-1) == labels).mean()
    return loss, {"loss": loss, "accuracy": acc}


# --------------------------------------------------------------------------
# Residual adapter — the speculative-decode draft head
# --------------------------------------------------------------------------
#
# A 2-layer bottleneck MLP applied residually to the draft trunk's hidden
# state (models/decode_engine.py): h -> h + relu(h @ w1 + b1) @ w2. The
# DOWN projection is ZERO-initialized, so at init the adapter is the
# identity and the draft's proposals are exactly the truncated-trunk
# argmax/sample — speculation correctness never depends on the head, and
# a later distillation pass (EAGLE/Medusa-style) can train w2 away from
# zero to raise the acceptance rate without touching the published
# target weights.

def init_draft_head(d_model: int, key, d_hidden: int = 0):
    d_hidden = d_hidden or max(8, d_model // 4)
    return {
        "w1": jax.random.normal(key, (d_model, d_hidden), jnp.float32)
        / math.sqrt(d_model),
        "b1": jnp.zeros((d_hidden,), jnp.float32),
        "w2": jnp.zeros((d_hidden, d_model), jnp.float32),
    }


def apply_draft_head(head, h):
    """h: [..., d_model] (any leading shape). Identity when w2 == 0."""
    if head is None:
        return h
    hd = h.astype(jnp.float32)
    up = jax.nn.relu(hd @ head["w1"] + head["b1"])
    return (hd + up @ head["w2"]).astype(h.dtype)
