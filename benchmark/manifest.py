"""BENCHMARK.json and the files it names, found by name.

A cell is an entry of ``workloads``: a configuration under a traffic mix
(or a training job). Everything that belongs to one configuration, one
mix or one per-layer metric is a file of its own under this directory,
so a later PR adds a cell by adding files and entries and edits nothing.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class ManifestError(ValueError):
    """BENCHMARK.json or a file it names does not hold what it must."""


def check_name(name: str) -> str:
    """A name is a file's stem too: letters, digits, ``_``, ``.``, ``-``."""
    if not isinstance(name, str) or not NAME.match(name) or ".." in name:
        raise ManifestError(f"not a name: {name!r}")
    return name


def load_manifest(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(kind: str, name: str, base: str) -> dict:
    path = os.path.join(base, kind, check_name(name) + ".json")
    if not os.path.isfile(path):
        raise ManifestError(f"no {kind} file for {name!r}: {path}")
    with open(path) as f:
        return json.load(f)


def _metrics_of(manifest: dict, section: str, workload: str) -> list[dict]:
    return [m for m in manifest[section]
            if "workloads" not in m or workload in m["workloads"]]


def cell(manifest: dict, workload: str, base: str = HERE) -> dict:
    """Everything a run of ``workload`` needs, by name."""
    check_name(workload)
    found = [w for w in manifest["workloads"] if w["name"] == workload]
    if len(found) != 1:
        raise ManifestError(f"workload {workload!r}: {len(found)} entries")
    w = found[0]
    return {
        "name": workload, "chips": int(w["chips"]),
        "config_name": check_name(w["config"]),
        "traffic_name": check_name(w["traffic"]),
        "config": _read_json("configs", w["config"], base),
        "traffic": _read_json("traffic", w["traffic"], base),
        "end_to_end": _metrics_of(manifest, "end_to_end", workload),
        "per_layer": _metrics_of(manifest, "per_layer", workload),
    }


def layer_metric_reader(name: str, base: str = HERE):
    """``layer_metrics/<name>.py``'s ``read(ctx)``: the metric's value, or
    None where it finds nothing to read (the metric is then left out)."""
    path = os.path.join(base, "layer_metrics", check_name(name) + ".py")
    if not os.path.isfile(path):
        raise ManifestError(f"no reader for per-layer metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def llama_fields(config: dict) -> dict:
    """The published ``config.json`` keys as ``LlamaConfig`` fields.
    A bias, a sliding window or tied embeddings has no field there: a
    configuration that needs one is refused, not approximated."""
    if config.get("bias") or config.get("attention_bias") \
            or config.get("sliding_window") \
            or config.get("hidden_act", "silu") != "silu":
        raise ManifestError("the repo's block has no bias, no sliding "
                            "window and no other activation than silu")
    if config["hidden_size"] % config["num_attention_heads"]:
        raise ManifestError("head size is not hidden / heads")
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": int(config["num_hidden_layers"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "d_ff": int(config["intermediate_size"]),
        "rope_theta": float(config["rope_theta"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": bool(config.get("tie_word_embeddings", False)),
        "dtype": "bfloat16",
    }


REHEARSAL = "rehearsal:"  # prefix of a model name: tiny widths, f32
TINY_FIELDS = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                   n_kv_heads=2, d_ff=128, rope_theta=1e6, rms_eps=1e-5,
                   tie_embeddings=False, dtype="float32")


def model_fields(model: str) -> dict:
    """``LlamaConfig`` fields of configuration ``model`` (a name under
    ``benchmark/configs``), or the CPU rehearsal's stand-in."""
    if model.startswith(REHEARSAL):
        return dict(TINY_FIELDS)
    return llama_fields(_read_json("configs", model, HERE))
