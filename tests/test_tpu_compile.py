"""The programs the benchmark's cells run, as comparable text.

Run as a script from a checkout's root this writes the optimised HLO of
the programs the serving cells run and of both train cells' steps,
compiled for a described v5e and made comparable between two checkouts
(``dump_serving_programs``): a refactor that claims to leave those
programs alone diffs the parent's ``<dir>`` against its own.

    PYTHONPATH=. JAX_PLATFORMS=cpu python3 tests/test_tpu_compile.py <dir>

It holds no test: the compile tests are ``tests/test_tpu_compile_*.py``,
one file a block, and what they share with this script is
``tests/_tpu_compile.py``.
"""

import dataclasses
import functools
import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from _tpu_compile import (MOSAIC_BODY, _engine_args, _lower_prefill,
                          _mosaic_text, _on, _train_step)
from ray_tpu.models import decode_engine as de
from ray_tpu.models import llama
from ray_tpu.parallel import MeshConfig

SERVING_CELLS = (("internlm2-1.8b", "doc-saturated"),
                 ("internlm2-1.8b", "chat-steady"),
                 ("olmoe-1b-7b-0125-1chip", "doc-saturated"),
                 ("ling-3.0-flash-vl-ep4-1chip", "reason-saturated"),
                 ("k-exaone-236b-a23b-ep8-1chip", "reason-long-saturated"),
                 ("instella-moe-16b-a3b-pp4-1chip", "longdoc-saturated"),
                 ("solar-open2-250b-ep8-1chip", "longreason-saturated"),
                 ("mimo-v2.5-ep16-1chip", "longreason-saturated"),
                 ("granite-4.0-h-small-ep4-1chip", "sessions-saturated"),
                 ("dots3-note-prev-ep8-1chip", "longreason-saturated-24"),
                 ("glm-5.2-ep16-1chip", "longreason-saturated-16"),
                 ("glm-5.3-flash-ep8-1chip", "longreason-saturated-24"),
                 ("lfm2-8b-a1b-ep2-1chip", "rag-saturated"))
TRAIN_CELLS = (("mistral-7b-v0.3-1chip", "pretrain-4k"),
               ("internlm2-1.8b", "pretrain-4k-fsdp2tp2"))


def _comparable(compiled) -> str:
    """``as_text()`` without what names the checkout: ``metadata={...}``,
    the header's source tables, and in a Mosaic call the module's bytes
    (they carry paths and line numbers) for a hash of its text without
    locations. Instruction suffixes ``.N`` are renamed in order of first
    appearance: numbering apart, the same program gives the same text."""
    def mosaic(m):
        return '"body": "%s"' % hashlib.sha1(
            _mosaic_text(m.group(1)).encode()).hexdigest()

    text = re.sub(r", metadata=\{[^}]*\}", "", compiled.as_text())
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(\d+ .*\n)*", "\n", text)
    text = MOSAIC_BODY.sub(mosaic, text)
    names: dict = {}
    return re.sub(r"\.\d+\b", lambda m: names.setdefault(
        m.group(0), f".n{len(names)}"), text)


def dump_serving_programs(out_dir: str, only: tuple = ()) -> None:
    """Every program the benchmark's cells run, compiled for a described
    v5e: ``decode_chunk(lanes=None)`` and the one-row
    ``_prefill_batch_into_slots`` at every bucket, at each serving
    configuration's own fields and each engine shape of its cells
    (``benchmark/``; ``chat-bursty``'s is ``chat-steady``'s), and both
    cells' train steps on their meshes. ``only``: the configurations
    whose name holds one of these words (none: every one)."""
    from jax.experimental import topologies

    from benchmark import manifest
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import grouped_matmul as gm

    jax.config.update("jax_enable_compilation_cache", False)
    # (the dispatches would read the CPU backend here and take
    # ragged_dot, the XLA body and the reference product)
    gm.grouped_matmul = functools.partial(gm.grouped_matmul, use_kernel=True)
    da.decode_attention = functools.partial(da.decode_attention,
                                            use_kernel=True)
    if hasattr(da, "decode_attention_latent"):  # (a parent before PR 39)
        da.decode_attention_latent = functools.partial(
            da.decode_attention_latent, use_kernel=True)
    from ray_tpu.models import ling
    if hasattr(ling, "_kda_step"):  # (a parent before PR 41)
        ling._kda_step = functools.partial(ling._kda_step, use_kernel=True)
    if hasattr(ling, "_kda_chunk"):  # (a parent before PR 47)
        ling._kda_chunk = functools.partial(ling._kda_chunk, use_kernel=True)
    if hasattr(ling, "_kda_qkvg"):  # (a parent before PR 57)
        ling._kda_qkvg = functools.partial(ling._kda_qkvg, use_kernel=True)
    try:
        from ray_tpu.models import solar
        solar._kda_step = ling._kda_step
        if hasattr(ling, "_kda_chunk"):
            solar._kda_chunk = ling._kda_chunk
        if hasattr(ling, "_kda_qkvg"):
            solar._kda_qkvg = ling._kda_qkvg
    except ImportError:  # (a parent before PR 42)
        pass
    try:
        from ray_tpu.models import glm_next
        glm_next._kda_qkvg = ling._kda_qkvg
    except ImportError:  # (a parent before PR 65)
        pass
    try:
        from ray_tpu.models import granite
        granite._ssd_step = functools.partial(granite._ssd_step,
                                              use_kernel=True)
    except ImportError:  # (a parent before PR 54)
        pass
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def write(name, compiled):
        path = f"{out_dir}/{name}.txt"
        with open(path, "w") as f:
            f.write(_comparable(compiled))
        print(path, flush=True)

    def traffic(name):
        with open(f"benchmark/traffic/{name}.json") as f:
            return json.load(f)

    def wanted(config):
        return not only or any(word in config for word in only)

    for config, cell in SERVING_CELLS:
        if not os.path.isfile(f"benchmark/configs/{config}.json") \
                or not wanted(config):
            continue  # (a parent that lacks the configuration)
        eng = traffic(cell)["engine"]
        slots, max_len = eng["slots"], eng["max_len"]
        fam, m = manifest.model(config)
        prog = fam.build(m, max_seq_len=max_len, remat=False)
        cfg = prog.cfg
        if isinstance(cfg, llama.LlamaConfig):
            cfg = dataclasses.replace(cfg, use_flash=True)
            params, state, _ = _engine_args(cfg, chip, slots, max_len)
        else:  # (a block of its own draws the serving types itself)
            if hasattr(cfg, "use_flash"):
                cfg = dataclasses.replace(cfg, use_flash=True)
            params = _on(chip, jax.eval_shape(prog.init_params,
                                              jax.random.PRNGKey(0)))
            state = _on(chip, jax.eval_shape(
                lambda: de.slot_model(cfg).init_state(cfg, slots, max_len)))
        vec = lambda dt, n=slots: jax.ShapeDtypeStruct(  # noqa: E731
            (n,), dt, sharding=chip)
        programs = {"decode_chunk": de.decode_chunk.lower(
            params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
            chunk=eng["chunk_tokens"])}
        for bucket in eng["prompt_buckets"]:
            programs[f"prefill_{bucket}"] = _lower_prefill(
                cfg, chip, bucket, (params, state, vec))
        for name, lowered in programs.items():
            write(f"{config}.{slots}x{max_len}.{name}", lowered.compile())
    for config, cell in TRAIN_CELLS:
        if not wanted(config):
            continue
        tr = traffic(cell)
        fam, m = manifest.model(config)
        cfg = dataclasses.replace(
            fam.build(m, max_seq_len=tr["seq"], remat=True).cfg,
            use_flash=True)
        write(f"{config}.{cell}.train_step", _train_step(
            topo, cfg, MeshConfig(**tr["mesh"]), tr["batch"], tr["seq"]))


if __name__ == "__main__":
    dump_serving_programs(sys.argv[1], tuple(sys.argv[2:]))
