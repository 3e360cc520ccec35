"""Compile the main path's kernels and step programs for the real chip.

The TPU compiler is installed here and compiles for a chip that is
DESCRIBED, not attached (on-chip-measurement guide, section 2): what it
refuses here — a Mosaic kernel GSPMD cannot partition, a tile that does
not align, a program that does not fit HBM — costs no chip time. Nothing
runs, so these say nothing about results or speed; ``chip_smoke.py`` is
the run.

Code that asks ``jax.default_backend()`` sees the CPU during such a
compile, so every case asks for the kernel explicitly (``use_flash=True``,
``interpret=False``) and asserts the custom call is in the compiled text.
The cheap cases are tier-1; the 14-25 s programs are ``-m slow``:

    JAX_PLATFORMS=cpu python -m pytest tests/test_tpu_compile.py -m slow -s

Run as a script from a checkout's root it writes the optimised HLO of
the programs the serving cells run, made comparable between two
checkouts (``dump_serving_programs``): a refactor that claims to leave
those programs alone diffs the parent's ``<dir>`` against its own.

    PYTHONPATH=. JAX_PLATFORMS=cpu python3 tests/test_tpu_compile.py <dir>
"""

import base64
import functools
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import Mesh, SingleDeviceSharding  # noqa: E402

from ray_tpu.models import decode_engine as de  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.ops.flash_attention import flash_attention  # noqa: E402
from ray_tpu.parallel import AXES, MeshConfig, use_mesh  # noqa: E402
from ray_tpu.train import batch_sharding, make_train_step  # noqa: E402
from ray_tpu.train.optim import fused_adamw  # noqa: E402
from ray_tpu.train.step import train_state_shardings  # noqa: E402

KERNEL = "tpu_custom_call"
MIB = 2**20


@pytest.fixture(scope="module")
def topo():
    """The described v5e 2x2 host. The persistent compile cache is off
    around these compiles: an entry written for a described device
    cannot be read back without the chip (it only warns next time)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    """Shapes of ``tree`` placed by ``sharding`` (one sharding, or a tree
    of them): a described device cannot hold arrays."""
    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, sharding)
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _serve_cfg(size="1b", max_len=288):
    # as serve/llm.py build_model makes it
    return llama.LlamaConfig(**{
        **llama.llama2_size(size).__dict__, "vocab_size": 32128,
        "max_seq_len": max_len, "dtype": "bfloat16", "remat": False})


def _train_cfg(size="1b", seq=2048, **kw):
    # the 1B recipe of chip_smoke.model_fields and its train phase;
    # use_flash=True because the dispatch would read the CPU backend
    # here and take the reference
    return llama.LlamaConfig(**{
        **llama.llama2_size(size).__dict__, "vocab_size": 32128,
        "max_seq_len": seq, "dtype": "bfloat16", "remat": True,
        "remat_policy": "flash_qkv", "use_flash": True, **kw})


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"arguments_mib": m.argument_size_in_bytes // MIB,
            "temporaries_mib": m.temp_size_in_bytes // MIB,
            "outputs_mib": m.output_size_in_bytes // MIB,
            "aliased_mib": m.alias_size_in_bytes // MIB}


# ---- kernels ----

FLASH_SHAPES = {
    # name: (batch, seq, q heads, kv heads, head dim)
    "1b": (2, 2048, 16, 8, 128),
    "350m": (8, 2048, 8, 8, 128),
}
# The GQA 1B shape compiles in 2-4 s; tier-1 keeps its backward case,
# whose program holds the forward kernel too. The MHA 350M shape takes
# the multi-head grid cells (flash_heads_per_block=4) and Mosaic needs
# ~12 s for each direction, so it rides with the long programs.
_slow = pytest.mark.slow


@pytest.mark.parametrize("shape,direction", [
    pytest.param("1b", "forward", marks=_slow), ("1b", "backward"),
    pytest.param("350m", "forward", marks=_slow),
    pytest.param("350m", "backward", marks=_slow)])
def test_flash_kernel_compiles(topo, shape, direction):
    b, t, hq, hkv, d = FLASH_SHAPES[shape]
    chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((b, t, hq, d), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((b, t, hkv, d), jnp.bfloat16, sharding=chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    fn = fwd if direction == "forward" else jax.grad(
        lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    text = jax.jit(fn).lower(q, kv, kv).compile().as_text()
    # backward: the forward kernel and the fused backward kernel
    assert text.count(KERNEL) >= (1 if direction == "forward" else 2)


def _engine_args(cfg, chip, slots=8, max_len=288):
    # what an engine hands its programs: the serving cast of the masters
    params = _on(chip, jax.eval_shape(lambda: llama.serving_params(
        cfg, llama.init_params(cfg, jax.random.PRNGKey(0)))))
    cache = _on(chip, jax.eval_shape(
        lambda: de.init_ragged_cache(cfg, slots, max_len)))
    vec = lambda dt, n=slots: jax.ShapeDtypeStruct(  # noqa: E731
        (n,), dt, sharding=chip)
    return params, cache, vec


def _weight_casts(text: str, cfg) -> list:
    """What a compiled serving program still holds of the f32 masters:
    its f32 entry parameters larger than a norm stack, and every f32
    array of a matrix's shape (the stack, one layer of it, the embedding
    or the head), which is the operand or the result of a cast."""
    masters = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    flat = jax.tree_util.tree_flatten_with_path(masters)[0]
    norms = max(a.size for path, a in flat
                if path[-1].key in llama._F32_LEAVES)
    found = [f"f32 parameter [{dims}]" for dims in re.findall(
        r"= f32\[([\d,]+)\]\S* parameter\(\d+\), sharding=", text)
        if np.prod([int(d) for d in dims.split(",")]) > norms]
    for path, a in flat:
        if path[-1].key in llama._F32_LEAVES:
            continue
        stacked = path[0].key == "layers"
        for shape in {a.shape, a.shape[stacked:], (1, *a.shape[stacked:])}:
            dims = ",".join(map(str, shape))
            if f"f32[{dims}]" in text:
                found.append(f"{path[-1].key}: f32[{dims}]")
    return found


def _lower_prefill(cfg, chip, bucket, args=None, **engine):
    """The engine's cold prefill call: one prompt, one row of its
    bucket's width, into a cache of ``slots`` x ``max_len`` (``args``:
    a model's own (params, state, vec) in place of ``_engine_args``')."""
    params, cache, vec = args or _engine_args(cfg, chip, **engine)
    prompt = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=chip)
    return de._prefill_batch_into_slots.lower(
        params, prompt, vec(jnp.int32, 1), vec(jnp.int32, 1),
        vec(jnp.uint32, 1), vec(jnp.float32, 1), vec(jnp.float32, 1),
        cache, vec(jnp.int32), cfg=cfg)


def _whole_layer_ops(text: str, cfg, slots: int, rows: int) -> list:
    """Operations of a compiled serving program that make an array of
    ``slots`` x ``rows`` cache rows (one layer of the cache, or the
    stack) by moving it: a ``dynamic-slice`` (fused or not), a ``copy``
    or a ``transpose``."""
    layer = slots * rows * cfg.n_kv_heads * 128
    found = []
    for name, dims, op in re.findall(
            r"%([\w.\-]+) = \w+\[([\d,]+)\]\S* ([\w\-]+)\(", text):
        moved = op in ("copy", "transpose", "dynamic-slice") or (
            op == "fusion" and re.search(r"dynamic-slice|copy|transpose",
                                         name))
        if moved and f"{slots},{rows}," in dims + "," and np.prod(
                [int(d) for d in dims.split(",")]) >= layer:
            found.append(f"{name}: [{dims}] {op}")
    return found


def _kda_chunk_calls(text: str, prefetched_ok: bool = False) -> list:
    """The ``kda_chunk`` kernel's calls in a compiled prefill program
    (``ops/kda_chunk.py``: one a KDA layer, inside the segment scan
    where the prefill has one), checked for what the kernel is for: no
    float32 array with a ``64, 64, 128`` tail is left (the pairwise
    decays of a chunk's rows, which the XLA body makes whole), and XLA
    added nothing that moves an operand on the call's account (a
    ``copy``, an asynchronous copy or slice between memories: the
    arrays are taken as the projections leave them, ``S`` in the buffer
    the scan carries). ``prefetched_ok``: an operand of a few MB that
    XLA's memory-space assignment brings into VMEM ahead of the call
    (an asynchronous copy into ``S(1)``, its own choice for an array
    that fits there, not a relayout) is let through."""
    lines = text.splitlines()
    calls = [ln for ln in lines
             if KERNEL in ln and "kda_chunk" in ln.split(" = ")[0]]
    assert not re.findall(r"f32\[[\d,]*64,64,128\]", text)
    made_by = {m.group(1): m.group(2) for m in (
        re.match(r"\s*(%[\w.\-]+) = .*?\s([\w\-]+)\(", ln) for ln in lines)
        if m}
    for call in calls:
        assert re.search(r"attn/attn_linear/(jit\(_kda_chunk\)/)?kda_chunk/"
                         "pallas_call", call), call[:300]
        operands = re.search(r"custom-call\(([^)]*)\)", call).group(1)
        operands = re.findall(r"%[\w.\-]+", operands)
        assert len(operands) == 6, operands
        moved = {o: made_by.get(o) for o in operands if made_by.get(o) in (
            "copy", "copy-done", "slice-done", "dynamic-slice-done",
            "async-done", "transpose")}
        if prefetched_ok:
            moved = {o: op for o, op in moved.items() if not (
                op == "copy-done" and re.search(
                    re.escape(o) + r" = f32\[[\d,]+\]\{[^}]*S\(1\)\}", text))}
        assert not moved, moved
        assert "output_to_operand_aliasing={{1}: (5, {})}" in call, call[:600]
    return calls


def test_decode_chunk_compiles_at_1b_widths(topo):
    cfg = _serve_cfg()
    chip = SingleDeviceSharding(topo.devices[0])
    params, cache, vec = _engine_args(cfg, chip)
    compiled = de.decode_chunk.lower(
        params, cache, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=8).compile()
    mem = _mem(compiled)
    # the serving tree (bf16 matrices) and the cache are the arguments;
    # no bf16 copy of the weights is left among the temporaries
    assert mem["arguments_mib"] + mem["temporaries_mib"] < 3 * 1024, mem


# InternLM2-1.8B's widths, two layers deep
INTERNLM2 = dict(vocab_size=92544, d_model=2048, n_layers=2, n_heads=16,
                 n_kv_heads=8, d_ff=8192, rope_theta=1e6, rms_eps=1e-5,
                 max_seq_len=1296, dtype="bfloat16", remat=False)


# the serving cells' engine shapes (benchmark/traffic/doc-saturated.json,
# chat-steady.json and chat-bursty.json) and prompt buckets
DOC, CHAT = dict(slots=8, max_len=1296), dict(slots=32, max_len=512)
PREFILL_CALLS = [("internlm2", DOC, 256), ("internlm2", DOC, 512),
                 ("internlm2", DOC, 1024), ("internlm2", CHAT, 64),
                 ("internlm2", CHAT, 128), ("internlm2", CHAT, 256),
                 ("olmoe", DOC, 1024)]


def _shapes(text: str) -> set:
    """Every array shape of a compiled program's text."""
    return {tuple(int(d) for d in dims.split(","))
            for dims in re.findall(r"\b[a-z]+\d*\[([\d,]+)\]", text)}


@pytest.mark.parametrize("model,engine,bucket", PREFILL_CALLS, ids=[
    f"{m}-{e['slots']}x{e['max_len']}-{b}" for m, e, b in PREFILL_CALLS])
def test_one_row_prefill_is_sized_by_its_bucket(topo, monkeypatch, model,
                                                engine, bucket):
    """The cells' cold prefill call, two layers deep, at each of their
    six buckets (and the sparse model's widest): one prompt of P rows
    into ``slots`` x ``max_len``. Attention is the ``flash_fwd`` kernel
    over the prompt's own rows; nothing but the stack itself has an
    extent of ``max_len`` rows (no temporary cache, no ``[.., P,
    max_len]`` scores); the head sees one row (no ``[P, vocabulary]``
    logits); the donated stack is updated in place, P rows of one slot,
    and no layer of it moves."""
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    slots, max_len = engine["slots"], engine["max_len"]
    # (use_flash: the dispatch would read the CPU backend here)
    cfg = llama.LlamaConfig(**{
        **(INTERNLM2 if model == "internlm2" else {**OLMOE, "n_layers": 2}),
        "max_seq_len": max_len, "use_flash": True})
    chip = SingleDeviceSharding(topo.devices[0])
    compiled = _lower_prefill(cfg, chip, bucket, **engine).compile()
    text = compiled.as_text()
    mem = _mem(compiled)
    print(f"\nprefill 1 x {bucket} into {slots} x {max_len}: {mem}")
    assert "flash_fwd" in text
    assert text.count(KERNEL) == (1 if model == "internlm2" else 4)
    shapes = _shapes(text)
    stack = (cfg.n_layers, slots, max_len, cfg.n_kv_heads * 128)
    assert {s for s in shapes if max_len in s} == {stack}
    assert not [s for s in shapes if cfg.vocab_size in s and bucket in s]
    # the cache is donated: updated in place, never copied
    assert mem["aliased_mib"] >= 2 * 2 * slots * max_len * (
        cfg.n_kv_heads * 128) * 2 // MIB, mem
    assert mem["temporaries_mib"] < 64, mem
    assert _whole_layer_ops(text, cfg, slots, max_len) == []


# ---- the dropless expert layer (OLMoE's widths) ----

OLMOE = dict(vocab_size=50304, d_model=2048, n_layers=4, n_heads=16,
             n_kv_heads=16, d_ff=1024, rope_theta=1e4, rms_eps=1e-5,
             n_experts=64, top_k=8, norm_topk_prob=False, qk_norm=True,
             moe_impl="dropless", max_seq_len=1296, dtype="bfloat16",
             remat=False)


@pytest.mark.parametrize("rows,direction", [
    (64, "forward"), (8192, "forward"), (8192, "backward")])
def test_grouped_matmul_compiles_at_olmoe_shapes(topo, rows, direction):
    """A decode step's 64 assignment rows and a 1024-token prefill's
    8192, gate (2048 -> 1024) and down (1024 -> 2048), at the tile the
    kernel picks; backward: the transposed product and ``moe_tgmm``."""
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    chip = SingleDeviceSharding(topo.devices[0])
    sizes = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=chip)
    for k, n in ((2048, 1024), (1024, 2048)):
        lhs = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=chip)
        rhs = jax.ShapeDtypeStruct((64, k, n), jnp.bfloat16, sharding=chip)

        def fwd(a, b, s):
            return grouped_matmul(a, b, s, use_kernel=True)

        fn = fwd if direction == "forward" else jax.grad(
            lambda a, b, s: fwd(a, b, s).astype(jnp.float32).sum(), (0, 1))
        text = jax.jit(fn).lower(lhs, rhs, sizes).compile().as_text()
        assert text.count(KERNEL) >= (1 if direction == "forward" else 2)


def test_olmoe_decode_chunk_reads_the_expert_stack_in_place(
        topo, monkeypatch):
    """The cell's decode program (4 layers, 8 slots x 1296 rows): three
    kernel calls a layer, and no copy of a layer's experts out of the
    stack: a scan that sliced ``[L, 64, 2048, 1024]`` for the kernel
    copied all 64 experts in every step, read or not."""
    from ray_tpu.ops import grouped_matmul as gm

    # (the dispatch would read the CPU backend here and take ragged_dot)
    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    cfg = llama.LlamaConfig(**OLMOE)
    chip = SingleDeviceSharding(topo.devices[0])
    params, cache, vec = _engine_args(cfg, chip, slots=8, max_len=1296)
    compiled = de.decode_chunk.lower(
        params, cache, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=16).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 3
    assert "bf16[64,2048,1024]" not in text
    assert "bf16[64,1024,2048]" not in text
    mem = _mem(compiled)
    print(f"\nolmoe decode chunk: {mem}")
    # the serving tree (3.8 GB) beside the cache; the f32 masters and
    # the program's bf16 copies of them took 11.3 GB
    assert mem["arguments_mib"] + mem["temporaries_mib"] < 5 * 1024, mem


# (slots, rows a slot, query heads, kv heads) of the serving cells
DECODE_ATTN_SHAPES = {
    "internlm2-doc": (8, 1296, 16, 8),
    "olmoe-doc": (8, 1296, 16, 16),
    "internlm2-chat": (32, 512, 16, 8),
}


@pytest.mark.parametrize("cell", list(DECODE_ATTN_SHAPES))
def test_decode_attention_kernel_compiles_at_the_cells_shapes(topo, cell):
    """``decode_attn`` at a decode step's one query row a slot, reading
    a layer of a stack of 24 in place (the stack is an operand of the
    custom call, no slice of it is)."""
    from ray_tpu.ops.decode_attention import decode_attention

    slots, rows, hq, hkv = DECODE_ATTN_SHAPES[cell]
    chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((slots, 1, hq, 128), jnp.bfloat16,
                             sharding=chip)
    stack = jax.ShapeDtypeStruct((24, slots, rows, hkv * 128),
                                 jnp.bfloat16, sharding=chip)
    text = jax.jit(functools.partial(
        decode_attention, use_kernel=True)).lower(
        q, stack, stack,
        jax.ShapeDtypeStruct((), jnp.int32, sharding=chip),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
    ).compile().as_text()
    assert text.count(KERNEL) == 1 and "decode_attn" in text
    assert not re.search(
        rf"= bf16\[(1,)?{slots},{rows},{hkv * 128}\]", text)


@pytest.mark.parametrize("model", ["internlm2", "olmoe"])
def test_decode_chunk_leaves_the_cache_where_it_lies(topo, monkeypatch,
                                                     model):
    """The doc cell's decode program (8 slots x 1296 rows, 2 layers): a
    step writes 8 rows into the stacked cache and the ``decode_attn``
    kernel reads the layer's live blocks out of the stack in place. No
    kv head is repeated for its query group (f32 ``[8,1296,8,2,128]``
    broadcasts were 1.6 s of an 8 s trace), no layer's cache is sliced
    out of the stack (``constant_dynamic-slice_fusion.17`` / ``.19``, a
    fifth of a chunk), copied or transposed, none is written back into
    the stack whole, the stack is never copied (OLMoE's ``copy.129`` /
    ``.130``), and the donated cache is updated in place."""
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, use_kernel=True))
    cfg = llama.LlamaConfig(**(
        INTERNLM2 if model == "internlm2" else {**OLMOE, "n_layers": 2}))
    chip = SingleDeviceSharding(topo.devices[0])
    params, cache, vec = _engine_args(cfg, chip, slots=8, max_len=1296)
    compiled = de.decode_chunk.lower(
        params, cache, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=16).compile()
    text = compiled.as_text()
    assert "decode_attn" in text
    # one call in the layer loop's body (and OLMoE's three moe_gmm)
    assert text.count(KERNEL) == (1 if model == "internlm2" else 4)
    assert _whole_layer_ops(text, cfg, 8, 1296) == []
    if cfg.n_kv_heads < cfg.n_heads:
        assert "[8,1296,8,2,128]" not in text
        assert "[8,1296,16,128]" not in text
    layer_elems = 8 * 1296 * cfg.n_kv_heads * 128
    elems = {name: int(np.prod([int(d) for d in dims.split(",")]))
             for name, dims in re.findall(
                 r"%([\w.\-]+) = \w+\[([\d,]+)\]", text)}
    for update in re.findall(
            r" dynamic-update-slice\(%[\w.\-]+, %([\w.\-]+),", text):
        assert elems.get(update, 0) < layer_elems, update
    assert not re.search(
        rf"= \w+\[{cfg.n_layers},8,1296,[\d,]+\]\S* copy\(", text)
    mem = compiled.memory_analysis()
    cache_bytes = 2 * cfg.n_layers * layer_elems * 2  # k and v, bf16
    assert mem.alias_size_in_bytes >= cache_bytes, _mem(compiled)


@pytest.mark.parametrize("program", ["chunk", "prefill"])
@pytest.mark.parametrize("model", ["internlm2", "olmoe"])
def test_serving_programs_hold_no_cast_of_a_weight(topo, monkeypatch,
                                                   model, program):
    """The greedy chunk and a one-row prefill call, handed the serving
    tree (``_engine_args``): no f32 parameter larger than a norm stack,
    no f32 array of a matrix's shape anywhere in the program. From the
    f32 masters the same chunk holds both (the casts were 21% of a
    chunk and half of a prefill call, PERF.md PR 28)."""
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    cfg = llama.LlamaConfig(**(
        INTERNLM2 if model == "internlm2" else {**OLMOE, "n_layers": 2}))
    chip = SingleDeviceSharding(topo.devices[0])
    shape = dict(slots=8, max_len=1296)
    if program == "prefill":
        # (512: OLMoE's 256 x top-8 assignment rows are [2048, 2048]
        # themselves, the shape of its wq)
        text = _lower_prefill(cfg, chip, 512, **shape).compile().as_text()
        # (the one row's logits are a fused multiply and reduce over the
        # head, which converts it on the fly inside the fusion: no copy;
        # on the chip 0.52 ms for the head's 379 MB, PERF.md PR 35)
        assert [c for c in _weight_casts(text, cfg)
                if not c.startswith("lm_head: ")] == []
        return
    params, cache, vec = _engine_args(cfg, chip, **shape)
    masters = _on(chip, jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0))))
    serving, from_masters = (de.decode_chunk.lower(
        tree, cache, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=16).compile().as_text() for tree in (params, masters))
    assert _weight_casts(serving, cfg) == []
    assert len(_weight_casts(from_masters, cfg)) >= 8


# ---- the hybrid block (models/ling.py) at the reason cell's sizes ----


def _ling_cell(topo, monkeypatch):
    """``ling-3.0-flash-vl-ep4-1chip.reason-saturated``'s model, engine
    shape and arguments on one described chip, the kernels asked for by
    name (the dispatches would read the CPU backend here)."""
    from benchmark import manifest
    from ray_tpu.models import ling
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    monkeypatch.setattr(ling, "_kda_step", functools.partial(
        ling._kda_step, use_kernel=True))
    monkeypatch.setattr(ling, "_kda_chunk", functools.partial(
        ling._kda_chunk, use_kernel=True))
    with open("benchmark/traffic/reason-saturated.json") as f:
        eng = json.load(f)["engine"]
    fam, m = manifest.model("ling-3.0-flash-vl-ep4-1chip")
    prog = fam.build(m, max_seq_len=eng["max_len"], remat=False)
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(prog.init_params,
                                      jax.random.PRNGKey(0)))
    state = _on(chip, jax.eval_shape(lambda: ling.SLOTS.init_state(
        prog.cfg, eng["slots"], eng["max_len"])))
    vec = lambda dt, n=eng["slots"]: jax.ShapeDtypeStruct(  # noqa: E731
        (n,), dt, sharding=chip)
    return fam, m, prog.cfg, eng, params, state, vec


def test_ling_decode_chunk_keeps_its_state_and_weights_where_they_lie(
        topo, monkeypatch):
    """``ling-3.0-flash-vl-ep4-1chip.reason-saturated``'s decode program
    (7 layers, 128 of 512 experts held, 32 slots x 3088 rows): three
    kernel calls an expert layer; the donated state is updated in place
    and never copied (the six float32 ``[32,32,128,128]`` KDA states,
    the latent rows ``[32,3088,512]``); no matrix exists in float32 (the tree arrives in
    the serving types: a cast of one 250 M expert stack is 1 GB); and
    arguments and temporaries stay under 13 GiB of the chip's 16."""
    from ray_tpu.models import ling

    fam, m, cfg, eng, params, state, vec = _ling_cell(topo, monkeypatch)
    slots, max_len = eng["slots"], eng["max_len"]
    compiled = de.decode_chunk.lower(
        params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=eng["chunk_tokens"]).compile()
    text = compiled.as_text()
    kda_calls = [line for line in text.splitlines()
                 if KERNEL in line and "kda_step" in line.split(" = ")[0]]
    assert len(kda_calls) == sum(
        cfg.attn_kind(i) == "kda" for i in range(cfg.n_layers)) == 6
    assert text.count(KERNEL) == 3 * cfg.moe_layers + len(kda_calls) == 24
    for dims in (f"f32[{slots},32,128,128]", f"bf16[{slots},{max_len},512]"):
        assert dims in text
        assert not re.search(re.escape(dims) + r"\S* copy\(", text), dims
    # a KDA layer's step is one call that takes its state as operand 3
    # (behind ``active``, the vectors and v) and returns it in that
    # buffer; nothing else moves a state, whole or a quarter of it (the
    # XLA body's slices between memories; and with the kernel's operand
    # left to the compiler, its own: it brought four layers' states into
    # VMEM in quarters before the call and copied them back behind it)
    for line in kda_calls:
        assert line.split(" = ")[1].startswith(
            f"(f32[{slots},32,128,128]"), line
        assert "output_to_operand_aliasing={{0}: (3, {})}" in line, line
    moves = re.compile(r"\s*%(copy|copy-start|slice-start|async-start|"
                       r"dynamic-slice-start)[.\d]* = ")
    moved = [line[:160] for line in text.splitlines() if moves.match(line)
             and re.search(rf"f32\[({slots}|{slots // 4}),32,128,128\]", line)]
    assert not moved, moved[:3]
    # (the 64-wide rotated keys, 2% of the state, change their layout
    # once a chunk on the way in and out of the step loop: XLA's choice
    # for a minor dimension of half a lane tile, outside the loop)
    assert len(re.findall(rf"bf16\[{slots},{max_len},64\]\S* copy\(",
                          text)) <= 2
    matrices = {a.shape for a in jax.tree_util.tree_leaves(params)
                if a.dtype == jnp.bfloat16 and a.size > 1 << 20}
    assert (128, 2560, 768) in matrices and (2560, 12288) in matrices
    for shape in matrices:
        assert f"f32[{','.join(map(str, shape))}]" not in text, shape
    mem = compiled.memory_analysis()
    state_bytes = sum(ling.SLOTS.state_bytes(state).values())
    assert state_bytes == slots * sum(
        fam.state_bytes_per_slot(m, max_len).values())
    assert mem.alias_size_in_bytes >= state_bytes, _mem(compiled)
    print(f"\nling decode chunk: {_mem(compiled)}")
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 13 * 1024 * MIB), _mem(compiled)


@pytest.mark.parametrize("bucket", [256, 512, 1024])
def test_ling_prefill_holds_one_kda_chunk_call_a_kda_layer(
        topo, monkeypatch, bucket):
    """``ling-3.0-flash-vl-ep4-1chip.reason-saturated``'s cold prefill
    call at each of its buckets (32 heads, 4 to 16 chunks): the
    chunkwise delta rule is one ``kda_chunk`` call a KDA layer, six a
    program, on the arrays as the projections leave them
    (``_kda_chunk_calls``); arguments and temporaries stay under 13 GiB
    of the chip's 16."""
    fam, m, cfg, eng, params, state, vec = _ling_cell(topo, monkeypatch)
    assert tuple(eng["prompt_buckets"]) == (256, 512, 1024)
    compiled = _lower_prefill(cfg, vec(jnp.int32).sharding, bucket,
                              (params, state, vec)).compile()
    # (at 256 and 512 rows XLA prefetches a layer's 4 to 8 MB ``g`` into
    # VMEM ahead of two of the calls; at 1,024 nothing moves)
    calls = _kda_chunk_calls(compiled.as_text(), prefetched_ok=bucket < 1024)
    assert len(calls) == sum(
        cfg.attn_kind(i) == "kda" for i in range(cfg.n_layers)) == 6
    assert all(f"f32[1,{bucket},4096]" in c for c in calls), calls[0][:300]
    mem = compiled.memory_analysis()
    print(f"\nling prefill 1 x {bucket}: {_mem(compiled)}")
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 13 * 1024 * MIB), _mem(compiled)


def test_lowering_lings_prefill_traces_the_kda_chunk_kernel_once(
        topo, monkeypatch):
    """What the kernel costs a process's start is its trace
    (``ops/kda_chunk.py``: a thousand lines of columns, seconds each):
    the call is jitted by itself, so lowering the 1,024-row prefill with
    its six KDA layers runs the kernel's body ONCE, not once a layer,
    and the lowered module holds one copy of the kernel that the six
    layers call. (Traced a layer, Ling's set-up read 120-160 s for the
    parent's 80-88: ``PERF.md`` §6, PRs 45-47.)"""
    from ray_tpu.ops import kda_chunk as kc

    fam, m, cfg, eng, params, state, vec = _ling_cell(topo, monkeypatch)
    traced = []
    body = kc._kernel
    monkeypatch.setattr(kc, "_kernel", lambda *a, **kw: (
        traced.append(kw), body(*a, **kw))[1])
    jax.clear_caches()  # (an earlier test's trace of this shape)
    lowered = _lower_prefill(cfg, vec(jnp.int32).sharding, 1024,
                             (params, state, vec))
    assert traced == [{"hb": 16}], len(traced)
    text = lowered.as_text()
    assert len(re.findall(r"func\.func private @_kda_chunk\w*\(", text)) == 1
    assert len(re.findall(r"call @_kda_chunk\w*\(", text)) == 6


# ---- the block with window layers beside full ones (models/exaone.py)
# at the reason-long cell's sizes ----


def _exaone_cell(topo, monkeypatch):
    """``k-exaone-236b-a23b-ep8-1chip.reason-long-saturated``'s model,
    engine shape and arguments on one described chip, the kernels asked
    for by name (the dispatches would read the CPU backend here)."""
    from benchmark import manifest
    from ray_tpu.models import exaone
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, use_kernel=True))
    with open("benchmark/traffic/reason-long-saturated.json") as f:
        eng = json.load(f)["engine"]
    fam, m = manifest.model("k-exaone-236b-a23b-ep8-1chip")
    prog = fam.build(m, max_seq_len=eng["max_len"], remat=False)
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(prog.init_params,
                                      jax.random.PRNGKey(0)))
    state = _on(chip, jax.eval_shape(lambda: exaone.SLOTS.init_state(
        prog.cfg, eng["slots"], eng["max_len"])))
    vec = lambda dt, n=eng["slots"]: jax.ShapeDtypeStruct(  # noqa: E731
        (n,), dt, sharding=chip)
    return fam, m, prog.cfg, eng, params, state, vec


def _no_f32_matrix(text: str, params, but=()) -> None:
    matrices = {a.shape for a in jax.tree_util.tree_leaves(params)
                if a.dtype == jnp.bfloat16 and a.size > 1 << 20}
    assert (16, 6144, 2048) in matrices and (6144, 10240) in matrices
    for shape in matrices - set(but):
        assert f"f32[{','.join(map(str, shape))}]" not in text, shape


def test_exaone_decode_chunk_reads_both_stacks_in_place(topo, monkeypatch):
    """The cell's decode program (5 layers, 16 of 128 experts held, 64
    slots: four rings of 128 rows and one full stack of 5,136): a step
    calls ``decode_attn`` once a layer, on the ring or on the full stack
    (8 query rows a kv head), and ``moe_gmm`` three times an expert
    layer at 6144 x 2048 (``tiling``: 512 columns a block); the donated
    stacks are updated in place, never copied or sliced by layer; no
    matrix exists in float32; arguments and temporaries stay under
    11 GiB of the chip's 16."""
    from ray_tpu.models import exaone

    fam, m, cfg, eng, params, state, vec = _exaone_cell(topo, monkeypatch)
    slots, max_len = eng["slots"], eng["max_len"]
    compiled = de.decode_chunk.lower(
        params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=eng["chunk_tokens"]).compile()
    text = compiled.as_text()
    assert text.count("decode_attn") >= cfg.n_layers
    assert text.count(KERNEL) == cfg.n_layers + 3 * cfg.moe_layers == 17
    for dims in (f"bf16[1,{slots},{max_len},1024]",
                 f"bf16[4,{slots},128,1024]"):
        assert dims in text
        assert not re.search(re.escape(dims) + r"\S* copy\(", text), dims
    # no one layer of either stack is sliced out or written back whole
    assert _whole_layer_ops(text, cfg, slots, max_len) == []
    assert _whole_layer_ops(text, cfg, slots, 128) == []
    _no_f32_matrix(text, params)
    mem = compiled.memory_analysis()
    state_bytes = sum(exaone.SLOTS.state_bytes(state).values())
    assert state_bytes == slots * sum(
        fam.state_bytes_per_slot(m, max_len).values())
    assert mem.alias_size_in_bytes >= state_bytes, _mem(compiled)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 2 * fam.num_params(m)) < 1 << 20  # (f32 norms)
    print(f"\nexaone decode chunk: {_mem(compiled)}")
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 11 * 1024 * MIB), _mem(compiled)


def test_exaone_one_row_prefill_compiles(topo, monkeypatch):
    """The cell's cold prefill call at its widest bucket: one prompt of
    1,024 rows, band-masked in the sliding layers, its last 128 rows
    gathered into the four rings and all of them into the full stack;
    the donated state is updated in place and no matrix is cast."""
    from ray_tpu.models import exaone

    fam, m, cfg, eng, params, state, vec = _exaone_cell(topo, monkeypatch)
    prompt = jax.ShapeDtypeStruct((1, 1024), jnp.int32,
                                  sharding=vec(jnp.int32).sharding)
    compiled = de._prefill_batch_into_slots.lower(
        params, prompt, vec(jnp.int32, 1), vec(jnp.int32, 1),
        vec(jnp.uint32, 1), vec(jnp.float32, 1), vec(jnp.float32, 1),
        state, vec(jnp.int32), cfg=cfg).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 3 * cfg.moe_layers and "moe_gmm" in text
    # (the one row's logits are a fused multiply and reduce over the
    # head, which converts it on the fly inside the fusion: no copy)
    _no_f32_matrix(text, params, but=[(6144, 19200)])
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        exaone.SLOTS.state_bytes(state).values()), _mem(compiled)
    print(f"\nexaone prefill 1 x 1024: {_mem(compiled)}")
    assert mem.temp_size_in_bytes < 2 * 1024 * MIB, _mem(compiled)


# ---- the block with gated latent attention in every layer
# (models/instella.py) at the longdoc cell's sizes ----


def _instella_cell(topo, monkeypatch):
    """``instella-moe-16b-a3b-pp4-1chip.longdoc-saturated``'s model,
    engine shape and arguments on one described chip, the kernels asked
    for by name (the dispatches would read the CPU backend here)."""
    import dataclasses

    from benchmark import manifest
    from ray_tpu.models import instella
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    monkeypatch.setattr(da, "decode_attention_latent", functools.partial(
        da.decode_attention_latent, use_kernel=True))
    with open("benchmark/traffic/longdoc-saturated.json") as f:
        eng = json.load(f)["engine"]
    fam, m = manifest.model("instella-moe-16b-a3b-pp4-1chip")
    prog = fam.build(m, max_seq_len=eng["max_len"], remat=False)
    cfg = dataclasses.replace(prog.cfg, use_flash=True)
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(prog.init_params,
                                      jax.random.PRNGKey(0)))
    state = _on(chip, jax.eval_shape(lambda: instella.SLOTS.init_state(
        cfg, eng["slots"], eng["max_len"])))
    vec = lambda dt, n=eng["slots"]: jax.ShapeDtypeStruct(  # noqa: E731
        (n,), dt, sharding=chip)
    return fam, m, cfg, eng, params, state, vec


def test_instella_decode_chunk_reads_the_latent_stack_in_place(
        topo, monkeypatch):
    """The cell's decode program (7 layers, all 64 experts, 32 slots of
    16,912 rows of 640): a step calls ``decode_attn_latent`` once a
    layer on the one stack and ``moe_gmm`` three times an expert layer;
    the donated stack is updated in place, never copied nor sliced by
    layer; no matrix exists in float32; arguments and temporaries stay
    under 13 GiB of the chip's 16."""
    from ray_tpu.models import instella

    fam, m, cfg, eng, params, state, vec = _instella_cell(topo, monkeypatch)
    slots, max_len = eng["slots"], eng["max_len"]
    compiled = de.decode_chunk.lower(
        params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=eng["chunk_tokens"]).compile()
    text = compiled.as_text()
    assert text.count("decode_attn_latent") >= cfg.n_layers
    assert "decode_attn." not in text.replace("decode_attn_latent", "")
    assert text.count(KERNEL) == cfg.n_layers + 3 * cfg.moe_layers == 25
    dims = f"bf16[7,{slots},{max_len},640]"
    assert dims in text
    assert not re.search(re.escape(dims) + r"\S* copy\(", text)
    # no one layer of the stack is sliced out or written back whole
    assert f"bf16[1,{slots},{max_len},640]" not in text
    assert f"bf16[{slots},{max_len},640]" not in text
    for shape in {a.shape for a in jax.tree_util.tree_leaves(params)
                  if a.dtype == jnp.bfloat16 and a.size > 1 << 20}:
        assert f"f32[{','.join(map(str, shape))}]" not in text, shape
    mem = compiled.memory_analysis()
    state_bytes = instella.SLOTS.state_bytes(state)["latent"]
    assert state_bytes == slots * fam.state_bytes_per_slot(
        m, max_len)["latent"]
    assert mem.alias_size_in_bytes >= state_bytes, _mem(compiled)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 2 * fam.num_params(m)) < 1 << 20  # (f32 norms)
    print(f"\ninstella decode chunk: {_mem(compiled)}")
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 13 * 1024 * MIB), _mem(compiled)


def test_instella_16384_row_prefill_forms_no_scores(topo, monkeypatch):
    """The cell's cold prefill call at its widest bucket, one prompt of
    16,384 rows: ``flash_fwd`` once a layer on 16 / 16 heads of 128 (k =
    nope ‖ the shared rotated key) and ``moe_gmm`` three times an expert
    layer; NO array of ``16384 x 16384`` exists (the scores would be 17
    GB in float32); the donated stack is updated in place; arguments and
    temporaries fit the chip's 16 GiB with room for the reference's
    probe."""
    from ray_tpu.models import instella

    fam, m, cfg, eng, params, state, vec = _instella_cell(topo, monkeypatch)
    prompt = jax.ShapeDtypeStruct((1, 16384), jnp.int32,
                                  sharding=vec(jnp.int32).sharding)
    compiled = de._prefill_batch_into_slots.lower(
        params, prompt, vec(jnp.int32, 1), vec(jnp.int32, 1),
        vec(jnp.uint32, 1), vec(jnp.float32, 1), vec(jnp.float32, 1),
        state, vec(jnp.int32), cfg=cfg).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == cfg.n_layers + 3 * cfg.moe_layers
    assert text.count("flash_fwd") >= cfg.n_layers and "moe_gmm" in text
    assert "16384,16384" not in text
    assert "bf16[1,16384,16,128]" in text or "bf16[1,16,16384,128]" in text
    # (no [P, vocabulary] logits either: the head sees the last real row)
    assert "16384,128896" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= instella.SLOTS.state_bytes(
        state)["latent"], _mem(compiled)
    print(f"\ninstella prefill 1 x 16384: {_mem(compiled)}")
    assert mem.temp_size_in_bytes < 2304 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 14.5 * 1024 * MIB), _mem(compiled)


# ---- the hybrid block without positions (models/solar.py) at the
# longreason cell's sizes ----


def _solar_cell(topo, monkeypatch):
    """``solar-open2-250b-ep8-1chip.longreason-saturated``'s model,
    engine shape and arguments on one described chip, the kernels asked
    for by name (the dispatches would read the CPU backend here)."""
    import dataclasses

    from benchmark import manifest
    from ray_tpu.models import solar
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, use_kernel=True))
    monkeypatch.setattr(solar, "_kda_step", functools.partial(
        solar._kda_step, use_kernel=True))
    monkeypatch.setattr(solar, "_kda_chunk", functools.partial(
        solar._kda_chunk, use_kernel=True))
    with open("benchmark/traffic/longreason-saturated.json") as f:
        eng = json.load(f)["engine"]
    fam, m = manifest.model("solar-open2-250b-ep8-1chip")
    prog = fam.build(m, max_seq_len=eng["max_len"], remat=False)
    cfg = dataclasses.replace(prog.cfg, use_flash=True)
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(prog.init_params,
                                      jax.random.PRNGKey(0)))
    state = _on(chip, jax.eval_shape(lambda: solar.SLOTS.init_state(
        cfg, eng["slots"], eng["max_len"])))
    vec = lambda dt, n=eng["slots"]: jax.ShapeDtypeStruct(  # noqa: E731
        (n,), dt, sharding=chip)
    return fam, m, cfg, eng, params, state, vec


def test_solar_decode_chunk_keeps_both_kinds_of_state_where_they_lie(
        topo, monkeypatch):
    """The cell's decode program (4 layers, 40 of 320 experts held, 32
    slots of 34,832 rows): a step calls ``kda_step`` once a KDA layer at
    64 heads (``S`` operand 3, returned in its buffer, nothing else
    moves a state: the call carries no ``cost_estimate``, as Ling's),
    ``decode_attn`` once on the GQA layer's stack in place (23 MiB of
    temporaries beside a k stack of 2.28 GB), and
    ``moe_gmm`` three times a layer; no matrix exists in float32;
    arguments and temporaries stay under 13 GiB of the chip's 16."""
    from ray_tpu.models import solar

    fam, m, cfg, eng, params, state, vec = _solar_cell(topo, monkeypatch)
    slots, max_len = eng["slots"], eng["max_len"]
    compiled = de.decode_chunk.lower(
        params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=eng["chunk_tokens"]).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if KERNEL in line]
    kda_calls = [c for c in calls if "kda_step" in c.split(" = ")[0]]
    assert len(kda_calls) == cfg.kda_layers == 3
    assert sum("decode_attn" in c.split(" = ")[0] for c in calls) \
        == cfg.full_layers == 1
    assert len(calls) == 3 + 1 + 3 * cfg.n_layers == 16
    s_dims = f"f32[{slots},64,128,128]"
    for line in kda_calls:
        assert line.split(" = ")[1].startswith(f"({s_dims}"), line
        assert "output_to_operand_aliasing={{0}: (3, {})}" in line, line
    moves = re.compile(r"\s*%(copy|copy-start|slice-start|async-start|"
                       r"dynamic-slice-start)[.\d]* = ")
    moved = [line[:160] for line in text.splitlines() if moves.match(line)
             and re.search(rf"f32\[({slots}|{slots // 4}),64,128,128\]",
                           line)]
    assert not moved, moved[:3]
    stack = f"bf16[1,{slots},{max_len},1024]"
    assert stack in text
    assert not re.search(re.escape(stack) + r"\S* copy\(", text)
    for shape in {a.shape for a in jax.tree_util.tree_leaves(params)
                  if a.dtype == jnp.bfloat16 and a.size > 1 << 20}:
        assert f"f32[{','.join(map(str, shape))}]" not in text, shape
    mem = compiled.memory_analysis()
    state_bytes = sum(solar.SLOTS.state_bytes(state).values())
    assert state_bytes == slots * sum(
        fam.state_bytes_per_slot(m, max_len).values())
    assert mem.alias_size_in_bytes >= state_bytes, _mem(compiled)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 2 * fam.num_params(m)) < 1 << 20  # (f32 leaves)
    print(f"\nsolar decode chunk: {_mem(compiled)}")
    # (the one-layer stack is bitcast to [slots, max_len, 1024] and
    # scattered into in place: no temporary is the size of a layer's k)
    assert mem.temp_size_in_bytes < 256 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 13 * 1024 * MIB), _mem(compiled)


def test_solar_32768_row_prefill_runs_its_tokenwise_work_in_segments(
        topo, monkeypatch):
    """The cell's cold prefill call at its widest bucket, one prompt of
    32,768 rows in 16 segments of 2,048: ``flash_fwd`` once on 64 / 8
    heads of 128 and ``moe_gmm`` three times a layer; NO float32 array
    of ``[32768, 64, 128]`` (q, k, v or g of a KDA layer whole would be
    1.07 GB each), no gather of ``32768 x 8`` assignment rows, no
    ``32768 x 32768`` scores, no ``[P, vocabulary]`` logits exist; the
    donated state is updated in place; beside 32 slots the call fits
    the chip's 16 GiB (temporaries 3,213 MiB: the stream in and out of
    a layer and the GQA layer's q and o in both layouts, 537 MB each);
    the chunkwise delta rule is ONE ``kda_chunk`` call a KDA layer
    inside its segment scan (``_kda_chunk_calls``)."""
    from ray_tpu.models import solar

    fam, m, cfg, eng, params, state, vec = _solar_cell(topo, monkeypatch)
    assert eng["prompt_buckets"][-1] == 32768
    assert solar.SLOTS.prefill_segments(cfg, 32768) == 16
    compiled = _lower_prefill(cfg, vec(jnp.int32).sharding, 32768,
                              (params, state, vec)).compile()
    text = compiled.as_text()
    assert text.count("flash_fwd") >= cfg.full_layers and "moe_gmm" in text
    # the chunkwise delta rule: one kernel call a KDA layer, in the scan
    calls = _kda_chunk_calls(text)
    assert len(calls) == cfg.kda_layers == 3
    assert all("/while/body/" in c and "f32[1,2048,8192]" in c
               for c in calls), calls[0][:300]
    arrays = {(dt, tuple(int(d) for d in dims.split(",")))
              for dt, dims in re.findall(r"\b(f32|bf16|s32)\[([\d,]+)\]",
                                         text)}
    whole = [(dt, dims) for dt, dims in arrays if dt == "f32"
             and np.prod(dims) >= 32768 * 64 * 128]
    assert not whole, whole[:4]
    assert not [d for _, d in arrays if 32768 * 8 in d], "a whole gather"
    assert "32768,32768" not in text and "32768,24576" not in text
    assert ("bf16", (1, 32768, 64, 128)) in arrays \
        or ("bf16", (1, 64, 32768, 128)) in arrays  # (flash's q, whole)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        solar.SLOTS.state_bytes(state).values()), _mem(compiled)
    print(f"\nsolar prefill 1 x 32768: {_mem(compiled)} (temporaries "
          "with the XLA body, PR 42: 3,213 MiB)")
    assert mem.temp_size_in_bytes < 3584 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 14.5 * 1024 * MIB), _mem(compiled)


# ---- the train step, on one chip and sharded over four ----


def _train_step(topo, cfg, mesh_cfg: MeshConfig, batch=2, seq=2048):
    """chip_smoke.py's train step, compiled for a mesh over
    the first ``mesh_cfg.size`` described chips. `init_train_state`
    would place real arrays; `train_state_shardings` gives the same
    shardings with shapes only."""
    devices = np.asarray(topo.devices[:mesh_cfg.size])
    mesh = Mesh(devices.reshape(mesh_cfg.shape), AXES)
    opt = fused_adamw(1e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16,
                      nu_dtype=jnp.bfloat16)
    _, abstract, state_sh = train_state_shardings(
        lambda k: llama.init_params(cfg, k), llama.param_logical_axes(cfg),
        opt, mesh)
    step = make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg), opt, mesh, state_sh,
        compute_grad_norm=False, grads_dtype=jnp.bfloat16)
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                               sharding=batch_sharding(mesh))
    with use_mesh(mesh):
        return step.lower(_on(state_sh, abstract),
                          {"inputs": tok, "targets": tok}).compile()


def test_sharded_train_step_compiles_with_kernel(topo):
    """2 layers at 1B widths on fsdp=2 x tp=2: before the shard_map in
    ops/attention.py this failed with 'Mosaic kernels cannot be
    automatically partitioned'."""
    text = _train_step(topo, _train_cfg(n_layers=2),
                       MeshConfig(fsdp=2, tp=2)).as_text()
    assert KERNEL in text
    assert "all-reduce" in text and "all-gather" in text


def _in_flight(text: str, shape: str) -> dict:
    """-> {collective-permute-start of ``shape``: the scheduled lines
    between it and its ``-done``} (a compiled module's text is in
    schedule order)."""
    lines = text.splitlines()
    out = {}
    for i, line in enumerate(lines):
        m = re.match(r"\s*(%[\w.\-]+) = \(" + re.escape(shape)
                     + r".* collective-permute-start\(", line)
        if m:
            done = next(j for j in range(i + 1, len(lines))
                        if f"collective-permute-done({m.group(1)})"
                        in lines[j])
            out[m.group(1)] = lines[i + 1:done]
    return out


def test_sharded_train_step_hides_its_tp_transfers(topo):
    """2 layers at the four-chip cell's widths, batch and mesh
    (``internlm2-1.8b.pretrain-4k-fsdp2tp2``): the residual stream's
    all-reduces over the tp pair ([3, 4096, 2048], five a layer over
    forward, recompute and backward, each synchronous) are gone from the
    layer loops; in their place asynchronous transfers of half the rows,
    products scheduled between their start and their done
    (``parallel/tp_products.py``). What is left of that shape is the
    head's input gradient, once a step. Says the mechanism engaged;
    only the chip says how much of a transfer its product hides."""
    text = _train_step(
        topo, _train_cfg(seq=4096, n_layers=2, d_ff=8192, vocab_size=92544,
                         rope_theta=1e6),
        MeshConfig(fsdp=2, tp=2), batch=6, seq=4096).as_text()
    assert KERNEL in text
    whole = [ln for ln in text.splitlines()
             if re.search(r"= bf16\[3,4096,2048\]\S* all-reduce\(", ln)]
    assert all("lm_head" in ln for ln in whole) and len(whole) <= 1, whole
    flights = _in_flight(text, "bf16[3,2048,2048]")
    # a layer: 4 forward, 3 in the recompute (w_down's sum is not needed
    # again), 4 backward
    assert len(flights) == 11, list(flights)
    covered = [name for name, between in flights.items()
               if any("dot_general" in ln and " fusion(" in ln
                      for ln in between)]
    assert len(covered) >= 8, (covered, list(flights))


def test_sharded_flash_refuses_what_it_cannot_split(topo):
    from ray_tpu.ops.attention import attention

    q = jnp.zeros((2, 128, 4, 128), jnp.bfloat16)
    kv = jnp.zeros((2, 128, 1, 128), jnp.bfloat16)
    devs = np.asarray(topo.devices)
    with use_mesh(Mesh(devs.reshape(MeshConfig(fsdp=2, tp=2).shape), AXES)):
        with pytest.raises(ValueError, match="tp has to divide n_kv_heads"):
            jax.eval_shape(
                lambda: attention(q, kv, kv, use_flash=True))
    with use_mesh(Mesh(devs.reshape(MeshConfig(sp=2, tp=2).shape), AXES)):
        with pytest.raises(NotImplementedError, match="shards the sequence"):
            jax.eval_shape(
                lambda: attention(q, kv, kv, use_flash=True))
    # inside parallel/pipeline.py's pp stages: not brought up, said so
    cfg = _train_cfg(n_layers=2, pipeline_microbatches=2)
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    tok = jax.ShapeDtypeStruct((4, 2048), jnp.int32)
    with use_mesh(Mesh(devs.reshape(MeshConfig(pp=2, tp=2).shape), AXES)):
        with pytest.raises(NotImplementedError, match="already manual"):
            jax.eval_shape(lambda p, t: llama.forward(p, t, cfg), params, tok)


# ---- every block's programs say which part each operation came from
# (models/program_parts.py), at toy size ----

_ALWAYS = {"embed", "qkv", "cache", "attn", "attn_out", "lm_head", "sample"}
_MOE = {"moe_router", "moe_experts"}


def _toy_block(block: str):
    """-> (the block's toy configuration, the parts its serving programs
    should have). The dispatches read the CPU backend here and take the
    XLA bodies: the scopes are the same."""
    if block in ("llama", "olmoe"):
        cfg = llama.LlamaConfig(
            d_model=128, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=256,
            vocab_size=512, max_seq_len=64, remat=False,
            **({"n_experts": 4, "top_k": 2, "moe_impl": "dropless"}
               if block == "olmoe" else {}))
        return cfg, _ALWAYS | ({"mlp"} if block == "llama" else _MOE)
    if block == "ling":
        from ray_tpu.models import ling

        return ling.LingConfig.tiny(max_seq_len=64), \
            _ALWAYS | _MOE | {"mlp", "moe_shared"}
    if block == "instella":
        from ray_tpu.models import instella

        return instella.InstellaConfig.tiny(max_seq_len=64), \
            _ALWAYS | _MOE | {"mlp", "moe_shared"}
    if block == "solar":
        from ray_tpu.models import solar

        return solar.SolarConfig.tiny(max_seq_len=64), \
            _ALWAYS | _MOE | {"moe_shared"}
    from ray_tpu.models import exaone

    return exaone.ExaoneConfig.tiny(max_seq_len=64), \
        _ALWAYS | _MOE | {"mlp", "moe_shared"}


@pytest.mark.parametrize("program", ["decode_chunk", "prefill"])
@pytest.mark.parametrize("block", ["llama", "olmoe", "ling", "exaone",
                                   "instella", "solar"])
def test_every_part_of_a_block_is_in_its_programs_map(topo, block, program):
    """The map a capture is read through, from the text the TPU compiler
    leaves: every part the block should have is there, the second level
    under ``attn`` where the block has kinds of attention, and what the
    map can put nowhere stays under a tenth of the instructions."""
    from ray_tpu.models import program_parts as pp

    cfg, wanted = _toy_block(block)
    chip = SingleDeviceSharding(topo.devices[0])
    model = de.slot_model(cfg)
    key = jax.random.PRNGKey(0)
    init = (lambda: llama.init_params(cfg, key)) \
        if isinstance(cfg, llama.LlamaConfig) \
        else (lambda: sys.modules[type(cfg).__module__].init_params(cfg, key))
    params = _on(chip, jax.eval_shape(
        lambda: model.serving_params(cfg, init())))
    state = _on(chip, jax.eval_shape(
        lambda: model.init_state(cfg, 4, 64)))
    vec = lambda dt, n=4: jax.ShapeDtypeStruct(  # noqa: E731
        (n,), dt, sharding=chip)
    if program == "decode_chunk":
        lowered = de.decode_chunk.lower(
            params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
            chunk=4)
    else:
        lowered = _lower_prefill(cfg, chip, 16, (params, state, vec))
    text = lowered.compile().as_text()
    assert pp.program_name(text) == {
        "decode_chunk": "jit_decode_chunk",
        "prefill": "jit__prefill_batch_into_slots"}[program]
    parts = pp.parts_of(text)
    found = {p.removesuffix("+mixed") for p in parts.values()}
    top = {p.split("/")[0] for p in found}
    assert wanted <= top, sorted(wanted - top)
    kinds = {"ling": {"attn/attn_linear", "attn/attn_latent"},
             "exaone": {"attn/attn_window", "attn/attn_full"},
             "instella": {"attn/attn_latent"},
             "solar": {"attn/attn_linear", "attn/attn_full"}}.get(
        block, set())
    assert kinds <= found, sorted(kinds - found)
    if program == "decode_chunk":
        assert "loop" in top  # the steps' own counters at the least
    unscoped = [n for n, p in parts.items() if p.startswith("unscoped")]
    assert len(unscoped) < 0.1 * len(parts), (len(parts), unscoped)


# ---- the long programs: -m slow, run before a chip call ----


@pytest.mark.slow
@pytest.mark.parametrize("program", ["sampled", "spec", "prefill_32",
                                     "prefill_128"])
def test_serving_programs_compile_at_1b_widths(topo, program):
    cfg = _serve_cfg()
    chip = SingleDeviceSharding(topo.devices[0])
    params, cache, vec = _engine_args(cfg, chip)
    lanes = (vec(jnp.uint32), vec(jnp.float32), vec(jnp.float32))
    if program == "sampled":
        lowered = de.decode_chunk.lower(
            params, cache, vec(jnp.int32), vec(jnp.bool_), lanes,
            cfg=cfg, chunk=8)
    elif program == "spec":
        lowered = de.decode_chunk_spec.lower(
            params, None, cache, vec(jnp.int32), vec(jnp.bool_), *lanes,
            cfg=cfg, rounds=8, depth=4, draft_layers=1)
    else:
        lowered = _lower_prefill(cfg, chip, int(program.split("_")[1]))
    mem = _mem(lowered.compile())
    print(f"\n{program}: {mem}")
    assert mem["arguments_mib"] + mem["temporaries_mib"] < 15 * 1024, mem


@pytest.mark.slow
@pytest.mark.parametrize("chips", [1, 4])
def test_full_1b_train_step_compiles(topo, chips):
    """All 22 layers, b2 x T2048, flash_qkv remat, bf16 grads and
    moments: the program the smoke's train phases run. Prints
    memory_analysis() — on one chip it sits at the edge of 16 GB."""
    compiled = _train_step(
        topo, _train_cfg(),
        MeshConfig(fsdp=2, tp=2) if chips == 4 else MeshConfig())
    text = compiled.as_text()
    print(f"\n1b train step, {chips} chip(s): {_mem(compiled)} "
          f"kernel calls={text.count(KERNEL)}")
    assert KERNEL in text


# ---- as a script: the cells' programs as comparable text ----

SERVING_CELLS = (("internlm2-1.8b", "doc-saturated"),
                 ("internlm2-1.8b", "chat-steady"),
                 ("olmoe-1b-7b-0125-1chip", "doc-saturated"),
                 ("ling-3.0-flash-vl-ep4-1chip", "reason-saturated"),
                 ("k-exaone-236b-a23b-ep8-1chip", "reason-long-saturated"),
                 ("instella-moe-16b-a3b-pp4-1chip", "longdoc-saturated"),
                 ("solar-open2-250b-ep8-1chip", "longreason-saturated"))
TRAIN_CELLS = (("mistral-7b-v0.3-1chip", "pretrain-4k"),
               ("internlm2-1.8b", "pretrain-4k-fsdp2tp2"))


def _comparable(compiled) -> str:
    """``as_text()`` without what names the checkout: ``metadata={...}``,
    the header's source tables, and in a Mosaic call the module's bytes
    (they carry paths and line numbers) for a hash of its text without
    locations. Instruction suffixes ``.N`` are renamed in order of first
    appearance: numbering apart, the same program gives the same text."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def mosaic(m):
        with mlir.make_ir_context() as ctx:
            ctx.allow_unregistered_dialects = True
            asm = ir.Module.parse(base64.b64decode(m.group(1))) \
                .operation.get_asm(enable_debug_info=False)
        return '"body": "%s"' % hashlib.sha1(asm.encode()).hexdigest()

    text = re.sub(r", metadata=\{[^}]*\}", "", compiled.as_text())
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(\d+ .*\n)*", "\n", text)
    text = re.sub(r'\\?"body\\?": ?\\?"([A-Za-z0-9+/=]+)\\?"', mosaic, text)
    names: dict = {}
    return re.sub(r"\.\d+\b", lambda m: names.setdefault(
        m.group(0), f".n{len(names)}"), text)


def dump_serving_programs(out_dir: str) -> None:
    """Every program the benchmark's cells run, compiled for a described
    v5e: ``decode_chunk(lanes=None)`` and the one-row
    ``_prefill_batch_into_slots`` at every bucket, at each serving
    configuration's own fields and each engine shape of its cells
    (``benchmark/``; ``chat-bursty``'s is ``chat-steady``'s), and both
    cells' train steps on their meshes."""
    import dataclasses

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
    try:
        from ray_tpu.models import solar
        solar._kda_step = ling._kda_step
        if hasattr(ling, "_kda_chunk"):
            solar._kda_chunk = ling._kda_chunk
    except ImportError:  # (a parent before PR 42)
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

    for config, cell in SERVING_CELLS:
        if not os.path.isfile(f"benchmark/configs/{config}.json"):
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
        tr = traffic(cell)
        fam, m = manifest.model(config)
        cfg = dataclasses.replace(
            fam.build(m, max_seq_len=tr["seq"], remat=True).cfg,
            use_flash=True)
        write(f"{config}.{cell}.train_step", _train_step(
            topo, cfg, MeshConfig(**tr["mesh"]), tr["batch"], tr["seq"]))


if __name__ == "__main__":
    dump_serving_programs(sys.argv[1])
