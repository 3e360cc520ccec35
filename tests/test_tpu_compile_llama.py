"""The Llama block's kernels and its chunk program compiled for a described
v5e (``tests/_tpu_compile.py`` says how and why): the flash kernel, the
decode chunk at 1B widths and at the cells', the dropless expert layer at
OLMoE's widths, the decode attention kernel, what a serving program holds
of its weights; every block's programs against the parts map at toy
size; and, ``-m slow``, the long serving programs. Its cold prefill at
every bucket: ``tests/test_tpu_compile_llama_prefill.py``, ``_chat.py``.
"""

import functools
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from _tpu_compile import (  # noqa: F401 (topo: a fixture)
    _engine_args, INTERNLM2, KERNEL, _lower_prefill, _mem, OLMOE, _on, once,
    _serve_cfg, topo, _weight_casts, _whole_layer_ops)
from ray_tpu.models import decode_engine as de
from ray_tpu.models import llama
from ray_tpu.ops.flash_attention import flash_attention


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


# ---- the dropless expert layer (OLMoE's widths) ----

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


def _doc_chunk(topo, model):
    """The doc cell's decode program for ``model`` two layers deep (8
    slots x 1296 rows, a chunk of 16, the serving tree, both kernels
    asked for by name), compiled once for the two tests that read it.
    -> (cfg, the engine's arguments, the compiled program, its text)."""
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import grouped_matmul as gm

    def make():
        cfg = llama.LlamaConfig(**(
            INTERNLM2 if model == "internlm2" else {**OLMOE, "n_layers": 2}))
        args = _engine_args(cfg, SingleDeviceSharding(topo.devices[0]),
                            slots=8, max_len=1296)
        params, cache, vec = args
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(gm, "grouped_matmul", functools.partial(
                gm.grouped_matmul, use_kernel=True))
            patch.setattr(da, "decode_attention", functools.partial(
                da.decode_attention, use_kernel=True))
            compiled = de.decode_chunk.lower(
                params, cache, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
                chunk=16).compile()
        return cfg, args, compiled, compiled.as_text()

    return once(("doc chunk", model), make)


@pytest.mark.parametrize("model", ["internlm2", "olmoe"])
def test_decode_chunk_leaves_the_cache_where_it_lies(topo, model):
    """The doc cell's decode program (8 slots x 1296 rows, 2 layers): a
    step writes 8 rows into the stacked cache and the ``decode_attn``
    kernel reads the layer's live blocks out of the stack in place. No
    kv head is repeated for its query group (f32 ``[8,1296,8,2,128]``
    broadcasts were 1.6 s of an 8 s trace), no layer's cache is sliced
    out of the stack (``constant_dynamic-slice_fusion.17`` / ``.19``, a
    fifth of a chunk), copied or transposed, none is written back into
    the stack whole, the stack is never copied (OLMoE's ``copy.129`` /
    ``.130``), and the donated cache is updated in place."""
    cfg, _, compiled, text = _doc_chunk(topo, model)
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


@pytest.mark.parametrize("program", [
    "chunk", pytest.param("prefill", marks=pytest.mark.slow)])
@pytest.mark.parametrize("model", ["internlm2", "olmoe"])
def test_serving_programs_hold_no_cast_of_a_weight(topo, monkeypatch,
                                                   model, program):
    """The greedy chunk (``_doc_chunk``: the program of the test above,
    the cell's own with both kernels) and a one-row prefill call, handed
    the serving tree (``_engine_args``): no f32 parameter larger than a
    norm stack, no f32 array of a matrix's shape anywhere in the
    program. From the f32 masters the same chunk holds both (the casts
    were 21% of a chunk and half of a prefill call, PERF.md PR 28).
    The prefill case is ``-m slow`` (35 s a model on the driver's box):
    every tier-1 bucket of that program is held to the same lines in
    ``_tpu_compile._one_row_prefill_is_sized_by_its_bucket``."""
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, use_kernel=True))
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
    _, (params, cache, vec), _, serving = _doc_chunk(topo, model)
    masters = _on(chip, jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0))))
    from_masters = de.decode_chunk.lower(
        masters, cache, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=16).compile().as_text()
    assert _weight_casts(serving, cfg) == []
    assert len(_weight_casts(from_masters, cfg)) >= 8


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
    if block == "granite":
        from ray_tpu.models import granite

        return granite.GraniteConfig.tiny(max_seq_len=64), \
            _ALWAYS | _MOE | {"moe_shared"}
    if block == "lfm2":
        from ray_tpu.models import lfm2

        return lfm2.Lfm2Config.tiny(max_seq_len=64), _ALWAYS | _MOE | {"mlp"}
    from ray_tpu.models import exaone

    return exaone.ExaoneConfig.tiny(max_seq_len=64), \
        _ALWAYS | _MOE | {"mlp", "moe_shared"}


@pytest.mark.parametrize("program", ["decode_chunk", "prefill"])
@pytest.mark.parametrize("block", ["llama", "olmoe", "ling", "exaone",
                                   "instella", "solar", "granite", "lfm2"])
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
             "solar": {"attn/attn_linear", "attn/attn_full"},
             "granite": {"attn/attn_ssm", "attn/attn_full"},
             "lfm2": {"attn/attn_conv", "attn/attn_full"}}.get(
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
