"""``models/program_parts.py``: the map from a compiled program's text to
the model's parts, on a small recorded text (the shapes of a described
v5e's, cut to the lines that matter), and the engine's and the replica's
way to it on the CPU. None of it a measurement."""

import json
import os

import pytest

from ray_tpu.models import program_parts as pp

# One scan of two scoped blocks, a head and a sampler, as the TPU
# compiler leaves it: instruction names as a trace's events begin with
# them, the scopes in ``op_name``. (Types shortened; a layout keeps its
# parentheses, a tuple's type its own.)
HLO = '''HloModule jit_f, is_scheduled=true, entry_computation_layout={(bf16[8,64]{1,0:T(8,128)(2,1)})->s32[8]{0}}

%fused_computation.1 (param_0.1: bf16[8,64]) -> bf16[8,64] {
  %param_0.1 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %div.1 = bf16[8,64]{1,0:T(8,128)(2,1)} divide(%param_0.1, %param_0.1), metadata={op_name="jit(f)/while/body/closed_call/attn/div" stack_frame_id=3}
}

%fused_computation.2 (param_0.2: bf16[8,64]) -> bf16[8,64] {
  %param_0.2 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(0)
  %convolution.18 = bf16[8,64]{1,0:T(8,128)(2,1)} convolution(%param_0.2, %param_0.2), dim_labels=bf_io->bf, metadata={op_name="jit(f)/while/body/closed_call/mlp/dot_general"}
  ROOT %mul.7 = bf16[8,64]{1,0:T(8,128)(2,1)} multiply(%convolution.18, %param_0.2), metadata={op_name="jit(f)/while/body/closed_call/mlp/mul"}
}

%fused_computation.3 (param_0.3: bf16[8,64]) -> bf16[8,64] {
  %param_0.3 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(0)
  %convolution.19 = bf16[8,64]{1,0:T(8,128)(2,1)} convolution(%param_0.3, %param_0.3), dim_labels=bf_io->bf, metadata={op_name="jit(f)/while/body/closed_call/attn_out/dot_general"}
  %add.4 = bf16[8,64]{1,0:T(8,128)(2,1)} add(%convolution.19, %param_0.3), metadata={op_name="jit(f)/while/body/closed_call/mlp/add"}
  ROOT %mul.8 = bf16[8,64]{1,0:T(8,128)(2,1)} multiply(%add.4, %add.4), metadata={op_name="jit(f)/while/body/closed_call/mlp/square"}
}

%region_0.1 (a.1: f32[], b.1: f32[]) -> f32[] {
  %a.1 = f32[] parameter(0)
  %b.1 = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a.1, %b.1), metadata={op_name="jit(f)/lm_head/reduce_sum"}
}

%body.1 (arg_tuple.1: (s32[], bf16[8,64], bf16[4,64,64])) -> (s32[], bf16[8,64], bf16[4,64,64]) {
  %arg_tuple.1 = (s32[]{:T(128)}, bf16[8,64]{1,0:T(8,128)(2,1)}, bf16[4,64,64]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %get-tuple-element.1 = s32[]{:T(128)} get-tuple-element(%arg_tuple.1), index=0
  %get-tuple-element.2 = bf16[8,64]{1,0:T(8,128)(2,1)} get-tuple-element(%arg_tuple.1), index=1
  %get-tuple-element.3 = bf16[4,64,64]{2,1,0:T(8,128)(2,1)} get-tuple-element(%arg_tuple.1), index=2
  %dynamic-slice.3 = bf16[1,64,64]{2,1,0:T(8,128)(2,1)} dynamic-slice(%get-tuple-element.3, %get-tuple-element.1), dynamic_slice_sizes={1,64,64}, metadata={op_name="jit(f)/while/body/squeeze"}
  %copy.20 = bf16[8,64]{0,1:T(8,128)(2,1)} copy(%get-tuple-element.2)
  %bitcast.5 = bf16[8,64]{1,0:T(8,128)(2,1)} bitcast(%copy.20)
  %fusion.53 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(%bitcast.5), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/closed_call/attn/div" stack_frame_id=3}
  %decode_attn.12 = bf16[8,64]{1,0:T(8,128)(2,1)} custom-call(%fusion.53, %dynamic-slice.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/while/body/closed_call/attn/attn_window/decode_attn"}, backend_config={"custom_call_config": {"body": "abc("}}
  %fusion.38 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(%decode_attn.12), kind=kOutput, calls=%fused_computation.2
  %fusion.40 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(%fusion.38), kind=kOutput, calls=%fused_computation.3
  %convolution.30 = bf16[8,64]{1,0:T(8,128)(2,1)} convolution(%fusion.40, %fusion.40), dim_labels=bf_io->bf, metadata={op_name="jit(step)/jit(main)/transpose(jvp(while))/body/transpose(jvp(mlp))/dot_general"}
  %add.30 = bf16[8,64]{1,0:T(8,128)(2,1)} add(%convolution.30, %fusion.40), metadata={op_name="jit(f)/while/body/frobnicate/add"}
  %copy.21 = bf16[8,64]{1,0:T(8,128)(2,1)} copy(%add.30)
  %add.31 = s32[]{:T(128)} add(%get-tuple-element.1, %get-tuple-element.1), metadata={op_name="jit(f)/while/body/add"}
  ROOT %tuple.2 = (s32[]{:T(128)}, bf16[8,64]{1,0:T(8,128)(2,1)}, bf16[4,64,64]{2,1,0:T(8,128)(2,1)}) tuple(%add.31, %copy.21, %get-tuple-element.3)
}

%cond.1 (arg_tuple.2: (s32[], bf16[8,64], bf16[4,64,64])) -> pred[] {
  %arg_tuple.2 = (s32[]{:T(128)}, bf16[8,64]{1,0:T(8,128)(2,1)}, bf16[4,64,64]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %get-tuple-element.4 = s32[]{:T(128)} get-tuple-element(%arg_tuple.2), index=0
  %constant.4 = s32[]{:T(128)} constant(4)
  ROOT %lt.1 = pred[]{:T(512)} compare(%get-tuple-element.4, %constant.4), direction=LT, metadata={op_name="jit(f)/while/cond/lt"}
}

ENTRY %main.1 (x.1: bf16[8,64], w.1: bf16[4,64,64]) -> s32[8] {
  %x.1 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="x"}
  %w.1 = bf16[4,64,64]{2,1,0:T(8,128)(2,1)} parameter(1), metadata={op_name="w"}
  %constant.1 = s32[]{:T(128)} constant(0)
  %copy-start.1 = (bf16[4,64,64]{2,1,0:T(8,128)(2,1)S(1)}, bf16[4,64,64]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%w.1)
  %copy-done.1 = bf16[4,64,64]{2,1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.1)
  %tuple.1 = (s32[]{:T(128)}, bf16[8,64]{1,0:T(8,128)(2,1)}, bf16[4,64,64]{2,1,0:T(8,128)(2,1)}) tuple(%constant.1, %x.1, %copy-done.1)
  %while.1 = (s32[]{:T(128)}, bf16[8,64]{1,0:T(8,128)(2,1)}, bf16[4,64,64]{2,1,0:T(8,128)(2,1)}) while(%tuple.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(f)/while"}
  %get-tuple-element.5 = bf16[8,64]{1,0:T(8,128)(2,1)} get-tuple-element(%while.1), index=1
  %reduce.1 = f32[8]{0:T(128)} reduce(%get-tuple-element.5, %constant.1), dimensions={1}, to_apply=%region_0.1, metadata={op_name="jit(f)/lm_head/reduce_sum"}
  %copy.22 = f32[8]{0:T(128)S(1)} copy(%reduce.1)
  %mul.30 = f32[8]{0:T(128)} multiply(%x.1, %x.1), metadata={op_name="jit(f)/mul"}
  ROOT %argmax.1 = s32[8]{0:T(128)} fusion(%copy.22, %mul.30), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/sample/argmax"}
}
'''


@pytest.fixture(scope="module")
def parts():
    return pp.parts_of(HLO)


@pytest.mark.parametrize("instruction, part", [
    # a fusion that says where it came from
    ("fusion.53", "attn"),
    # one with no op_name: what the computation it calls names
    ("fusion.38", "mlp"),
    # ... and one whose computation names two parts: the majority, marked
    ("fusion.40", "mlp+mixed"),
    # a kernel under the second level of attn
    ("decode_attn.12", "attn/attn_window"),
    # the scan's own slicing, its counter, its condition
    ("dynamic-slice.3", "loop"), ("add.31", "loop"), ("lt.1", "loop"),
    ("while.1", "loop"),
    # a backward operation lands on its forward part
    ("convolution.30", "mlp"),
    # a scope outside the vocabulary, inside a loop and outside one
    ("add.30", "loop"),
    ("mul.30", "unscoped:jit(f)/mul"),
    # what the compiler made, with no op_name: charged to its user ...
    ("copy.20", "attn"), ("copy.22", "sample"),
    # ... to its operand where the loop carries it on ...
    ("copy.21", "loop"),
    # ... and through a tuple into the loop
    ("copy-start.1", "loop"), ("copy-done.1", "loop"),
    ("reduce.1", "lm_head"), ("argmax.1", "sample"),
])
def test_parts_of_a_recorded_text(parts, instruction, part):
    assert parts[instruction] == part


def test_parts_of_leaves_out_what_a_trace_cannot_show(parts):
    # fused and reducer computations' instructions, parameters,
    # constants, tuples, their elements, bitcasts
    assert set(parts) == {
        "fusion.53", "fusion.38", "fusion.40", "decode_attn.12",
        "dynamic-slice.3", "add.31", "lt.1", "while.1", "convolution.30",
        "add.30", "mul.30", "copy.20", "copy.21", "copy.22",
        "copy-start.1", "copy-done.1", "reduce.1", "argmax.1"}


@pytest.mark.parametrize("op_name, part", [
    ("jit(f)/while/body/closed_call/attn/div", "attn"),
    ("jit(f)/attn/attn_full/dot_general", "attn/attn_full"),
    ("jit(f)/transpose(jvp(attn/attn_latent))/mul", "attn/attn_latent"),
    # the OUTERMOST name decides: a sampler's argmax inside the head
    ("jit(f)/lm_head/sample/argmax", "lm_head"),
    ("jit(f)/checkpoint/rematted_computation/qkv/mul", "qkv"),
    ("jit(step)/optimizer/jit(_cast)/convert_element_type", "optimizer"),
    # a jitted function called like a part is no scope
    ("jit(loss)/add", "unscoped"), ("jit(f)/jit(sample)/add", "unscoped"),
    ("jit(f)/while/body/squeeze", "loop"),
    ("jit(f)/transpose(jvp(while))/body/add", "loop"),
    ("", "unscoped"),
])
def test_part_of_an_op_name(op_name, part):
    assert pp.part_of(op_name) == part


def test_program_name():
    assert pp.program_name(HLO) == "jit_f"


# ---- the engine's and the replica's way to the map, on the CPU ----


@pytest.fixture(scope="module")
def server():
    from ray_tpu.serve.llm import LLMServer

    srv = LLMServer("tiny", slots=4, max_len=64, chunk_tokens=4,
                    prompt_buckets=(8, 16), vocab_size=128)
    srv.generate([1, 2, 3, 4, 5], 6)  # bucket 8 and the chunk have run
    yield srv
    srv.shutdown(1.0)


def _compiles(monkeypatch):
    """Counts the backend compiles from here on."""
    from jax._src import compiler

    count = []
    real = compiler.backend_compile_and_load

    def counting(*a, **kw):
        count.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(compiler, "backend_compile_and_load", counting)
    return count


def test_the_engine_maps_the_programs_it_ran_and_compiles_nothing(
        server, monkeypatch):
    count = _compiles(monkeypatch)
    programs = server.engine.program_parts()
    assert not count
    import jax

    jax.jit(lambda x: x * 3 + 1)(1.0)
    assert count  # (the counter does count a compile)
    # bucket 16 has not run: left out, not compiled
    assert {name: [v["what"] for v in variants]
            for name, variants in programs.items()} == {
        "jit_decode_chunk": ["greedy"],
        "jit__prefill_batch_into_slots": ["cold, bucket 8"]}
    chunk = set(programs["jit_decode_chunk"][0]["parts"].values())
    assert {"embed", "qkv", "cache", "attn", "attn_out", "mlp", "lm_head",
            "sample", "loop"} <= {p.split("+")[0] for p in chunk}


def test_a_capture_writes_the_map_beside_it_and_holds_nothing_after(
        server, tmp_path, monkeypatch):
    before = dict(vars(server)), dict(vars(server.engine))
    count = _compiles(monkeypatch)
    server.start_trace(str(tmp_path))
    server.generate([1, 2, 3], 6)
    server.stop_trace()
    assert not count
    with open(tmp_path / pp.FILE) as f:
        doc = json.load(f)
    assert doc["engine"] == server.engine.name
    assert doc["vocabulary"] == list(pp.VOCABULARY)
    assert set(doc["programs"]) == {"jit_decode_chunk",
                                    "jit__prefill_batch_into_slots"}
    assert os.listdir(tmp_path / "plugins" / "profile")
    # nothing is left on the replica or its engine: no new attribute
    assert vars(server).keys() == before[0].keys()
    assert vars(server.engine).keys() == before[1].keys()
    assert server._trace_dir is None
