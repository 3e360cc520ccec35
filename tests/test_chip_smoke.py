"""CPU rehearsal of chip_smoke.py's control flow.

The script's defaults are its contract (1B widths on the TPU) and its
command line has no way round them; here its phase functions run with
``Plan.tiny()`` — test-sized widths, the CPU platform — on one small
shared cluster. This finds wrong paths, arguments and control flow
before a chip call does; it says nothing about the chip. The train,
kernels and hybrid phases are tier-1; the serve phase (two pool starts,
~30 s) and the four-chip phases (~90 s) are ``-m slow`` — run them
before a chip call that changes what they drive.

A check that ``hybrid_phase`` hands a child is run once a module, in
this process (``_answer``): the test of the check and the test of the
phase read the one result. That a child starts with the platform in
its environment and answers is ``kernels_phase``'s rehearsal, whose
``chip_child`` is the script's own.
"""

import json

import pytest

import chip_smoke
import ray_tpu

TINY = chip_smoke.Plan.tiny()
CPU = {"platform": "cpu", "kind": "cpu"}


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=8)
    yield
    ray_tpu.shutdown()


_ANSWERS = {}


def _answer(call, **args):
    """``chip_smoke.<call>(**args)`` as ``chip_child`` hands it back
    (through JSON), made in this process and once a module."""
    key = call, json.dumps(args, sort_keys=True)
    if key not in _ANSWERS:
        _ANSWERS[key] = json.loads(json.dumps(
            getattr(chip_smoke, call)(**args)))
    return _ANSWERS[key]


def _hybrid_args(**more):
    return dict(widths=TINY.hybrid_widths, seed=TINY.seed, **more)


def _run(capsys, plan, phases=None):
    rc = chip_smoke.run(plan, phases)
    out = capsys.readouterr()
    # (workers' own stdout is forwarded to the driver's; main() moves
    # all of that to stderr, run() alone does not)
    return rc, [json.loads(ln) for ln in out.out.splitlines()
                if ln.startswith("{")], out.err


def _check_lines(lines, names):
    assert [ln.get("phase") for ln in lines[:-1]] == names
    for ln in lines[:-1]:
        assert ln["ok"] is True and ln["seconds"] >= 0
        assert ln["compile_seconds"] >= 0 and ln["checked"]
        assert ln["device"].items() >= CPU.items()
    # the contract's last line: these keys and nothing more
    assert set(lines[-1]) == {"ok", "device"} and lines[-1]["ok"] is True
    assert set(lines[-1]["device"]) == {"platform", "kind", "count"}
    assert lines[-1]["device"].items() >= CPU.items()


def _stub(**facts):
    return lambda plan: dict(device={**CPU, "count": 1}, compile_s=0.0,
                             **facts)


def test_run_prints_a_line_per_phase_then_the_result(capsys):
    rc, lines, _ = _run(capsys, TINY, (("a", _stub(x=1)), ("b", _stub(y=2))))
    assert rc == 0
    _check_lines(lines, ["a", "b"])


def test_a_failed_phase_fails_the_run(capsys):
    def boom(plan):
        chip_smoke.check(False, "forced failure", plan=plan.model_size)

    rc, lines, err = _run(capsys, TINY, (
        ("first", _stub(x=1)), ("second", boom), ("never", _stub(x=1))))
    assert rc != 0
    assert [ln["phase"] for ln in lines] == ["first"], \
        "no line for the failed phase, none after it, no result"
    assert "forced failure" in err and "'second' FAILED" in err


def test_phases_that_disagree_on_the_device_fail_the_run(capsys):
    other = lambda plan: dict(  # noqa: E731
        device={"platform": "cpu", "kind": "other", "count": 1},
        compile_s=0.0, x=1)
    rc, lines, err = _run(capsys, TINY, (("a", _stub(x=1)), ("b", other)))
    assert rc != 0 and "disagree on the device" in err
    assert all("phase" in ln for ln in lines)


def test_no_chip_means_no_result(capsys, monkeypatch):
    """As the driver first runs it: in a sandbox without an accelerator
    the script exits non-zero and prints no result."""
    monkeypatch.delenv("RAY_TPU_CHIPS", raising=False)
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "found 0 device node(s)" in out.err


def test_the_store_is_sized_for_the_published_weights():
    # 1,168,199,680 f32 parameters in one object, plus headroom
    assert chip_smoke.store_bytes(chip_smoke.Plan()) > 4660 * 2**20
    assert chip_smoke.store_bytes(TINY) < 2 * 2**30


def test_train_phase_rehearses_on_the_cpu(cluster):
    facts = chip_smoke.train_phase(TINY)
    assert facts["device"].items() >= CPU.items()
    assert len(facts["losses"]) == TINY.steps
    assert facts["losses"][-1] < facts["losses"][0]
    assert not facts["batch_cut_to_fit"] and not facts["kernel_in_step"]


def test_parting_margins_measure_against_the_models_own_logits():
    """What ``serve_phase`` judges a spec / plain parting by on the
    chip: where two decodes first differ, and how far under the best of
    the uncached f32 forward's logits the two tokens lie."""
    import jax
    import numpy as np

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(**chip_smoke.model_fields("tiny", 32))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [np.arange(1, 9, dtype=np.int32),
               np.arange(9, 17, dtype=np.int32)]
    a = [5, 6, 7, 8]
    lg = np.asarray(llama.forward(
        params, np.concatenate([prompts[1], a[:2]])[None], cfg)[0, -1])
    order = np.argsort(lg)
    worst, second, best = (int(order[i]) for i in (0, -2, -1))
    for other, gap in ((second, lg[best] - lg[second]),
                       (worst, lg[best] - lg[worst])):
        same, parted = chip_smoke.parting_margins(
            params, cfg, prompts, [a, [5, 6, best, 8]],
            [a, [5, 6, other, 9]])
        assert same is None
        assert parted["at"] == 2  # the first difference, not 8 != 9
        np.testing.assert_allclose(parted["margin"], gap, rtol=1e-4)
    assert gap > chip_smoke.NEAR_TIE


def test_kernel_parting_rehearses_on_the_cpu():
    """What ``serve_phase`` asks of the decode step's attention on the
    chip, at tiny widths with the kernel in the Pallas interpreter:
    both head layouts, three prompts in four slots, and in f32 the
    kernel and the XLA body part nowhere outside a near-tie."""
    found = chip_smoke.kernel_parting(
        chip_smoke.model_fields("tiny", TINY.max_len, n_layers=1,
                                remat=False, use_flash=False),
        [2, 4], TINY.seed, TINY.slots, TINY.max_len, TINY.chunk_tokens,
        list(TINY.prompt_buckets), [24, 5, 13], TINY.max_tokens,
        interpret=True)
    assert found["device"].items() >= CPU.items()
    assert set(found["layouts"]) == {"4/2", "4/4"}
    for layout in found["layouts"].values():
        assert layout["kernel_calls_traced"] > 0
        assert len(layout["partings"]) == 3
        assert all(p is None or p["margin"] < chip_smoke.NEAR_TIE
                   for p in layout["partings"])


def test_prefill_parting_rehearses_on_the_cpu():
    """What ``serve_phase`` asks of the cold prefill on the chip, at tiny
    widths with ``flash_fwd`` in the Pallas interpreter: both head
    layouts, three prompts each padded to its bucket; in f32 the kernel
    and the plain product leave the same rows and part nowhere outside
    a near-tie."""
    found = chip_smoke.prefill_parting(
        chip_smoke.model_fields("tiny", TINY.max_len, n_layers=1,
                                remat=False, use_flash=False),
        [2, 4], TINY.seed, TINY.slots, TINY.max_len,
        list(TINY.prompt_buckets), [24, 5, 13], interpret=True)
    assert found["device"].items() >= CPU.items()
    assert set(found["layouts"]) == {"4/2", "4/4"}
    for layout in found["layouts"].values():
        assert layout["kernel_calls_traced"] > 0
        assert layout["rows_max_diff"] < 1e-5
        assert len(layout["partings"]) == 3
        assert all(p is None or p["margin"] < chip_smoke.NEAR_TIE
                   for p in layout["partings"])


def test_kernels_phase_rehearses_on_the_cpu():
    facts = chip_smoke.kernels_phase(TINY)
    assert facts["device"].items() >= CPU.items()
    assert set(facts["rel_err_vs_reference"]) == {"out", "dq", "dk", "dv"}
    assert not facts["kernel_in_forward"]  # the CPU takes the reference
    # the kernel at a prefill's shapes (here the interpreter: no speed)
    assert set(facts["flash_fwd_ms_at_batch_1"]) == {"256", "512", "1024"}
    assert all(t["ms"] > 0 and t["causal_flops_ms"] > 0
               for t in facts["flash_fwd_ms_at_batch_1"].values())


def test_segment_check_rehearses_on_the_cpu(monkeypatch):
    """What ``hybrid_phase`` asks of the block without positions
    (``models/solar.py``) on the chip, at tiny widths with segments of 8
    rows: prompts of 16 and 32 tokens run their KDA layer in two and
    four segments, the state carried, and agree with stepping; the gated
    GQA layer's prompt attention agrees with its steps over the rows,
    which are the same rows."""
    from ray_tpu.models import moe

    monkeypatch.setattr(moe, "SEGMENT_ROWS", 8)
    found = chip_smoke.segment_check("tiny", [16, 32], TINY.seed)
    assert found["device"].items() >= CPU.items()
    assert found["segments"] == {"16": 2, "32": 4}
    for errs in found["rel_err"].values():
        assert set(errs) == {"kda_out", "kda_state", "kda_conv", "gqa_out",
                             "gqa_rows"}
        assert errs["kda_conv"] == 0.0 and errs["gqa_rows"] == 0.0
        assert 0 < max(errs.values()) <= chip_smoke.HYBRID_TOLERANCE


@pytest.mark.parametrize("block, groups", [("granite", 1),
                                           ("nemotron", 2)])
def test_ssm_check_rehearses_on_the_cpu(block, groups, monkeypatch):
    """What ``hybrid_phase`` asks of the blocks of state-space layers
    (``models/granite.py``, one group; ``models/nemotron.py``, B and C
    in groups) on the chip, at tiny widths with segments of
    8 rows and the step kernel in the Pallas interpreter: prompts of 16
    and 32 tokens run their Mamba layer in two and four segments, the
    state carried, and agree with stepping; the kernel agrees with the
    XLA body and hands an inactive slot's state back bit for bit."""
    from ray_tpu.models import moe

    monkeypatch.setattr(moe, "SEGMENT_ROWS", 8)
    found = chip_smoke.ssm_check("tiny", [16, 32], TINY.seed,
                                 interpret=True, block=block)
    assert found["device"].items() >= CPU.items()
    assert found["segments"] == {"16": 2, "32": 4}
    assert found["groups"] == groups
    for errs in found["rel_err"].values():
        assert set(errs) == {"out", "state", "conv"}
        assert errs["conv"] == 0.0
        assert 0 < max(errs.values()) <= chip_smoke.HYBRID_TOLERANCE
    assert max(found["kernel"].values()) <= chip_smoke.SSD_KERNEL_TOLERANCE
    assert found["inactive_kept"] and found["in_program"] is False


def test_sconv_check_rehearses_on_the_cpu(monkeypatch):
    """What ``hybrid_phase`` asks of the block of gated short
    convolutions (``models/lfm2.py``) on the chip, at tiny widths with
    segments of 8 rows and the decode kernel in the Pallas interpreter:
    prompts of 16 and 32 tokens run their conv layer in two and four
    segments, the two rows of ``u`` carried, and agree with stepping;
    the GQA layer's segments at an offset agree with its decode steps;
    the kernel, eight heads of 16 a lane tile here, agrees with the XLA
    body and leaves an inactive slot's output zeros."""
    from ray_tpu.models import moe

    monkeypatch.setattr(moe, "SEGMENT_ROWS", 8)
    found = chip_smoke.sconv_check("tiny", [16, 32], TINY.seed,
                                   interpret=True)
    assert found["device"].items() >= CPU.items()
    assert found["segments"] == {"16": 2, "32": 4}
    for errs in found["rel_err"].values():
        assert set(errs) == {"conv_out", "conv_rows", "gqa_out", "gqa_rows"}
        assert errs["conv_rows"] == 0.0 and errs["gqa_rows"] == 0.0
        assert 0 < max(errs.values()) <= chip_smoke.HYBRID_TOLERANCE
    assert 0 < found["kernel"] <= chip_smoke.HYBRID_TOLERANCE
    assert found["inactive_zero"] and found["in_program"] is False
    assert found["heads_a_tile"] == 1  # (two kv heads of 16: no tile)


@pytest.mark.parametrize("block, chosen", [("dots", 16), ("glm_dsa", 8),
                                           ("glm_next", 2)])
def test_dsa_check_rehearses_on_the_cpu(block, chosen):
    """What ``hybrid_phase`` asks of the kernels of ``ops/dsa.py`` on the
    chip at the eighth block's widths, at the ninth's (heads of 24 +
    8 beside values of 32 at tiny widths) and at the tenth's (heads of 24
    + 0: no rotated part; keys pooled four rows a block, 2 whole blocks
    chosen a row and the open block read; the streams' mixes against a
    ``jax.numpy`` body), in the Pallas interpreter:
    128 query rows over 256 keys, ``index_topk`` chosen a row; each
    kernel agrees with its XLA body, the selection's kernel chooses the
    counting passes' sets, an inactive slot's step gives zeros."""
    found = _answer("dsa_check", **_hybrid_args(
        rows=TINY.dsa_rows, interpret=True, block=block))
    assert TINY.dsa_rows == 128
    assert found["device"].items() >= CPU.items()
    assert set(found["rel_err"]) == {"dsa_index", "dsa_attn",
                                     "dsa_decode_attn"} | (
        {"mhc"} if block == "glm_next" else set())
    assert max(found["rel_err"].values()) <= chip_smoke.DSA_KERNEL_TOLERANCE
    assert found["sets_equal"] and found["inactive_zero"]
    assert found["chosen"] == 128 * chosen


def test_ring_check_rehearses_on_the_cpu():
    """What ``hybrid_phase`` asks of a sliding-window layer's ring on the
    chip, at tiny widths (a window of 8) with the kernel in the Pallas
    interpreter: 20 positions, two wraps, four slots at different
    positions and one inactive; the kernel on the ring and the XLA body
    agree at every step."""
    found = _answer("ring_check", **_hybrid_args(
        steps=TINY.ring_steps, interpret=True))
    assert found["device"].items() >= CPU.items()
    assert (found["window"], found["wraps"]) == (8, 2)
    assert 0 <= found["rel_err"] <= chip_smoke.HYBRID_TOLERANCE


def test_kda_kernel_check_rehearses_on_the_cpu():
    """What ``hybrid_phase`` asks of the KDA decode step's kernel on the
    chip, at tiny widths with the kernel in the Pallas interpreter: five
    tokens over six slots, two of them inactive; and with nobody asking
    for the kernel the same check compares the XLA body with itself."""
    found = _answer("kda_kernel_check", **_hybrid_args(
        steps=TINY.kda_steps, interpret=True))
    assert found["device"].items() >= CPU.items()
    assert 0 <= max(found["rel_err"].values()) \
        <= chip_smoke.KDA_KERNEL_TOLERANCE
    assert found["inactive_kept"] and found["moved"] > 0.1
    assert found["in_program"] is False
    same = chip_smoke.kda_kernel_check("tiny", 2, TINY.seed)
    assert same["rel_err"] == {"state": 0.0, "out": 0.0}


def test_kda_chunk_check_rehearses_on_the_cpu():
    """What ``hybrid_phase`` asks of the chunkwise delta rule's kernel
    on the chip, at tiny widths (four heads, chunks of 8) with the kernel
    in the Pallas interpreter: 32 rows in two calls, S carried, against
    the XLA body and the recurrence a token at a time; and with nobody
    asking for the kernel the path IS the XLA body."""
    found = _answer("kda_chunk_check", **_hybrid_args(
        rows=TINY.kda_chunk_rows, interpret=True))
    assert found["device"].items() >= CPU.items()
    assert set(found["rel_err"]) == {"kernel_body", "kernel_recurrence",
                                     "body_recurrence"}
    for pair in found["rel_err"].values():
        assert set(pair) == {"out", "state"}
        assert 0 < max(pair.values()) <= chip_smoke.KDA_CHUNK_TOLERANCE
    assert found["in_program"] is False and found["heads"] == 4
    # (narrow heads off a TPU: what makes q, k, v and g is its XLA body)
    assert found["inputs_rel_err"] == 0.0
    assert found["inputs_in_program"] is False
    same = chip_smoke.kda_chunk_check("tiny", 16, TINY.seed)
    assert same["rel_err"]["kernel_body"] == {"out": 0.0, "state": 0.0}


def test_hybrid_check_rehearses_on_the_cpu():
    """What ``hybrid_phase`` asks first of the KDA / MLA block's two
    forms of each layer, at tiny widths in bf16: prompts of 9 and 21
    tokens cross the tiny chunk of 8. The convolution rows and the
    latent rows agree exactly; the state and the outputs within the
    phase's tolerance (a few 1e-3 on the CPU)."""
    found = _answer("hybrid_check", **_hybrid_args(
        lens=list(TINY.hybrid_lens)))
    assert found["device"].items() >= CPU.items()
    assert found["device"]["compile"]["seconds"] >= 0
    assert set(found["rel_err"]) == {"9", "21"}
    for errs in found["rel_err"].values():
        assert set(errs) == {"kda_out", "kda_state", "kda_conv", "mla_out",
                             "mla_rows"}
        assert errs["kda_conv"] == 0.0 and errs["mla_rows"] == 0.0
        assert 0 < max(errs.values()) <= chip_smoke.HYBRID_TOLERANCE


def test_latent_check_rehearses_on_the_cpu():
    """What ``hybrid_phase`` asks of the fourth block's gated MLA layer
    (``models/instella.py``), at tiny widths: the rows the two forms
    keep are the same rows, the outputs agree within the tolerance."""
    found = _answer("latent_check", **_hybrid_args(
        lens=list(TINY.latent_lens)))
    assert found["device"].items() >= CPU.items()
    assert set(found["rel_err"]) == {"9", "21"}
    for errs in found["rel_err"].values():
        assert errs["latent_rows"] == 0.0
        assert 0 < errs["latent_out"] <= chip_smoke.HYBRID_TOLERANCE


@pytest.mark.parametrize("call, more", [("segment_check", {}),
                                        ("ssm_check", {"interpret": True}),
                                        ("sconv_check", {"interpret": True})])
def test_the_phases_own_prompts_run_in_one_segment(call, more):
    """``segment_check``, ``ssm_check`` and ``sconv_check`` with the prompts the tiny
    plan gives ``hybrid_phase`` (9 and 21 tokens under segments of
    2,048 rows: one segment each; the tests above cut segments of 8)."""
    found = _answer(call, **_hybrid_args(
        lens=list(getattr(TINY, call.replace("check", "lens"))), **more))
    assert found["device"].items() >= CPU.items()
    assert found["segments"] == {"9": 1, "21": 1}
    assert 0 < max(v for errs in found["rel_err"].values()
                   for v in errs.values()) <= chip_smoke.HYBRID_TOLERANCE


def _answered_in_process(monkeypatch, spoil=None):
    """``chip_smoke.chip_child`` answered by ``_answer`` (-> the calls
    it got); ``spoil`` (a check's name -> what to lay over its answer)
    makes one child report something else."""
    calls = []

    def child(plan, call, args):
        assert plan is TINY
        calls.append(call)
        return {**_answer(call, **args), **(spoil or {}).get(call, {})}

    monkeypatch.setattr(chip_smoke, "chip_child", child)
    return calls


def test_hybrid_phase_rehearses_on_the_cpu(capsys, monkeypatch):
    """The phase asks its twelve children (the eight checks above,
    ``ssm_check`` a state-space block and ``dsa_check`` a sparse block) with the plan's own arguments and
    makes one line of their facts. Each child is answered in this
    process by the check itself, once a module (``_answer``: the tests
    above asked the same questions); what a child process adds is
    ``test_kernels_phase_rehearses_on_the_cpu``'s to show."""
    calls = _answered_in_process(monkeypatch)
    rc, lines, _ = _run(capsys, TINY, chip_smoke.ONE_CHIP[3:])
    assert rc == 0
    assert calls == [
        "hybrid_check", "ring_check", "kda_kernel_check", "kda_chunk_check",
        "latent_check", "segment_check", "ssm_check", "ssm_check",
        "sconv_check"] + 3 * ["dsa_check"]
    _check_lines(lines, ["hybrid"])
    facts = lines[0]["checked"]
    assert set(facts["rel_err"]) == {"9", "21"}
    for errs in facts["rel_err"].values():
        assert set(errs) == {"kda_out", "kda_state", "kda_conv", "mla_out",
                             "mla_rows"}
        assert errs["kda_conv"] == 0.0 and errs["mla_rows"] == 0.0
        assert max(errs.values()) <= chip_smoke.HYBRID_TOLERANCE
    # the KDA step's kernel (interpreted here) against the XLA body:
    # the same float32 lines, an inactive slot's state untouched, and no
    # kernel in the layer's own program off the TPU
    kda = facts["kda_kernel"]
    assert set(kda["rel_err"]) == {"state", "out"}
    assert max(kda["rel_err"].values()) <= chip_smoke.KDA_KERNEL_TOLERANCE
    assert kda["inactive_kept"] is True and kda["in_program"] is False
    assert kda["steps"] == TINY.kda_steps == 5
    # the chunkwise delta rule's kernel (interpreted) against the XLA
    # body and the recurrence, two calls with S carried
    chunk = facts["kda_chunk"]
    assert set(chunk["rel_err"]) == {"kernel_body", "kernel_recurrence",
                                     "body_recurrence"}
    assert max(v for pair in chunk["rel_err"].values()
               for v in pair.values()) <= chip_smoke.KDA_CHUNK_TOLERANCE
    assert chunk["in_program"] is False
    assert chunk["rows"] == TINY.kda_chunk_rows == 32
    # the fourth block's gated MLA layer: the rows the two forms keep
    # are the same rows, the outputs agree within the tolerance
    assert set(facts["latent"]) == {"9", "21"}
    for errs in facts["latent"].values():
        assert errs["latent_rows"] == 0.0
        assert 0 < errs["latent_out"] <= chip_smoke.HYBRID_TOLERANCE
    # the fifth block's two kinds of layer, each in one segment here
    assert facts["segment"]["segments"] == {"9": 1, "21": 1}
    assert max(v for errs in facts["segment"]["rel_err"].values()
               for v in errs.values()) <= chip_smoke.HYBRID_TOLERANCE
    assert facts["ssm"]["segments"] == {"9": 1, "21": 1}
    assert facts["ssm"]["inactive_kept"] and not facts["ssm"]["in_program"]
    assert facts["sconv"]["segments"] == {"9": 1, "21": 1}
    assert facts["sconv"]["kernel"] <= chip_smoke.HYBRID_TOLERANCE
    assert not facts["sconv"]["in_program"]
    assert facts["ring"]["wraps"] == 2 and facts["ring"]["window"] == 8
    assert set(facts["dsa"]) == {"dots", "glm_dsa", "glm_next"}
    assert all(found["sets_equal"] and found["rows"] == 128
               for found in facts["dsa"].values())


@pytest.mark.parametrize("call, spoil, said", [
    ("ring_check", {"rel_err": 1.0}, "sliding layer's ring"),
    ("kda_kernel_check", {"inactive_kept": False}, "kda_step kernel"),
    ("latent_check", {"device": {"platform": "tpu", "kind": "other",
                                 "count": 1}}, "latent child")])
def test_hybrid_phase_fails_when_one_child_does(call, spoil, said, capsys,
                                                monkeypatch):
    """One child's answer spoilt (an error over the tolerance, an
    inactive slot's state moved, another platform): the phase fails by
    that check's name, asks no child after it, and the run prints no
    line for the phase and no result. (The second, third and fifth
    child: a case that finds no answer made yet makes five at most.)"""
    calls = _answered_in_process(monkeypatch, {call: spoil})
    rc, lines, err = _run(capsys, TINY, chip_smoke.ONE_CHIP[3:])
    assert rc != 0 and lines == [] and calls[-1] == call
    assert said in err and "'hybrid' FAILED" in err


@pytest.mark.slow
def test_serve_phase_rehearses_on_the_cpu(cluster, capsys):
    rc, lines, _ = _run(capsys, TINY, chip_smoke.ONE_CHIP[:1])
    assert rc == 0
    _check_lines(lines, ["serve"])
    serve = lines[0]["checked"]
    assert serve["seed_replay_exact"]
    # the tiny model computes in f32: speculation changes no token
    assert set(serve["spec_on_vs_off_agreeing_tokens"].values()) == {
        TINY.max_tokens}
    assert serve["spec_on"]["spec_acceptance"]
    # each pool's capture left the map of the programs its engine ran
    assert serve["spec_on"]["capture"]["programs"].keys() == {
        "jit_decode_chunk_spec", "jit__prefill_batch_into_slots"}
    assert set(serve["spec_off"]["capture"]["programs"][
        "jit_decode_chunk"]) == {"greedy", "sampled"}


@pytest.mark.slow
def test_four_chip_phases_rehearse_on_the_cpu(cluster, capsys):
    rc, lines, _ = _run(capsys, chip_smoke.Plan.tiny(chips=4))
    assert rc == 0
    _check_lines(lines, ["train4", "pool4"])
    train4, pool4 = (ln["checked"] for ln in lines[:-1])
    assert train4["max_loss_diff"] <= train4["loss_tolerance"]
    assert len(train4["params_bytes_per_device"]) == 4
    assert pool4["four_replicas"]["replicas"] == 4
