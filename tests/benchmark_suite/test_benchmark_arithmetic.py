"""The benchmark's own arithmetic (``benchmark/``): the reduction of a
trace, percentiles and whole steps, operations and bytes against
hand-worked values, the fixed multiset of the traffic, and the loader
that finds a cell's files by name. Nothing here touches a device or
waits on a clock."""

import collections
import json
import os
import shutil

import pytest

from benchmark import manifest, model_math, stats, traffic, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "small_trace.json")) as f:
        return json.load(f)


def _fields(name):
    with open(os.path.join(manifest.HERE, "configs", name + ".json")) as f:
        return manifest.llama_fields(json.load(f))


# ------------------------------------------------------ trace reduction


def test_busy_is_the_union_of_operations_averaged_over_the_chips(trace):
    # chip 0: [0,1000) + [1200,1700) + [1800,2800) = 2500 ns (the while
    # spans its body's gap); chip 1: [100,1600) = 1500 ns; window 0..2800
    got = trace_reduce.busy(trace)
    assert got == {"busy_s": pytest.approx(2000e-9),
                   "window_s": pytest.approx(2800e-9)}


def test_a_trace_without_a_device_plane_has_nothing_to_read():
    host_only = {"planes": [{"name": "/host:CPU", "lines": []}]}
    assert trace_reduce.busy(host_only) is None
    assert trace_reduce.exposed_collective_share(host_only) is None
    assert trace_reduce.breakdown(host_only) == {
        "device_ops": [], "idle_gaps": []}


def test_program_time_per_execution_and_share(trace):
    durs = trace_reduce.program_durations(trace)
    assert sorted(durs["jit_decode_chunk"]) == pytest.approx(
        [1000e-9, 1000e-9, 1500e-9])
    assert durs["jit__prefill_batch_into_slots"] == pytest.approx([500e-9])
    # 3500 ns over two chips over a window of 2800 ns
    assert trace_reduce.program_share(trace, "jit_decode_chunk") \
        == pytest.approx(3500 / 2 / 2800)
    assert trace_reduce.program_share(trace, "jit_never_ran") is None


def test_kernel_time_counts_leaves_only(trace):
    # the while that holds fusion.1 is not counted a second time
    assert trace_reduce.op_seconds(trace, r"^fusion\.1$") \
        == pytest.approx([1300e-9, 1000e-9])
    assert trace_reduce.op_seconds(trace, r"^while") == [0.0, 0.0]


def test_exposed_collective_time(trace):
    # chip 0: all-reduce [1500,1700) alone: 200; chip 1: [1100,1400)
    # less the 100 ns fusion.3 covers: 200; both over 2800
    assert trace_reduce.exposed_collective_share(trace) \
        == pytest.approx(200 / 2800)


def test_breakdown_names_programs_and_charges_gaps_to_the_host(trace):
    got = trace_reduce.breakdown(trace)
    assert got["device_ops"][:2] == [
        ["jit_decode_chunk/fusion.1", pytest.approx(1300e-9)],
        ["jit_decode_chunk/fusion.2", pytest.approx(500e-9)]]
    assert ["jit__prefill_batch_into_slots/all-reduce.1",
            pytest.approx(200e-9)] in got["device_ops"]
    # [1000,1200) falls in the innermost host event, [1700,1800) in pump
    assert got["idle_gaps"] == [
        ["python3:$foo.py:1 wait", pytest.approx(200e-9)],
        ["python3:$decode_engine.py:909 pump", pytest.approx(100e-9)]]


def test_device_time_by_kind_of_operation(trace):
    assert trace_reduce.op_kinds(trace) == [
        ("fusion", pytest.approx(2100e-9)),
        ("all-reduce", pytest.approx(200e-9))]


def test_interval_arithmetic():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) \
        == [(0, 3), (5, 8)]
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 12)]) \
        == [(0, 2), (3, 5)]
    assert trace_reduce.op_name(
        "%fusion.3 = bf16[8,2048]{1,0} fusion(bf16[8] %p)") == "fusion.3"
    # a Pallas kernel, as the v5e compiler writes the flash forward
    assert trace_reduce.op_name(
        "%jvp__.1 = (bf16[1,32,4096,128]{3,2,1,0:T(8,128)(2,1)}, "
        "f32[1,32,4096,8]{3,2,1,0:T(8,128)}) custom-call(bf16[1,32,4096,128]"
        "{3,2,1,0} %copy_bitcast_fusion, bf16[1,8,4096,128]{3,2,1,0} %c.2), "
        'custom_call_target="tpu_custom_call"') == "custom-call/2out/jvp__.1"


# ------------------------------------------- percentiles and whole steps


@pytest.mark.parametrize("q, want", [(50, 5.5), (95, 9.55), (0, 1), (100, 10)])
def test_percentile_is_linear_between_ranks(q, want):
    assert stats.percentile(range(1, 11), q) == pytest.approx(want)


def test_a_missing_request_lands_in_the_tail():
    xs = [1.0] * 18 + [float("inf")] * 2
    assert stats.percentile(xs, 95) == float("inf")
    assert stats.percentile(xs, 50) == 1.0


def test_spread_is_the_interquartile_distance_over_the_median():
    import statistics
    xs = [100, 101, 99, 102, 98, 100]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / 100)


def test_whole_steps_only():
    # steps end at 1, 2, 3.5 and 5.2; a window of 5 s holds three
    rate, n = stats.whole_steps([11, 12, 13.5, 15.2], 10.0, 5.0, 4096)
    assert n == 3 and rate == pytest.approx(3 * 4096 / 3.5)
    with pytest.raises(ValueError):
        stats.whole_steps([20.0], 10.0, 5.0, 4096)


def test_tokens_are_counted_at_arrival_inside_the_window():
    arrivals = [(9.9, 16), (10.0, 1), (12.0, 16), (15.0, 16)]
    assert stats.tokens_in_window(arrivals, 10.0, 15.0) == 17


# ----------------------------------------------- operations and bytes


def test_internlm2_sizes_by_hand():
    m = _fields("internlm2-1.8b")
    # a layer: q and o 2048x2048 each, k and v 2048x1024 each, three
    # 2048x8192 MLP matrices, two norms
    layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192 + 2 * 2048
    assert model_math.layer_params(m) == layer == 62_918_656
    total = 2 * 92544 * 2048 + 24 * layer + 2048
    assert model_math.num_params(m) == total == 1_889_110_016
    # 6 x (24 x 62,914,560 + 189,530,112) + 3 x 24 x 4 x 2048 x 4096 / 2
    assert model_math.train_flops_per_token(m, 4096) \
        == pytest.approx(11.404836864e9)


def test_mistral_sizes_by_hand():
    m = _fields("mistral-7b-v0.3-1chip")
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 2 * 4096
    assert model_math.layer_params(m) == layer == 218_112_000
    assert model_math.num_params(m) == 2 * 32768 * 4096 + 4 * layer + 4096
    assert model_math.train_flops_per_token(m, 4096) \
        == pytest.approx(6.442450944e9)


def test_flash_operations_bytes_and_roofline():
    # 1 x 4096 x 32 heads x 128, causal: forward 2 products over half of
    # the square: 2 x 2 x 32 x 4096^2 x 128 / 2
    fwd = model_math.flash_flops(1, 4096, 32, 128, backward=False)
    assert fwd == 2 * 2 * 32 * 4096 ** 2 * 128 / 2 == 137_438_953_472
    assert model_math.flash_flops(1, 4096, 32, 128, backward=True) \
        == 2.5 * fwd
    # q and o at 32 heads, k and v at 8, bf16
    assert model_math.flash_bytes(1, 4096, 32, 8, 128, backward=False) \
        == 2 * (2 * 4096 * 32 * 128 + 2 * 4096 * 8 * 128)
    peak = model_math.peaks("TPU v5 lite")
    t, bound = model_math.roofline_seconds(fwd, 83_886_080, peak)
    assert bound == "compute" and t == pytest.approx(fwd / 197e12)
    assert model_math.roofline_seconds(1e6, 819e9, peak) == (1.0, "memory")


def test_an_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        model_math.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        model_math.peaks("source")


def test_decode_step_bytes_by_hand():
    m = _fields("internlm2-1.8b")
    # weights once in bf16 (every matmul parameter, 8 embedding rows),
    # and 8 slots x 700 live rows x 24 layers x k and v x 8 x 128, bf16
    weights = (24 * (62_918_656 - 4096) + 2048 * 92544 + 8 * 2048) * 2
    cache = 8 * 700 * 24 * 2 * 8 * 128 * 2
    assert model_math.decode_step_bytes(m, 8, 700) == weights + cache
    assert model_math.mean_live_rows([(100, 10), (200, 30)]) \
        == pytest.approx((10 * 105 + 30 * 215) / 40)


# ------------------------------------------------------------- traffic


def _traffic(name):
    with open(os.path.join(manifest.HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def test_doc_saturated_is_the_cross_product_in_balanced_blocks():
    entries = [tuple(e) for e in _traffic("doc-saturated")["shapes"]["entries"]]
    prompts = [256, 312, 380, 464, 565, 689, 840, 1024]
    outs = [64, 96, 160, 256]
    assert sorted(entries) == sorted((p, o) for p in prompts for o in outs)
    for start in range(0, 32, 8):
        block = entries[start:start + 8]
        assert sorted(p for p, _ in block) == prompts
        assert collections.Counter(o for _, o in block) \
            == {o: 2 for o in outs}
    eng = _traffic("doc-saturated")["engine"]
    assert all(p + o + 1 <= eng["max_len"] for p, o in entries), \
        "the engine would cut a request short of its max_tokens"


@pytest.mark.parametrize("name, seconds", [
    ("doc-saturated", 45), ("chat-steady", 45), ("chat-steady", 12)])
def test_two_seeds_offer_the_same_work_in_the_same_order(name, seconds):
    tr = _traffic(name)
    a, b = (traffic.plan(tr, seed, 92544, seconds)
            for seed in (7, 3_000_000_001))

    def shapes(plan):
        return collections.Counter(
            (len(r["prompt_ids"]), r["max_tokens"], r.get("measured"))
            for r in plan["requests"])

    assert shapes(a) == shapes(b)
    # order and arrival times are part of the work: the same every run
    assert [(len(r["prompt_ids"]), r["max_tokens"], r.get("due"))
            for r in a["requests"]] \
        == [(len(r["prompt_ids"]), r["max_tokens"], r.get("due"))
            for r in b["requests"]]
    assert [r["prompt_ids"] for r in a["requests"]] \
        != [r["prompt_ids"] for r in b["requests"]]
    again = traffic.plan(tr, 7, 92544, seconds)
    assert again == a, "the same seed gives the same inputs"


def test_doc_saturated_keeps_its_order_under_every_seed():
    tr = _traffic("doc-saturated")
    entries = [tuple(e) for e in tr["shapes"]["entries"]]
    for seed in (0, 1, 2, 3_000_000_001):
        got = [(len(r["prompt_ids"]), r["max_tokens"])
               for r in traffic.plan(tr, seed, 92544, 45)["requests"]]
        assert got == entries


def test_open_loop_offers_the_window_a_fixed_load():
    tr = _traffic("chat-steady")
    rate, ramp = tr["arrivals"]["rate_per_s"], tr["window"]["ramp_s"]
    for seed in (1, 2):
        plan = traffic.plan(tr, seed, 92544, 45)
        measured = [r for r in plan["requests"] if r["measured"]]
        assert len(measured) == round(rate * 45)
        assert all(ramp <= r["due"] < ramp + 45 for r in measured)
        dues = [r["due"] for r in plan["requests"]]
        assert dues == sorted(dues)
        eng = tr["engine"]
        assert all(len(r["prompt_ids"]) + r["max_tokens"] + 1
                   <= eng["max_len"] for r in plan["requests"])
    gaps = traffic.exponential_gaps(4.0, 100)
    assert sum(gaps) == pytest.approx(25.0)


def test_bursts_arrive_together_at_the_same_mean_rate():
    tr = _traffic("chat-steady")
    tr["arrivals"] = {**tr["arrivals"], "burst": 8}
    plan = traffic.plan(tr, 5, 92544, 40)
    measured = [r for r in plan["requests"] if r["measured"]]
    assert len(measured) == 8 * round(tr["arrivals"]["rate_per_s"] * 40 / 8)
    assert len({r["due"] for r in measured}) == len(measured) // 8


# -------------------------------------------------------------- loader


def test_every_cell_of_the_manifest_finds_its_files():
    m = manifest.load_manifest()
    for w in m["workloads"]:
        cell = manifest.cell(m, w["name"])
        assert cell["traffic"]["kind"] in ("serve", "train")
        assert {e["name"] for e in cell["end_to_end"]} >= {"setup_s"}
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
        for metric in cell["per_layer"]:
            assert callable(manifest.layer_metric_reader(metric["name"]))
            assert metric["moves"] in {e["name"] for e in cell["end_to_end"]}


def test_a_fifth_cell_needs_new_files_and_entries_only(tmp_path):
    """A later PR's cell: a third configuration, a new mix and a new
    per-layer metric, added as files and entries; no file edited."""
    base = tmp_path / "benchmark"
    shutil.copytree(manifest.HERE, base, ignore=shutil.ignore_patterns(
        "__pycache__"))
    (base / "configs" / "new-model.json").write_text(json.dumps(
        {"hidden_size": 1024, "num_hidden_layers": 2,
         "num_attention_heads": 8, "num_key_value_heads": 4,
         "intermediate_size": 4096, "vocab_size": 1000,
         "rope_theta": 10000.0, "rms_norm_eps": 1e-5}))
    (base / "traffic" / "new-mix.json").write_text(json.dumps(
        {"kind": "serve", "loop": "closed"}))
    (base / "layer_metrics" / "new_metric.x.py").write_text(
        "def read(facts):\n    return facts.get('n')\n")
    m = manifest.load_manifest()
    m["workloads"].append({"name": "new-model.new-mix", "config": "new-model",
                           "traffic": "new-mix", "chips": 1, "why": "-"})
    m["per_layer"].append({"name": "new_metric.x", "unit": "n",
                           "better": "higher", "source": "program_counter",
                           "layer": "x", "moves": "setup_s",
                           "workloads": ["new-model.new-mix"]})
    cell = manifest.cell(m, "new-model.new-mix", base=str(base))
    assert manifest.llama_fields(cell["config"])["d_model"] == 1024
    assert [p["name"] for p in cell["per_layer"]] == ["new_metric.x"]
    read = manifest.layer_metric_reader("new_metric.x", base=str(base))
    assert read({"n": 3}) == 3 and read({}) is None


@pytest.mark.parametrize("bad", [
    "a b", "a/b", "../x", "a,b", "", "x" * 65, "µs", "-lead", "a..b"])
def test_a_name_outside_the_allowed_characters_is_refused(bad):
    with pytest.raises(manifest.ManifestError):
        manifest.check_name(bad)
    with pytest.raises(manifest.ManifestError):
        manifest.cell({"workloads": [], "end_to_end": [],
                       "per_layer": []}, bad)


def test_an_unknown_cell_or_a_missing_file_is_refused():
    m = manifest.load_manifest()
    with pytest.raises(manifest.ManifestError):
        manifest.cell(m, "no-such.cell")
    with pytest.raises(manifest.ManifestError):
        manifest.layer_metric_reader("no_such_metric")
    with pytest.raises(manifest.ManifestError):
        manifest.llama_fields({"sliding_window": 4096})


def test_the_manifest_keeps_to_its_contract():
    m = manifest.load_manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[k]]
    for n in names:
        manifest.check_name(n)
    assert len(set(names)) == len(names)
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] \
        == ["internlm2-1.8b.pretrain-4k-fsdp2tp2"]
    for e in m["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.1
    for c in m["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            assert sorted(json.load(f)["reduced"]) == sorted(c["reduced"])
