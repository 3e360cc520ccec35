"""Device time by model part (``benchmark/part_reduce.py``) on a small
hand-made trace and map, after the structure of a capture of a replica:
one decode chunk (a ``while`` that holds its operations), two executions
of the prefill program at two buckets (one name, two ids, two maps), a
program the map does not know and an operation outside every program.
Times in ns. Nothing here touches a device or a clock."""

import json

import pytest

from benchmark import manifest, part_reduce

TRACE = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_decode_chunk(11)", 0, 1000],
            ["jit__prefill_batch_into_slots(21)", 1100, 300],
            ["jit__prefill_batch_into_slots(22)", 1500, 400],
            ["jit_other(5)", 2000, 100]]},
        {"name": "XLA Ops", "events": [
            # the chunk: 100 ns of the while are no operation's
            ["while.1", 0, 1000],
            ["fusion.1", 0, 400],
            ["custom-call/1out/decode_attn.12", 400, 200],
            ["fusion.2", 600, 200],
            ["mul.30", 800, 50],
            ["copy.9", 850, 50],  # the map of the chunk has no copy.9
            # the prefill at one bucket, then at another
            ["fusion.1", 1100, 200], ["fusion.7", 1300, 100],
            ["fusion.1", 1500, 300], ["fusion.8", 1800, 100],
            # a program the engine does not own; an operation alone
            ["fusion.1", 2000, 100],
            ["stray.1", 2200, 50]]}]},
    {"name": "/device:TPU:1", "lines": [  # (the first plane is read)
        {"name": "XLA Modules", "events": [["jit_decode_chunk(11)", 0, 9]]},
        {"name": "XLA Ops", "events": [["fusion.1", 0, 9]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["serve.pump", 0, 3000]]}]},
]}
MAP = {"engine": "decode-1", "seconds": 0.01,
       "vocabulary": ["qkv", "attn", "attn_out", "mlp", "lm_head", "sample"],
       "programs": {
           "jit_decode_chunk": [{"what": "greedy", "parts": {
               "while.1": "loop", "fusion.1": "mlp",
               "decode_attn.12": "attn/attn_window",
               "fusion.2": "attn_out+mixed",
               "mul.30": "unscoped:jit(f)/mul", "fusion.99": "qkv"}}],
           "jit__prefill_batch_into_slots": [
               {"what": "cold, bucket 256", "parts": {
                   "fusion.1": "qkv", "fusion.7": "sample"}},
               {"what": "cold, bucket 512", "parts": {
                   "fusion.1": "mlp", "fusion.8": "lm_head"}}]}}
BUSY_NS = 1000 + 300 + 400 + 100 + 50


@pytest.fixture
def facts(tmp_path):
    (tmp_path / part_reduce.FILE).write_text(json.dumps(MAP))
    return {"trace": TRACE, "log_dir": str(tmp_path)}


def test_parts_unscoped_and_unjoined_sum_to_the_busy_time(facts):
    t = part_reduce.table(facts)
    assert t["busy_s"] == pytest.approx(BUSY_NS / 1e9)
    ns = {prog: {part: round(s * 1e9) for part, s in parts.items()}
          for prog, parts in t["programs"].items()}
    assert ns == {
        # the while's own 100 ns are the loop's; a name the program's
        # map lacks is unjoined
        "jit_decode_chunk": {"mlp": 400, "attn/attn_window": 200,
                             "attn_out": 200, "unscoped": 50,
                             "unjoined": 50, "loop": 100},
        # each execution id took the map that knows its names: fusion.1
        # is the projections at one bucket and the MLP at the other
        "jit__prefill_batch_into_slots": {"qkv": 200, "sample": 100,
                                          "mlp": 300, "lm_head": 100},
        # a program absent from the map, and no program at all
        "jit_other": {"unjoined": 100}, "-": {"unjoined": 50}}
    assert sum(s for parts in ns.values() for s in parts.values()) \
        == BUSY_NS
    # mixed time is ALSO in its part; the unscoped come with what they
    # did say
    assert {p: round(s * 1e9) for p, s in t["mixed_s"].items()} \
        == {"jit_decode_chunk": 200}
    assert t["unscoped_ops"] == [
        ["jit_decode_chunk/mul.30", "jit(f)/mul", pytest.approx(50e-9)]]


@pytest.mark.parametrize("part, ns", [
    ("attn", 200), ("mlp", 700), ("lm_head", 100), ("sample", 100),
    ("loop", 100), ("unscoped", 50), ("cache", 0), ("moe_experts", 0)])
def test_a_part_share_is_its_seconds_over_the_busy_seconds(facts, part, ns):
    read = manifest.layer_metric_reader(f"device_part_share.{part}")
    assert read(facts) == pytest.approx(100.0 * ns / BUSY_NS)
    assert "device_parts" in facts  # made once, kept


READERS = [m["name"] for m in manifest.load_manifest()["per_layer"]
           if m["name"].startswith("device_part_share.")]


def test_the_manifest_lists_the_eight_shares():
    assert len(READERS) == 8
    for m in manifest.load_manifest()["per_layer"]:
        if m["name"] in READERS:
            assert (m["unit"], m["better"], m["source"], m["moves"]) == (
                "%", "lower", "device_trace", "out_tokens_per_s")
            assert all("saturated" in w for w in m["workloads"])


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("why", ["no map", "no device plane", "no trace"])
def test_without_a_map_or_a_device_every_reader_returns_none(
        tmp_path, name, why):
    """A parent commit writes no ``program_parts.json``; a CPU rehearsal
    has no device plane: None, and nothing raised."""
    if why != "no map":
        (tmp_path / part_reduce.FILE).write_text(json.dumps(MAP))
    trace = {"no map": TRACE, "no trace": None, "no device plane": {
        "planes": [p for p in TRACE["planes"]
                   if p["name"].startswith("/host")]}}[why]
    facts = {"trace": trace, "log_dir": str(tmp_path)}
    assert manifest.layer_metric_reader(name)(facts) is None
    assert manifest.layer_metric_reader(name)({"log_dir": None}) is None


def test_self_times():
    """A leaf's duration, a holder's rest; nesting two deep."""
    evs = [["outer", 0, 100], ["inner", 10, 50], ["leaf.a", 10, 20],
           ["leaf.b", 40, 20], ["leaf.c", 70, 10], ["alone", 200, 5]]
    assert sorted(part_reduce.self_times(evs)) == sorted([
        ("outer", 0, 100 - 50 - 10), ("inner", 10, 50 - 40),
        ("leaf.a", 10, 20), ("leaf.b", 40, 20), ("leaf.c", 70, 10),
        ("alone", 200, 5)])
