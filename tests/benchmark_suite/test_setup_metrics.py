"""The six ``setup_*`` readers on a hand-made ``serve.setup`` mark: each
one's value and unit, the newest of two marks, nothing without the mark
(a parent commit); and their entries of ``BENCHMARK.json``, looked up by
name. No device, no file, no clock."""

import json
import os

import pytest

from benchmark import manifest

RECORD = {
    "process_age_ms": 14800.0, "claim_ms": 5250.0, "chip_wait_ms": 3100.0,
    "weights_build_ms": 21000.0, "weights_cast_ms": 1500.0,
    "replica_start_ms": 29000.0, "first_calls": 9, "trace_ms": 40000.0,
    "lower_ms": 5000.0, "compile_ms": 700.0, "cache_read_ms": 2300.0,
    "first_call_ms": 49000.0, "compile_requests": 9, "cache_hits": 9,
    "proc_compile_requests": 40, "proc_cache_hits": 30,
    "init_mono_ns": 1_000_000_000_000, "ready_mono_ns": 1_029_000_000_000,
    "last_compile_mono_ns": 1_100_000_000_000}
OLDER = {**RECORD, "process_age_ms": 1.0, "claim_ms": 1.0,
         "weights_build_ms": 1.0, "weights_cast_ms": 1.0, "trace_ms": 1.0,
         "lower_ms": 1.0, "compile_ms": 1.0, "cache_read_ms": 1.0,
         "proc_compile_requests": 40, "proc_cache_hits": 0}
# metric -> (value of RECORD by hand, unit, better, layer's first words)
WANT = {
    "setup_process_spawn_s.serve": (14.8, "s", "lower", "node agent"),
    "setup_chip_claim_s.serve": (5.25, "s", "lower", "chip claim"),
    "setup_weights_s.serve": (22.5, "s", "lower", "replica pump"),
    "setup_trace_lower_s.serve": (45.0, "s", "lower", "engine programs"),
    "setup_compile_s.serve": (3.0, "s", "lower", "XLA compile"),
    "setup_compile_cache_hit_share.serve": (75.0, "%", "higher",
                                            "XLA compile"),
}


def _mark(attrs, start_ns=0):
    return ["serve.setup", start_ns, 0, dict(attrs)]


def _read(name, lines, **facts):
    spans = {"lines": [{"name": f"thread-{i}", "events": evs}
                       for i, evs in enumerate(lines)]} if lines else None
    return manifest.layer_metric_reader(name)(
        {"spans": spans, "trace": None, "log_dir": None, **facts})


def _entries():
    return {x["name"]: x for x in manifest.load_manifest()["per_layer"]}


@pytest.mark.parametrize("name", WANT)
def test_the_value_by_hand(name, capfd):
    got = _read(name, [[_mark(RECORD)]], setup_s=120.0)
    assert got == pytest.approx(WANT[name][0])
    err = capfd.readouterr().err
    # the run's setup_s beside what the record explains of it:
    # 14.8 + 29 + 49 of 120 s, and the rest as a number
    assert f"benchmark: {name}: " in err
    assert "setup_s 120.000 s" in err and "explains 92.800 s" in err
    assert "not 27.200 s" in err
    assert "ready to the last first call 71.000 s" in err
    assert "benchmark: serve.setup: {" in err  # the whole record, once


@pytest.mark.parametrize("name", WANT)
def test_the_newest_of_two_marks(name):
    # two captures in one run, on two threads' lines: the later start
    lines = [[_mark(RECORD, start_ns=9_000)],
             [["engine.state_init", 10_000, 0, {"slots": 8}],
              _mark(OLDER, start_ns=4_000)]]
    assert _read(name, lines) == pytest.approx(WANT[name][0])
    assert _read(name, lines[::-1]) == pytest.approx(WANT[name][0])


@pytest.mark.parametrize("name", WANT)
@pytest.mark.parametrize("lines", [
    None, [[["engine.state_init", 0, 0, {"slots": 8}]]],
    [[_mark({"engine": "decode-1"})]]],
    ids=["no-spans", "no-mark", "a-mark-without-the-keys"])
def test_a_parent_commit_reads_as_nothing(name, lines):
    # (and raises nothing: the CPU rehearsal forgives a KeyError alone)
    assert _read(name, lines) is None
    assert _read(name, lines, setup_s=50.0) is None


def test_the_claim_prints_the_wait_inside_it(capfd):
    _read("setup_chip_claim_s.serve", [[_mark(RECORD)]])
    assert "5.250 s (chip_wait_ms 3100.0)" in capfd.readouterr().err


def test_the_record_is_printed_once_a_run(capfd):
    facts = {"spans": {"lines": [{"name": "t", "events": [_mark(RECORD)]}]},
             "trace": None, "log_dir": None, "setup_s": 100.0}
    for name in WANT:
        manifest.layer_metric_reader(name)(facts)
    err = capfd.readouterr().err
    assert err.count("benchmark: serve.setup: {") == 1
    assert err.count("setup_s 100.000 s") == len(WANT)


def test_no_request_of_the_cache_is_no_share():
    rec = {**RECORD, "proc_compile_requests": 0, "proc_cache_hits": 0}
    assert _read("setup_compile_cache_hit_share.serve",
                 [[_mark(rec)]]) is None


@pytest.mark.parametrize("name", WANT)
def test_the_manifest_entry_by_name(name):
    m = manifest.load_manifest()
    entry = _entries()[name]
    _, unit, better, layer = WANT[name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["unit"], entry["better"]) == (unit, better)
    assert entry["source"] == "program_span"
    assert entry["moves"] == "setup_s" and entry["layer"].startswith(layer)
    # exactly the cells whose traffic is served, in the manifest's order
    serving = []
    for w in m["workloads"]:
        with open(os.path.join(manifest.HERE, "traffic",
                               w["traffic"] + ".json")) as f:
            if json.load(f)["kind"] == "serve":
                serving.append(w["name"])
    assert entry["workloads"] == serving, (
        "a new serving cell reports setup_s: append it to this metric's "
        "workloads, its replica leaves the mark")
    assert len(serving) >= 10
    # the file it names exists and holds a reader
    assert callable(manifest.layer_metric_reader(name))


def test_setup_s_has_layers_under_it_and_every_cell_reports_it():
    m = manifest.load_manifest()
    movers = [x["name"] for x in m["per_layer"] if x["moves"] == "setup_s"]
    assert set(WANT) <= set(movers)  # (a later PR may add one)
    (setup,) = [x for x in m["end_to_end"] if x["name"] == "setup_s"]
    assert "workloads" not in setup  # every cell is judged on it
    # a replica's layer keeps the name the manifest already had for it
    layers = {x["layer"] for x in m["per_layer"]
              if x["moves"] != "setup_s"}
    assert _entries()["setup_weights_s.serve"]["layer"] in layers
