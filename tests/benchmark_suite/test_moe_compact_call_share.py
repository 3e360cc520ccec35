"""``moe_compact_call_share.reason`` on made-up spans: the compact calls
over the expert-layer calls that had the branch, by hand, summed over the
read-backs that carry them; a read-back without the attrs (a parent
commit's, a chunk with no cold prefill before it) is counted out, and
with none that carries them the metric is nothing. No device, no file,
no clock."""

import pytest

from benchmark import manifest

NAME = "moe_compact_call_share.reason"
CELLS = ["solar-open2-250b-ep8-1chip.longreason-saturated",
         "mimo-v2.5-ep16-1chip.longreason-saturated",
         "k-exaone-236b-a23b-ep8-1chip.reason-long-saturated"]


def _readback(calls=None, compact=None):
    attrs = {"live_rows": 100, "cache_rows": 1000, "expert_load_max": 90.0,
             "expert_load_mean": 64.0}
    if calls is not None:
        attrs.update(moe_expert_calls=calls, moe_compact_calls=compact)
    return ["engine.readback", 0, 0, attrs]


def _read(events):
    spans = {"lines": [{"name": "python", "events": events}]} \
        if events is not None else None
    return manifest.layer_metric_reader(NAME)(
        {"spans": spans, "trace": None, "log_dir": None})


def test_compact_calls_over_the_calls_that_had_the_branch():
    # a 9,984-token prompt's five live segments in six expert layers,
    # one of them over the capacity; a 32,768-token prompt's sixteen
    got = _read([_readback(30, 29), _readback(96, 96),
                 ["engine.prefill", 0, 0, {"moe_expert_calls": 7}]])
    assert got == pytest.approx(100 * 125 / 126)


def test_every_call_compact_reads_100_and_none_reads_0():
    assert _read([_readback(30, 30), _readback(6, 6)]) == 100.0
    assert _read([_readback(12, 0)]) == 0.0


def test_a_readback_without_the_attrs_is_counted_out(capfd):
    got = _read([_readback(), _readback(8, 6), _readback()])
    assert got == pytest.approx(75.0)
    assert "1 engine.readback with compact calls" in capfd.readouterr().err


@pytest.mark.parametrize("events", [None, [], [_readback()],
                                    [_readback(0, 0)]])
def test_no_call_that_had_the_branch_reads_as_nothing(events):
    assert _read(events) is None


def test_the_manifest_names_it_for_the_cells_whose_shapes_give_a_capacity():
    m = manifest.load_manifest()
    entry = {x["name"]: x for x in m["per_layer"]}[NAME]
    beside = {x["name"]: x for x in m["per_layer"]}[
        "moe_held_assignment_share.reason"]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": beside["layer"],
        "moves": "out_tokens_per_s", "workloads": CELLS}
    assert set(CELLS) <= set(beside["workloads"])
