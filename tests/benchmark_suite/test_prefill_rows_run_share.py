"""``prefill_rows_run_share.doc`` on made-up spans: the real tokens over
the rows of the segments a call ran, by hand; a span without
``live_segments`` (a parent commit's) is counted out, and with none that
carries it the metric is nothing. No device, no file, no clock."""

import pytest

from benchmark import manifest

NAME = "prefill_rows_run_share.doc"
CELLS = ["solar-open2-250b-ep8-1chip.longreason-saturated",
         "mimo-v2.5-ep16-1chip.longreason-saturated"]


def _call(tokens, bucket, segments, live=None):
    attrs = {"bucket": bucket, "prompts": 1, "rows": 1, "tokens": tokens,
             "segments": segments}
    if live is not None:
        attrs["live_segments"] = live
    return ["engine.prefill", 0, 0, attrs]


def _read(events):
    spans = {"lines": [{"name": "python", "events": events}]} \
        if events is not None else None
    return manifest.layer_metric_reader(NAME)(
        {"spans": spans, "trace": None, "log_dir": None})


def test_real_tokens_over_the_rows_of_the_segments_run():
    # 9,984 in five of 16,384's eight segments, 32,768 in all sixteen
    got = _read([_call(9984, 16384, 8, 5), _call(32768, 32768, 16, 16),
                 ["engine.readback", 0, 0, {"live_segments": 9}]])
    assert got == pytest.approx(100 * (9984 + 32768) / (5 * 2048 + 32768))


def test_the_traffic_files_turn_reads_97_percent():
    turn = [(8192, 8192), (9984, 16384), (12160, 16384), (14848, 16384),
            (18080, 24576), (22048, 24576), (26880, 32768), (32768, 32768)]
    got = _read([_call(n, b, b // 2048, -(-n // 2048)) for n, b in turn])
    assert got == pytest.approx(100 * 144960 / 149504)


def test_a_span_without_the_attr_is_counted_out(capfd):
    got = _read([_call(9984, 16384, 8), _call(12160, 16384, 8, 6)])
    assert got == pytest.approx(100 * 12160 / (6 * 2048))
    assert "1 engine.prefill with live_segments" in capfd.readouterr().err


def test_an_unsegmented_call_reads_its_bucket():
    assert _read([_call(300, 512, 1, 1)]) == pytest.approx(100 * 300 / 512)


@pytest.mark.parametrize("events", [None, [], [_call(9984, 16384, 8)]])
def test_no_span_that_carries_it_reads_as_nothing(events):
    assert _read(events) is None


def test_the_manifest_names_it_for_the_two_longreason_cells():
    m = manifest.load_manifest()
    entry = {x["name"]: x for x in m["per_layer"]}[NAME]
    beside = {x["name"]: x for x in m["per_layer"]}[
        "prefill_token_use_share.doc"]
    assert entry == {**beside, "name": NAME, "workloads": CELLS}
    assert m["per_layer"][-1] is entry  # (appended: nothing moved)
    assert set(CELLS) <= set(beside["workloads"])
