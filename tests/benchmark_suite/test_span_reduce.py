"""The reduction of the program's spans (``benchmark/span_reduce.py``)
on a small hand-made set after a recorded trace's structure: one host
line for the pump thread (``serve.pump`` holding ``engine.admit`` >
``engine.prefill``, ``engine.decode_dispatch``, ``engine.readback``,
``engine.deliver`` and the first-token marks, then
``serve.pump_bookkeeping``), one for a handler thread (poll marks), and
a device plane whose operations leave three idle gaps. Times in ns.
Nothing here touches a device, a file or a clock."""

import pytest

from benchmark import manifest, span_reduce

PUMP_LINE = {"name": "python", "events": [
    # pump 1: 1000..11000; admits two prompts in two buckets
    ["serve.pump", 1000, 10000,
     {"mono_ns": 5_000_001_000, "active": 0, "queued": 2}],
    ["engine.admit", 1100, 2900,
     {"admitted": 2, "prefilled": 0, "cold": 2, "warm": 0}],
    ["engine.prefill", 1200, 1300,
     {"bucket": 512, "prompts": 1, "rows": 8, "tokens": 300}],
    ["engine.prefill", 2600, 1300,
     {"bucket": 1024, "prompts": 1, "rows": 8, "tokens": 900}],
    ["engine.decode_dispatch", 4100, 400,
     {"active": 2, "chunk": 16, "depth": 0}],
    ["engine.readback", 4600, 6000, {}],
    ["engine.deliver", 10600, 300,
     {"delivered": 34, "firsts": 2, "finished": 0}],
    ["serve.first_token", 10650, 0,
     {"sid": 1, "queue_wait_ms": 40.0, "prefill_to_token_ms": 700.0,
      "upstream_ms": 12.0, "proxy_to_pool_ms": 5.0,
      "admission_wait_ms": 3.0, "pool_to_replica_ms": 4.0}],
    ["serve.first_token", 10700, 0,
     {"sid": 2, "queue_wait_ms": 10.0, "prefill_to_token_ms": 500.0,
      "upstream_ms": 30.0, "proxy_to_pool_ms": 20.0,
      "admission_wait_ms": 4.0, "pool_to_replica_ms": 6.0}],
    ["serve.pump_bookkeeping", 10920, 60, {"waiters": 0, "streams": 2}],
    # pump 2: 11100..18100; one prefill that held three prompts
    ["serve.pump", 11100, 7000,
     {"mono_ns": 5_000_011_100, "active": 2, "queued": 3}],
    ["engine.admit", 11150, 1000,
     {"admitted": 3, "prefilled": 0, "cold": 3, "warm": 0}],
    ["engine.prefill", 11200, 900,
     {"bucket": 512, "prompts": 3, "rows": 8, "tokens": 1000}],
    ["engine.decode_dispatch", 12200, 300,
     {"active": 5, "chunk": 16, "depth": 0}],
    ["engine.readback", 12600, 5000, {}],
    ["engine.deliver", 17700, 300,
     {"delivered": 83, "firsts": 3, "finished": 1}],
    # a first token that came without birth stamps (driver-direct)
    ["serve.first_token", 17750, 0,
     {"sid": 3, "queue_wait_ms": 100.0, "prefill_to_token_ms": 600.0}],
    # pump 3: 18200..21200, no admission
    ["serve.pump", 18200, 3000,
     {"mono_ns": 5_000_018_200, "active": 4, "queued": 0}],
    ["engine.readback", 18700, 2000, {}],
]}
HANDLER_LINE = {"name": "python", "events": [
    ["serve.poll_pickup", 10900, 0,
     {"sid": 1, "tokens": 17, "first": 1, "pickup_ms": 6.0}],
    ["serve.poll_pickup", 10950, 0,
     {"sid": 2, "tokens": 17, "first": 1, "pickup_ms": 9.0}],
    ["serve.poll_pickup", 17900, 0,
     {"sid": 1, "tokens": 16, "first": 0, "pickup_ms": 500.0}],
    ["serve.poll_pickup", 17950, 0,
     {"sid": 3, "tokens": 17, "first": 1, "pickup_ms": 30.0}],
]}
SPANS = {"lines": [PUMP_LINE, HANDLER_LINE]}

# the device: busy but for 2000..2500 (its middle inside the first
# engine.prefill), 4200..4400 (inside engine.decode_dispatch) and
# 21500..22000 (after the last span)
TRACE = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit__prefill_batch_into_slots(1)", 1000, 1000],
            ["jit_decode_chunk(2)", 4400, 6000]]},
        {"name": "XLA Ops", "events": [
            ["fusion.1", 1000, 1000], ["fusion.2", 2500, 1700],
            ["fusion.3", 4400, 17100], ["fusion.4", 22000, 500]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["poll", 0, 30000]]}]},
]}


def _facts(spans=SPANS, trace=None):
    return {"spans": spans, "trace": trace, "log_dir": None}


def _read(name, facts):
    return manifest.layer_metric_reader(name)(facts)


def test_encoded_names_give_back_their_attrs():
    # what TraceMe writes where metadata cannot ride as stats
    assert span_reduce.split_name(
        "engine.prefill#bucket=512,prompts=1,rows=8#") == (
        "engine.prefill", {"bucket": 512, "prompts": 1, "rows": 8})
    assert span_reduce.split_name(
        "serve.first_token#queue_wait_ms=1.5,engine=llm-7#") == (
        "serve.first_token", {"queue_wait_ms": 1.5, "engine": "llm-7"})
    assert span_reduce.split_name("serve.pump") == ("serve.pump", {})
    assert span_reduce.split_name("$threading.py:637 wait") == (
        "$threading.py:637 wait", {})


def test_children_are_found_by_nesting_on_their_own_line():
    pumps = [ev for ev in PUMP_LINE["events"] if ev[0] == "serve.pump"]
    assert [len(span_reduce.inside(PUMP_LINE, p, "engine.prefill"))
            for p in pumps] == [2, 1, 0]
    assert [len(span_reduce.inside(PUMP_LINE, p, "engine.readback"))
            for p in pumps] == [1, 1, 1]
    # a mark of another thread inside a pump's interval is not its child
    assert span_reduce.inside(PUMP_LINE, pumps[0], "serve.poll_pickup") \
        == []
    assert len(span_reduce.named(SPANS, "serve.poll_pickup")) == 4


@pytest.mark.parametrize("cell", ["doc", "chat"])
def test_pump_host_work_is_the_pump_less_its_readback(cell, capfd):
    # 10000-6000, 7000-5000, 3000-2000 ns -> median 2000 ns
    assert _read(f"pump_host_work_ms.{cell}", _facts()) \
        == pytest.approx(2000e-6)
    assert "3 serve.pump in the traced part" in capfd.readouterr().err


@pytest.mark.parametrize("cell", ["doc", "chat"])
def test_the_two_prefill_ratios_by_hand(cell):
    # three calls holding 1, 1 and 3 prompts
    assert _read(f"prefill_prompts_per_call.{cell}", _facts()) \
        == pytest.approx(5 / 3)
    # useful 300 + 900 + 1000 tokens over 8*512 + 8*1024 + 8*512 paid
    assert _read(f"prefill_token_use_share.{cell}", _facts()) \
        == pytest.approx(100 * 2200 / 16384)


def test_request_stages_are_medians_of_the_first_token_marks(capfd):
    f = _facts()
    assert _read("engine_queue_wait_p50_ms.chat", f) == 40.0
    assert _read("prefill_to_first_token_p50_ms.chat", f) == 600.0
    # only two of the three requests carried birth stamps
    assert _read("upstream_wait_p50_ms.chat", f) == 21.0
    err = capfd.readouterr().err
    assert "2 serve.first_token with upstream_ms" in err
    assert "proxy_to_pool_ms median 12.500 over 2" in err
    # the poll that took a later batch of tokens is no first pickup
    assert _read("poll_pickup_p50_ms.chat", f) == 9.0


NEW_METRICS = [
    "prefill_prompts_per_call.doc", "prefill_prompts_per_call.chat",
    "prefill_token_use_share.doc", "prefill_token_use_share.chat",
    "pump_host_work_ms.doc", "pump_host_work_ms.chat",
    "engine_queue_wait_p50_ms.chat", "prefill_to_first_token_p50_ms.chat",
    "upstream_wait_p50_ms.chat", "poll_pickup_p50_ms.chat"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_trace_without_program_spans_reads_as_nothing(name, tmp_path):
    # a parent commit: no span in the trace, or no trace file at all
    assert _read(name, _facts(spans=None)) is None
    assert _read(name, {"log_dir": str(tmp_path), "trace": TRACE}) is None
    assert _read(name, {"log_dir": None}) is None


def test_new_metrics_are_declared_with_an_existing_layer():
    m = manifest.load_manifest()
    by_name = {x["name"]: x for x in m["per_layer"]}
    old_layers = {x["layer"] for x in m["per_layer"]
                  if x["name"] not in NEW_METRICS}
    for name in NEW_METRICS:
        entry = by_name[name]
        assert entry["layer"] in old_layers
        assert entry["source"] == "device_trace"
        cell = manifest.cell(m, entry["workloads"][0])
        assert entry["moves"] in {e["name"] for e in cell["end_to_end"]}


def test_idle_gaps_are_charged_to_the_innermost_program_span():
    got = dict(span_reduce.charge_gaps(TRACE, SPANS))
    # 2000..2500: pump 1 and engine.admit span its middle (the first
    # prefill's span ended at 2500 but the middle, 2250, is inside it)
    assert got["engine.prefill"] == pytest.approx(500e-9)
    # 4200..4400: serve.pump > engine.decode_dispatch -> the innermost
    assert got["engine.decode_dispatch"] == pytest.approx(200e-9)
    # 21500..22000: no program span
    assert got["outside-spans"] == pytest.approx(500e-9)
    assert set(got) == {"engine.prefill", "engine.decode_dispatch",
                        "outside-spans"}
    # a trace with no device plane has no gap to charge
    assert span_reduce.charge_gaps({"planes": []}, SPANS) == []


def test_spans_and_device_planes_share_one_clock():
    # the decode chunk 4400..10400 ends inside the first read-back
    # (4600..10600): 200 ns before it; the other read-backs hold no
    # chunk's end. One prefill execution began inside the pumps' part of
    # the trace (1000 is pump 1's own start), three spans did.
    assert span_reduce.against_device(TRACE, SPANS) == {
        "readbacks": 1,
        "readback_end_after_chunk_end_ms_median": pytest.approx(200e-6),
        "readback_end_after_chunk_end_ms_max": pytest.approx(200e-6),
        "prefill_spans": 3, "prefill_executions": 1}
    assert span_reduce.against_device({"planes": []}, SPANS) == {}
