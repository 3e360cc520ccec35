"""The Llama block's cold prefill call compiled for a described v5e at the
two chat cells' engine shape, every bucket (``tests/_tpu_compile.py`` says
how and why, and holds the check; the doc cells' buckets:
``tests/test_tpu_compile_llama_prefill.py``). Tier-1 holds the narrowest
bucket, half a flash block, into 32 slots; 128 and 256 rows (the same
lines at other extents, 30 to 48 s each on the driver's box) are ``-m
slow``, and both chat cells compile them on the chip in every PR's
check.
"""

import pytest

from _tpu_compile import (  # noqa: F401 (topo: a fixture)
    CHAT, _one_row_prefill_is_sized_by_its_bucket, topo)

PREFILL_CALLS = [("internlm2", CHAT, 64), ("internlm2", CHAT, 128),
                 ("internlm2", CHAT, 256)]


@pytest.mark.parametrize("model,engine,bucket", [
    pytest.param(m, e, b, id=f"{m}-{e['slots']}x{e['max_len']}-{b}",
                 marks=[pytest.mark.slow] * (b > 64))
    for m, e, b in PREFILL_CALLS])
def test_one_row_prefill_is_sized_by_its_bucket(topo, monkeypatch, model,
                                                engine, bucket):
    _one_row_prefill_is_sized_by_its_bucket(topo, monkeypatch, model, engine,
                                            bucket)
