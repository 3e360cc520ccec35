"""The Llama block's cold prefill call compiled for a described v5e at the
doc cells' engine shape, every bucket of InternLM2's cell and the sparse
model's widest (``tests/_tpu_compile.py`` says how and why, and holds the
check). Apart from the block's other compiles
(``tests/test_tpu_compile_llama.py``) and from the chat cells' buckets
(``tests/test_tpu_compile_llama_prefill_chat.py``): these are 40 s each
on the driver's box, so tier-1 holds InternLM2's narrowest bucket (one
flash block) and the sparse model's widest; InternLM2's 512 and 1,024
rows (the same lines at other extents; 1,024 rows are one block too)
are ``-m slow``, and the doc cell compiles them on the chip in every
PR's check.
"""

import pytest

from _tpu_compile import (  # noqa: F401 (topo: a fixture)
    DOC, _one_row_prefill_is_sized_by_its_bucket, topo)

PREFILL_CALLS = [("internlm2", DOC, 256), ("internlm2", DOC, 512),
                 ("internlm2", DOC, 1024), ("olmoe", DOC, 1024)]
SLOW = [("internlm2", 512), ("internlm2", 1024)]


@pytest.mark.parametrize("model,engine,bucket", [
    pytest.param(m, e, b, id=f"{m}-{e['slots']}x{e['max_len']}-{b}",
                 marks=[pytest.mark.slow] * ((m, b) in SLOW))
    for m, e, b in PREFILL_CALLS])
def test_one_row_prefill_is_sized_by_its_bucket(topo, monkeypatch, model,
                                                engine, bucket):
    _one_row_prefill_is_sized_by_its_bucket(topo, monkeypatch, model, engine,
                                            bucket)
