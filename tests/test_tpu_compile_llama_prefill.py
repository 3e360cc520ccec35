"""The Llama block's cold prefill call compiled for a described v5e at the
doc cells' engine shape, every bucket of InternLM2's cell and the sparse
model's widest (``tests/_tpu_compile.py`` says how and why, and holds the
check). Apart from the block's other compiles
(``tests/test_tpu_compile_llama.py``) and from the chat cells' buckets
(``tests/test_tpu_compile_llama_prefill_chat.py``): these are half a
minute each.
"""

import pytest

from _tpu_compile import (  # noqa: F401 (topo: a fixture)
    DOC, _one_row_prefill_is_sized_by_its_bucket, topo)

PREFILL_CALLS = [("internlm2", DOC, 256), ("internlm2", DOC, 512),
                 ("internlm2", DOC, 1024), ("olmoe", DOC, 1024)]


@pytest.mark.parametrize("model,engine,bucket", PREFILL_CALLS, ids=[
    f"{m}-{e['slots']}x{e['max_len']}-{b}" for m, e, b in PREFILL_CALLS])
def test_one_row_prefill_is_sized_by_its_bucket(topo, monkeypatch, model,
                                                engine, bucket):
    _one_row_prefill_is_sized_by_its_bucket(topo, monkeypatch, model, engine,
                                            bucket)
