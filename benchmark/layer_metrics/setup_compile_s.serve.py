"""XLA compile and the persistent cache: ``compile_ms`` +
``cache_read_ms`` of the ``serve.setup`` mark in seconds, summed over
the engine's ``engine.compiled`` marks: jax's backend stage of each
program's first call, which is XLA's compile where the cache missed and
the entry's read where it hit (``setup_compile_cache_hit_share.serve``
says which a run had). Moves ``setup_s``; lower is better. None without
the mark."""
from benchmark import setup_reduce

NAME = "setup_compile_s.serve"


def read(facts):
    return setup_reduce.seconds(facts, NAME, "compile_ms", "cache_read_ms")
