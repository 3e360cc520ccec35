"""XLA compile and the persistent cache: of the programs the replica's
process asked the persistent compile cache for, the share it held, in
percent (``proc_cache_hits`` / ``proc_compile_requests`` of the
``serve.setup`` mark): the machine's state, which tells a slow
``setup_s`` that is the lease's from one that is the program's. Higher
is better. None without the mark or where nothing was asked."""
from benchmark import setup_reduce

NAME = "setup_compile_cache_hit_share.serve"


def read(facts):
    return setup_reduce.cache_hit_share(facts, NAME)
