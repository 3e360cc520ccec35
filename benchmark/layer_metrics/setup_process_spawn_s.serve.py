"""Node agent and worker spawn: how old the replica's process was when
``LLMServer.__init__`` was entered (``process_age_ms`` of the
``serve.setup`` mark: the process's start time in ``/proc/self/stat``
against the boot clock), in seconds: the agent's spawn, the interpreter,
the imports (``jax`` among them) and the actor's creation. Moves
``setup_s``; lower is better. None without the mark (a parent commit)."""
from benchmark import setup_reduce

NAME = "setup_process_spawn_s.serve"


def read(facts):
    return setup_reduce.seconds(facts, NAME, "process_age_ms")
