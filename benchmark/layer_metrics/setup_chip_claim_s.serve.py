"""Chip claim: ``claim_ms`` of the ``serve.setup`` mark in seconds, the
replica's ``serve.claim_device`` span: ``accelerator.claim_device()``,
which waits for a chip that a process in exit still holds
(``chip_wait_ms`` of it, printed beside; 0 where nothing was busy) and
then initialises the backend (``jax.devices()``). Moves ``setup_s``;
lower is better. None without the mark (a parent commit)."""
from benchmark import setup_reduce

NAME = "setup_chip_claim_s.serve"


def read(facts):
    return setup_reduce.seconds(facts, NAME, "claim_ms",
                                beside=("chip_wait_ms",))
