"""Replica pump: median duration of one ``serve.pump`` less its
``engine.readback``: what the host costs per chunk while the device is
not being waited for. Prints the device's idle gaps by program span."""
from benchmark import span_reduce


def read(facts):
    return span_reduce.pump_host_work_ms(facts, "pump_host_work_ms.chat")
