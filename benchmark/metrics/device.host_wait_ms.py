"""Host time per call spent waiting on the device: the program's
``device.phase_a_wait`` (the phase-A survivor scalar read) and
``device.result_wait`` (the blocking read of the verdict planes) spans,
summed over the traced window. Time that could carry overlapped host
work."""

from lib import spans


def read(run):
    return spans.span_ms(run, ["device.phase_a_wait", "device.result_wait"])
