"""Copies between host and device per job, in ms: the self time of the
program's ``repro.<entry>.put`` spans (padding, and the host-to-device
copies as far as the host waits for them) and ``repro.<entry>.fetch``
spans (device-to-host copies and the slice to the batch) inside the
window's jobs, from ``dispatch.spans()``.  The re-qualification cell."""
from bench import program_spans


def read(ctx):
    return program_spans.stage_ms(ctx, ("put", "fetch"))
