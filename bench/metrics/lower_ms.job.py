"""Operand lowering per job, in ms: the self time of the program's
``repro.<entry>.lower`` spans (building each dispatch's operands on the
host) inside the window's jobs, from ``dispatch.spans()``.  Batch cells
only."""
from bench import program_spans


def read(ctx):
    return program_spans.stage_ms(ctx, ("lower",))
