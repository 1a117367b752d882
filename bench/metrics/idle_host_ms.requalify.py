"""Device idle under host work per job, in ms: the device-idle time
inside ``bench.window`` whose innermost program span is a host stage
(anything but ``repro.<entry>.dispatch`` and ``.compile``), with the
program's spans moved onto the trace's clock (``bench/program_spans.py``).
Host work that overlaps device work does not count.  The
re-qualification cell."""
from bench import program_spans


def read(ctx):
    return program_spans.idle_host_ms(ctx)
