"""Share of the fleet's dispatched lanes that were padding, in %: dead
lanes over all lanes the ``fleet`` entry dispatched inside the window's
jobs.  Each ``repro.fleet.put`` span carries what its dispatch added to
the entry's ``lanes_total`` and ``padded_lanes_total`` counters (attrs
``lanes`` and ``padded_lanes``), so the sum over the window's spans is the
counters' delta over the window.  None on a program whose spans carry no
lane counts.  Fleet cells."""
from bench import program_spans

ENTRY = "repro.fleet.put"


def puts(ctx) -> list | None:
    """The window's ``repro.fleet.put`` span records that count lanes."""
    spans = program_spans.window_spans(ctx)
    if spans is None:
        return None
    return [r for r in spans
            if r.name == ENTRY and "padded_lanes" in r.attrs] or None


def read(ctx):
    found = puts(ctx)
    if not found:
        return None
    lanes = sum(r.attrs["lanes"] for r in found)
    pad = sum(r.attrs["padded_lanes"] for r in found)
    return 100.0 * pad / (lanes + pad)
