"""Every paper table/figure function runs through the engine and returns
rows (``python -m repro.figures`` prints them)."""
import pytest

from repro import figures


@pytest.mark.parametrize("fn", figures.ALL, ids=lambda fn: fn.__name__)
def test_figure_returns_rows(fn):
    rows = fn()
    assert rows
    assert all(len(row) == 3 and row[0] for row in rows)
