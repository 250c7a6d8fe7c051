"""The tests' own four-chip cell, whole, on four of the virtual CPU
devices."""

from tiny_cells import check_line, go


def test_realloc_cell_traced(tmp_path):
    """The four-chip layout on four of the virtual CPU devices: trained
    d2t2, generated on a d4t1 replica, resharded every step; both
    layouts are held to the reference."""
    out = go("tiny.grpo-realloc", True, tmp_path)
    check_line(out, trace=True)
    assert out["metrics"]["reshard.s"]["value"] > 0
    assert out["device"]["count"] >= 4
