"""The control of ``correct`` fails the cell's limits (at a test size)."""

import pytest

from bench import control
from bench.tests.helpers import tiny_cell


@pytest.mark.parametrize("cell", ["pagerank-s19", "bfs-s19", "serve-c16-s17"])
def test_control_fails_a_limit(cell):
    c = tiny_cell(cell, scale=11)
    got = control.readings(c, 987654321987)
    limits = c["traffic"]["limits"]
    assert got and all(v > limits[k] for k, v in got.items()), (got, limits)
