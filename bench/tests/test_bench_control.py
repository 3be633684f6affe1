"""The control, the reference computed in bfloat16 and put in the
program's place, fails a number of every cell at a size a test run holds,
while the program's own readings pass."""
from __future__ import annotations

import pytest

from bench import control
from bench.lib import cell
from bench.tests import tiny

CELLS = [w["name"] for w in
         cell.load_json(cell.ROOT, "BENCHMARK.json")["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_a_number(root, name):
    out = control.readings(tiny.files(root, "tiny-" + name), 2**31 + 9, 1.0)
    limits = out["limits"]
    assert all(out["program"][k] <= limits[k] for k in limits), out
    assert any(out["control"][k] > limits[k] for k in limits), out
