"""A tiny copy of `matching-mid.solve` driven on the CPU through the whole run
but the look for a chip: a sound run is correct, and each fault the cell
can have, planted under the timed path, makes `correct` false."""
from __future__ import annotations

import pytest

from bench.tests import faults, tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("fault", list(faults.SOLVE))
def test_fault_makes_the_run_incorrect(root, fault, monkeypatch):
    if faults.SOLVE[fault] is not None:
        faults.SOLVE[fault](monkeypatch)
    out = tiny.run(root, "tiny-matching-mid.solve")
    assert out["correct"] is (fault == "none"), out["checks"]
    assert list(out)[-1] == "checks"
    assert all(set(c) == {"value", "limit"} for c in out["checks"].values())
