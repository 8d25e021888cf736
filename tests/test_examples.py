"""The examples are executable documentation: each runs end to end at a
small size (N = 64, partitions 4 then 2) through every task-flow graph."""

import runpy
import sys
from pathlib import Path

import pytest

EXAMPLES = Path(__file__).resolve().parents[1] / "examples"


@pytest.mark.parametrize("name", ["quickstart", "lu_solve"])
def test_example_runs(name, monkeypatch, capsys):
    path = str(EXAMPLES / f"{name}.py")
    monkeypatch.setattr(sys, "argv", [path, "64", "4", "2"])
    monkeypatch.setattr(sys, "path", list(sys.path))  # the example edits it
    # run main() only: the script's __main__ block would also switch on the
    # persistent compile cache for the rest of this process
    runpy.run_path(path)["main"]()
    out = capsys.readouterr().out
    for graph in ("g1", "g2", "g2p", "g3"):
        assert f"graph {graph} " in out, out
