"""Each cell's run end to end through the harness, on the CPU at a small
size: set-up, warm-up, the window, the reference check and the result
line. A CPU run reports no metric: no number from another device is
written under a device metric's name."""
import json

import pytest

from bench.harness import runner
from bench.tests import small

SEED = 2**31 + 3


@pytest.mark.parametrize("name", small.CELLS)
def test_rehearsal(name, capsys):
    out = runner.run_cell(name, SEED, 2.0, False, allow_cpu=True, log=print,
                          **small.cell(name))
    runner.print_result(out)
    cap = capsys.readouterr()
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["metrics"] == {}
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "compiles_in_window misses=0 backend=0" in cap.out
    # the numbers compared end stderr, each beside its limit
    tail = cap.err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and " limit " in t for t in tail)
