"""The control comes out not correct: the reference computed with float8
operands and results (the step below the bfloat16 the configurations
state), put in the program's place, against the float32 reference, judged
by the cell's own limits, at ``cfg.smoke()`` size on the CPU.  On the chip
at the cells' own sizes ``bench/calibrate.py`` reads the same numbers.

``mamba2-k5-fedavg``'s control is caught by ``gen_loss_gap`` at its own
size on the chip only (1.39e-3 and up against a limit of 9e-4); at the
sizes a CPU test holds the float8 rounding moves G's loss less (6.5e-5 to
1.5e-3 on three seeds at 32 and 128 tokens), so that cell is not among
these cases (PERF.md, Open questions).
"""
import pytest

from harness import compare
from harness.program import Program, arch_config, config_dict
from harness.runner import SMOKE_TRAFFIC
from harness.spec import cell_names, load_cell
from reference.train import Reference


CAUGHT_AT_TEST_SIZE = [c for c in cell_names() if c != "mamba2-k5-fedavg"]


@pytest.mark.parametrize("name", CAUGHT_AT_TEST_SIZE)
def test_control_is_not_correct(name):
    cell = load_cell(name)
    t = dict(cell.traffic, **SMOKE_TRAFFIC)
    cfg = arch_config(cell.config, smoke=True)
    config = dict(cell.config, config=config_dict(cfg))
    prog = Program(cfg, config, t, 2 ** 31 + 4242)
    n = compare.checked_rounds(t["K"])
    args = (prog.params0(), prog.shards, prog.keys, n)
    truth = Reference(config, t).run(*args)
    control = Reference(config, t, prec="fp8").run(*args)
    values = compare.numbers(control, truth)
    limits = {k: v for k, v in cell.limits.items() if k in values}
    ok, checks = compare.judge(values, limits)
    assert not ok, checks
