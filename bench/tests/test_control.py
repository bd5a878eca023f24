"""The control comes out not correct: the reference computed with float8
operands and results (the step below the bfloat16 the configurations
state), put in the program's place, against the float32 reference, judged
by the cell's own limits, at ``cfg.smoke()`` size on the CPU.  On the chip
at the cells' own sizes ``bench/calibrate.py`` reads the same numbers.

A cell whose control is caught at its own size on the chip only, because
at the sizes a CPU test holds the float8 rounding moves its numbers less,
brings ``data/control_on_chip/<cell>.json``: the compared numbers of the
program and of the control on each seed that ``bench/calibrate.py`` read
on the chip, the count of those seeds on which the cell's limits catch
the control, and where the readings come from.  Such a cell runs no CPU
case here; its record is judged by the cell's limits instead: every
program reading must pass, and the control must be caught on at least
the recorded count of seeds, which must be most of them.  A limit that
fails the program or lets the control pass on more seeds then fails.
"""
import json
import os

import pytest

from harness import compare
from harness.program import Program, arch_config, config_dict
from harness.runner import SMOKE_TRAFFIC
from harness.spec import cell_names, load_cell
from reference.train import Reference

ON_CHIP = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "control_on_chip")
CAUGHT_ON_CHIP_ONLY = sorted(f[:-len(".json")] for f in os.listdir(ON_CHIP)
                             if f.endswith(".json"))
CAUGHT_AT_TEST_SIZE = [c for c in cell_names()
                       if c not in CAUGHT_ON_CHIP_ONLY]


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


@pytest.mark.parametrize("name", CAUGHT_ON_CHIP_ONLY)
def test_chip_only_control_is_on_record(name):
    with open(os.path.join(ON_CHIP, name + ".json")) as f:
        record = json.load(f)
    assert set(record) == {"seeds", "program", "control", "control_caught",
                           "origin"}
    assert record["origin"].strip()
    seeds = record["seeds"]
    assert len(set(seeds)) == len(record["program"]) == \
        len(record["control"]) == len(seeds)
    limits = load_cell(name).limits

    def correct(values):
        return compare.judge(
            values, {k: x for k, x in limits.items() if k in values})[0]

    failing = [s for s, v in zip(seeds, record["program"]) if not correct(v)]
    assert not failing, ("program not correct on", failing)
    caught = sum(not correct(v) for v in record["control"])
    assert 2 * record["control_caught"] > len(seeds), record["control_caught"]
    assert caught >= record["control_caught"], (caught, len(seeds))
