"""The correctness check's control, on the chip: the plain reference
computed at the next precision below the configuration's (``"high"``,
three bf16 passes, for float32 at ``"highest"``) and put in the
program's place has to come out not correct, while the program, on
the same requests, comes out correct.  Three seeds per cell, at the
cell's own size and load; needs a TPU (it skips elsewhere):

    python3 -m pytest benchmarks/chip/tests/test_control.py
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(ROOT, "src"))

SEEDS = (2 ** 32 + 5, 2 ** 32 + 6, 2 ** 32 + 7)


def _workloads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def _over(numbers, limits):
    return sorted(k for k, v in numbers.items() if v > limits[k])


@pytest.fixture(scope="module")
def chip():
    import jax
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the control is read on a TPU")
    import run
    run.enable_compile_cache()


@pytest.mark.parametrize("workload", _workloads())
def test_control_fails_and_program_passes(chip, workload):
    import calibrate
    import check
    import harness
    cell = harness.load_cell(workload)
    limits = check.checks(cell.config)["limits"]
    for seed in SEEDS:
        r = calibrate.readings(cell, seed, 2.0)
        assert not _over(r["program"], limits), r
        assert _over(r["control"], limits), r
