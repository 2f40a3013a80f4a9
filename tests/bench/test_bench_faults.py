"""``correct`` comes out false for every fault a cell can have, planted
under the timed path of a toy-size run on the CPU (``bench/faults.py``).
The ``control`` fault is the reference's own coloring with its conflict
resolution left out, in the program's place.  ``kron10-d1-full`` (a toy
cell only, ``toycells.UNLISTED``) shows each caught on a skewed graph."""
import pytest
from toycells import run_toy, run_toy_subprocess

ONE_CHIP = ["hex128-d1-full", "hex128-d1-box10", "hex64-d2-full",
            "kron10-d1-full"]
FAULTS = ["control", "unchanged", "half", "altered"]
FOUR_CHIP_FAULTS = FAULTS + ["no_exchange"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("workload", ONE_CHIP)
def test_fault_makes_the_run_incorrect(workload, fault):
    line = run_toy(workload, fault=fault)
    assert line["correct"] is False
    assert line["failed"] == line["window"]["checked"] >= 1
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("fault", FOUR_CHIP_FAULTS)
def test_fault_makes_the_four_chip_run_incorrect(fault):
    line = run_toy_subprocess("hex-d1-4chip-full", devices=4, fault=fault)
    assert line["correct"] is False and line["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
