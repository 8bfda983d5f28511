"""The control of `correct` (benchmarks/control.py) at a size a test run
can hold: the float32 reference in the program's place has to come out
as NOT correct, in every mix, on three seeds; in a mix that writes,
over the tables as the writes leave them."""

import os

import pytest

from conftest import REHEARSAL


@pytest.mark.parametrize("workload", ["rehearsal.short",
                                      "rehearsal.power_nds",
                                      "rehearsal.power_nds_h",
                                      "rehearsal.dm_nds"])
def test_float32_reference_is_not_correct(workload):
    from benchmarks import compare, control, generator, run
    spec = run.load_cell(REHEARSAL, workload)
    config = spec["config"]
    try:
        root = run.build_warehouse(config)
    finally:
        run.kill_children()
    mix = generator.load_mix(spec["cell"]["traffic"])
    for seed in (1, 2, 3):
        out = control.control_numbers(config, mix, seed,
                                      os.path.join(root, "raw"))
        ok, checks = compare.verdict(out["numbers"], config["limits"])
        assert not ok, checks
        # it fails by the gap, and by a wide margin over the limit
        assert checks["max_rel_gap"]["value"] > 3 * checks[
            "max_rel_gap"]["limit"]
        assert checks["rows_wrong"]["value"] == 0
