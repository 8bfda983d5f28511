"""The benchmark's own tests run on the CPU, at SF0.01, through the
rehearsal cells of benchmarks/tests/rehearsal/BENCHMARK.json:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They are not part of the repository's tier-1 suite (tests/)."""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"       # before any jax import

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

REHEARSAL = os.path.join(HERE, "rehearsal", "BENCHMARK.json")
CELLS = ("rehearsal.short", "rehearsal.power_nds", "rehearsal.power_nds_h")


@pytest.fixture
def run_cell(capsys):
    """Drive one run of a rehearsal cell in this process; returns the
    exit code and the result line as a dict."""
    from benchmarks import run

    def go(workload, seed=7, seconds=0.3, trace=0, tamper=None):
        capsys.readouterr()
        rc = run.main(["--benchmark", REHEARSAL, "--workload", workload,
                       "--seed", str(seed), "--seconds", str(seconds),
                       "--trace", str(trace)], tamper=tamper)
        out = capsys.readouterr()
        lines = [ln for ln in out.out.splitlines() if ln.strip()]
        return rc, (json.loads(lines[-1]) if lines else None), out.err

    return go
