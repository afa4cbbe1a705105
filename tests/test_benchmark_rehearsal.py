"""The benchmark harness under tier-1: every cell of BENCHMARK.json,
untraced and traced, rehearsed on the CPU at the family's tiny sizes
(``benchmark/run.py --rehearse-cpu``). A package change that breaks the
harness fails here, not on the chip as ``more_failures``. Nothing a
rehearsal prints is a device number; only the shape of the result and
its ``correct`` verdict are asserted."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

# Per-layer metrics a CPU trace cannot read, and why: their readers
# divide by the chip's peak (no peak is looked up in a rehearsal) or
# read events of the Pallas kernels, which route only off-CPU.
_NEEDS_THE_CHIP = ("_roofline_pct", "step_mfu_pct", "flash_", "moe_gmm_",
                   "dsa_index_", "ssd_ms_", "short_conv_ms_")


def _reads_on_cpu(name):
    return not any(part in name for part in _NEEDS_THE_CHIP)


@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_rehearses(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", cell,
         "--seed", "0", "--seconds", "2", "--trace", str(trace),
         "--rehearse-cpu"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=150)
    assert proc.returncode == 0, proc.stderr[-4000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, proc.stderr[-4000:]
    assert result["failed"] == 0
    assert result["rehearsal"]
    if not trace:
        assert set(result["metrics"]) == {
            m["name"] for m in BENCH["end_to_end"]}
        return
    expected = {m["name"] for m in BENCH["per_layer"]
                if cell in m.get("workloads", [cell])
                and _reads_on_cpu(m["name"])}
    assert expected <= set(result["metrics"]), proc.stderr[-4000:]
