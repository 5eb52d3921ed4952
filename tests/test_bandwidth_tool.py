"""tools/bandwidth/measure.py (reference tools/bandwidth — the KVStore
allreduce benchmark): smoke-run both
measurement modes on the suite's virtual mesh and validate the output
contract (finite positive GB/s for the kvstore path and the raw psum)."""
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bandwidth_tool_reports_both_paths():
    proc = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tools", "bandwidth", "measure.py"),
         "--size-mb", "8", "--repeat", "3"],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rates = dict(re.findall(r"(kvstore \w+|xla psum over mesh):\s+"
                            r"([0-9.]+) GB/s", proc.stdout))
    assert "kvstore local" in rates and "xla psum over mesh" in rates, \
        proc.stdout
    for k, v in rates.items():
        assert float(v) > 0, (k, v)
