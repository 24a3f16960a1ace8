"""Without a TPU the benchmark exits non-zero and prints no result."""
import json
import os
import subprocess
import sys

from harness import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_cpu_run_exits_nonzero_with_no_device_metric():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cell = spec.load_benchmark()["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell, "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(doc, dict) and "metrics" in doc), line
