"""What the tests of the open-loop cell share: ``BENCHMARK.json`` read,
and a rehearsal of one cell on the CPU: ``run.py`` (or ``faults.py``) as
a process of its own, what it said, and its last line."""

import json
import os
import re
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def rehearse(workload: str, trace: int, seconds: float = 3,
             seed: int = 3_000_000_021, fault: str = "") -> tuple:
    """(the last line as a dict, the failures the run listed)."""
    script = [os.path.join(BENCH, "faults.py"), fault] if fault else \
        [os.path.join(BENCH, "run.py")]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, *script, "--workload", workload, "--rehearse",
         "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=300).stdout
    lines = out.strip().splitlines()
    said = re.search(r"failures: (\[.*\])$", out, re.M)
    assert said, out[-3000:]
    return json.loads(lines[-1]), said.group(1)
