"""The measurement path refuses anything that is not a TPU."""
import json
import os
import subprocess
import sys

import pytest

from bench import cells, session


def test_require_chips_refuses_the_cpu():
    with pytest.raises(session.NoChip, match="TPU"):
        session.require_chips(1)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_run_exits_nonzero_without_a_result(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", "darknet19-saturate", "--seed", "3", "--seconds", "1",
         "--trace", trace],
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3, p.stderr
    assert "TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
