"""run.py refuses to run without the chips its cell asks for, and
outside a checkout, and then prints no result."""
import json
import os
import shutil
import subprocess
import sys

from chipbench import harness

RUN = harness.HERE / "run.py"
ARGS = ["--workload", "danube-chat", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _no_result(stdout: str) -> bool:
    for line in stdout.strip().splitlines()[-1:]:
        try:
            json.loads(line)
            return False
        except ValueError:
            pass
    return True


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_exits_nonzero_without_a_tpu():
    p = subprocess.run([sys.executable, str(RUN)] + ARGS, cwd=harness.ROOT,
                       env=_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_exits_nonzero_in_a_bare_directory(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py"] + ARGS,
                       cwd=tmp_path, env=_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert _no_result(p.stdout)
