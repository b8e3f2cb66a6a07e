"""Shared by the chipbench tests: run one cell in a child process the
way the driver does, and make a copy of the benchmark that a test can
add files to."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(workload, seed, trace=0, seconds=1.5, cwd=ROOT, rehearsal=True,
             extra=(), env=None):
    """(exit code, stdout lines, stderr) of one run of the benchmark's
    own command from BENCHMARK.json."""
    with open(os.path.join(cwd, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    command = [sys.executable if c == "python3" else c for c in command]
    argv = command + ["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)]
    if rehearsal:
        argv.append("--rehearsal")
    child_env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    child_env.pop("ELASTICDL_TPU_FORCE_INTERPRET", None)
    child_env.update(env or {})
    proc = subprocess.run(argv + list(extra), cwd=cwd, env=child_env,
                          capture_output=True, text=True, timeout=600)
    return proc.returncode, proc.stdout.strip().splitlines(), proc.stderr


def last_json(lines):
    return json.loads(lines[-1])


def copy_benchmark(dst):
    """BENCHMARK.json and `chipbench/` copied to `dst`; the program is
    linked in, as a checkout would hold it."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(dst, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("elasticdl_tpu", "model_zoo"):
        os.symlink(os.path.join(ROOT, name), os.path.join(dst, name))
    return dst
