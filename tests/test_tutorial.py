"""The user tutorial (docs/tutorials/train_on_kubernetes.md) is
executable documentation: every fenced bash block marked `<!-- ci -->`
runs verbatim here, in a scratch directory, against the real CLI and
library. If the tutorial drifts from the code, this fails — the same
contract the reference's CI enforced on its tutorial job scripts
(reference scripts/travis/run_job.sh)."""

import os
import re
import subprocess
import sys

import pytest

# CI drills shard (make test-drills): the sub-5-min per-commit gate excludes this file.
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TUTORIAL = os.path.join(REPO, "docs", "tutorials",
                        "train_on_kubernetes.md")


def _ci_blocks():
    text = open(TUTORIAL).read()
    blocks = re.findall(r"<!-- ci -->\s*```bash\n(.*?)```", text,
                        re.DOTALL)
    assert blocks, "tutorial lost its ci-checked blocks"
    return blocks


def test_tutorial_ci_blocks_run(tmp_path):
    # Load-sensitive (like test_two_process_spmd_train): the blocks
    # spawn 5 jax processes; under heavily parallel pytest invocations
    # the job can outlive the generous ceiling. Passes serially.
    blocks = _ci_blocks()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # the tutorial's relative paths (my_zoo, mnist_data, exported/...)
    # land in the scratch dir; model_zoo/scripts resolve via REPO
    script = "\n".join(
        ["set -euo pipefail",
         "ln -sfn %s/model_zoo model_zoo" % REPO,
         "ln -sfn %s/scripts scripts" % REPO]
        + blocks
    )
    # the blocks pay jax import + first-compile in five separate
    # processes (master, two workers, two python heredocs) — slow under
    # a loaded machine, so the ceiling is generous; a healthy run is
    # ~5 min
    proc = subprocess.run(
        ["bash", "-c", script.replace("python ", sys.executable + " ")],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=2400,
    )
    assert proc.returncode == 0, (
        "tutorial block failed:\nSTDOUT:\n%s\nSTDERR:\n%s"
        % (proc.stdout[-4000:], proc.stderr[-4000:])
    )
    assert "serving OK" in proc.stdout


def test_tutorial_references_exist():
    """Every repo path the tutorial names must exist."""
    text = open(TUTORIAL).read()
    for rel in (
        "manifests/elasticdl-tpu-rbac.yaml",
        "scripts/run_cluster_job_smoke.sh",
        "scripts/validate_job_status.py",
        "tests/test_convergence_parity.py",
        "tests/test_worker_master_integration.py",
        "tests/test_local_elastic_e2e.py",
        "elasticdl_tpu/api/local_executor.py",
        "common/tb_events.py",
        "docs/designs",
        "PERF.md",
        "tests/test_finetune.py",
    ):
        assert rel in text, "tutorial no longer mentions %s" % rel
    assert os.path.exists(os.path.join(REPO, "elasticdl_tpu",
                                       "common", "tb_events.py"))
    for rel in ("manifests/elasticdl-tpu-rbac.yaml",
                "scripts/validate_job_status.py",
                "docs/designs", "PERF.md"):
        assert os.path.exists(os.path.join(REPO, rel)), rel
