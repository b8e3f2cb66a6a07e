"""manifests/ validated by machinery:

* always: YAML parses; the master manifest's labels match the selector
  keys the k8s client generates services against, its args parse with
  the real master argparser, and the RBAC rules cover every verb the
  client code calls;
* when a cluster is reachable (kind/minikube): a server-side dry-run
  apply through scripts/run_cluster_job_smoke.sh (skipped otherwise —
  mirroring the reference's minikube CI job, scripts/travis/run_job.sh).
"""

import os
import shutil
import subprocess

import pytest
import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFESTS = os.path.join(REPO, "manifests")


def _load(name):
    with open(os.path.join(MANIFESTS, name)) as f:
        return list(yaml.safe_load_all(f))


def test_manifests_parse():
    names = sorted(os.listdir(MANIFESTS))
    assert "master-example.yaml" in names
    assert "elasticdl-tpu-rbac.yaml" in names
    for name in names:
        docs = [d for d in _load(name) if d]
        assert docs, name


def test_master_manifest_matches_client_label_contract():
    """The by-hand master pod must carry exactly the labels the k8s
    client selects on (services, watch streams) — validated against the
    client's constants, not by eye."""
    from elasticdl_tpu.common import k8s_client as k8s

    (pod,) = [d for d in _load("master-example.yaml") if d]
    assert pod["kind"] == "Pod"
    labels = pod["metadata"]["labels"]
    assert labels["app"] == k8s.ELASTICDL_APP_NAME
    job_name = labels[k8s.ELASTICDL_JOB_KEY]
    assert labels[k8s.ELASTICDL_REPLICA_TYPE_KEY] == "master"
    assert labels[k8s.ELASTICDL_REPLICA_INDEX_KEY] == "0"
    # the pod name must equal what Client.get_master_pod_name derives,
    # or the master's owner references / TB service selector dangle
    assert pod["metadata"]["name"] == k8s.get_master_pod_name(job_name)


def test_master_manifest_args_parse():
    """The example args must satisfy the real master argparser — a
    manifest drift (renamed flag, missing required arg) fails here, not
    in the cluster."""
    from elasticdl_tpu.common.args import parse_master_args

    (pod,) = [d for d in _load("master-example.yaml") if d]
    (container,) = pod["spec"]["containers"]
    args = parse_master_args(container["args"])
    assert args.model_zoo == "/model_zoo"
    assert args.num_workers == 2


def test_rbac_covers_client_verbs():
    """The RBAC role must allow every operation common/k8s_client.py
    performs (pods create/get/delete/patch/watch, services create/get)."""
    docs = [d for d in _load("elasticdl-tpu-rbac.yaml") if d]
    roles = [d for d in docs if d["kind"] in ("Role", "ClusterRole")]
    assert roles
    allowed = {}
    for role in roles:
        for rule in role.get("rules", []):
            for res in rule.get("resources", []):
                allowed.setdefault(res, set()).update(rule["verbs"])
    for verb in ("create", "get", "delete", "patch", "list", "watch"):
        assert verb in allowed.get("pods", set()), (verb, allowed)
    for verb in ("create", "get"):
        assert verb in allowed.get("services", set()), (verb, allowed)


def test_cluster_dry_run_smoke():
    """Server-side validation against a real (kind/minikube) cluster;
    skipped when no cluster is reachable — the reference ran this level
    in CI only (scripts/travis/run_job.sh:32-45)."""
    if shutil.which("kubectl") is None:
        pytest.skip("kubectl not installed")
    r = subprocess.run(
        [os.path.join(REPO, "scripts", "run_cluster_job_smoke.sh")],
        capture_output=True, text=True, timeout=300,
    )
    if r.returncode == 3:
        pytest.skip("no reachable cluster")
    assert r.returncode == 0, r.stdout + r.stderr
