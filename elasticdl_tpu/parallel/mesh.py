"""Device-mesh construction: the TPU-native replacement for the reference's
process topology (N worker pods + M PS pods over gRPC).

Where the reference scales by adding pods, this framework scales by widening a
``jax.sharding.Mesh`` whose named axes carry the parallelism taxonomy
(SURVEY.md §2.5): ``dp`` (data), ``fsdp`` (sharded params over the data axis),
``ep`` (embedding/expert shards — the PS-equivalent axis for sparse tables),
``tp`` (tensor), ``sp`` (sequence/context for ring attention). Elastic
re-formation on membership change = rebuilding the mesh and re-jitting
(reference: FTLib re-init, collective_ops/communicator.py:37-144).
"""

import math

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticdl_tpu.common.constants import MeshAxis
from elasticdl_tpu.common.log_utils import default_logger as logger


def parse_mesh_spec(spec):
    """Parse 'dp=4,ep=2' style mesh specs into an axis-size dict.

    -1 (at most once) means "fill with all remaining devices" — the default
    for dp, which is how elasticity shows up: the same job spec runs on any
    device count.
    """
    sizes = {ax: 1 for ax in MeshAxis.ALL}
    if not spec:
        sizes[MeshAxis.DP] = -1
        return sizes
    seen_fill = False
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        ax, _, val = part.partition("=")
        ax = ax.strip()
        if ax not in sizes:
            raise ValueError(
                "Unknown mesh axis %r (valid: %s)" % (ax, MeshAxis.ALL)
            )
        val = int(val)
        if val == -1:
            if seen_fill:
                raise ValueError("Only one mesh axis may be -1")
            seen_fill = True
        sizes[ax] = val
    if not seen_fill and math.prod(
        v for v in sizes.values()
    ) <= 0:
        raise ValueError("Invalid mesh spec %r" % spec)
    return sizes


def build_mesh(mesh_spec=None, devices=None):
    """Build a Mesh over `devices` (default: all) from a spec string/dict.

    Axes of size 1 are kept in the mesh so PartitionSpecs referencing any
    canonical axis always resolve; XLA treats size-1 axes as free.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if isinstance(mesh_spec, dict):
        sizes = {ax: 1 for ax in MeshAxis.ALL}
        sizes.update(mesh_spec)
    else:
        sizes = parse_mesh_spec(mesh_spec)
    fixed = math.prod(v for v in sizes.values() if v != -1)
    for ax, v in sizes.items():
        if v == -1:
            if n % fixed != 0:
                raise ValueError(
                    "Cannot fill axis %s: %d devices not divisible by %d"
                    % (ax, n, fixed)
                )
            sizes[ax] = n // fixed
    total = math.prod(sizes.values())
    if total != n:
        raise ValueError(
            "Mesh %r needs %d devices but %d are available"
            % (sizes, total, n)
        )
    shape = tuple(sizes[ax] for ax in MeshAxis.ALL)
    if devices[0].platform == "cpu":
        # virtual/CPU device sets have no interconnect to lay out
        dev_array = np.asarray(devices).reshape(shape)
    else:
        # orders devices by ICI adjacency; on an accelerator a topology
        # it cannot lay out is an error, not something to paper over
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    mesh = Mesh(dev_array, MeshAxis.ALL)
    logger.info("Built mesh %s over %d devices", dict(sizes), n)
    return mesh


def batch_sharding(mesh):
    """Input batches shard their leading axis over (dp, fsdp) — fsdp is a
    data-parallel axis for the batch too."""
    return NamedSharding(mesh, P((MeshAxis.DP, MeshAxis.FSDP)))


def replicated(mesh):
    return NamedSharding(mesh, P())


def local_mesh():
    """A 1-device mesh (single-chip / local-executor path)."""
    return build_mesh({MeshAxis.DP: 1}, devices=jax.devices()[:1])


def current_mesh():
    """The Mesh active via `with mesh:` (how model code — e.g. the
    transformer's attention — discovers the sp axis at trace time inside
    the Trainer's compiled step), or None outside any mesh context."""
    # `with mesh:` sets the legacy thread-local resource env, which has
    # no public reader: jax.sharding.get_abstract_mesh() only sees
    # jax.set_mesh / use_mesh contexts, and carries no devices.
    from jax._src.mesh import thread_resources

    mesh = thread_resources.env.physical_mesh
    if mesh is None or mesh.empty:
        return None
    return mesh
