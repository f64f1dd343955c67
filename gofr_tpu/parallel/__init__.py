"""Device-mesh parallelism: the framework's distributed backbone.

The reference (nidhey27/gofr) has NO parallelism or distributed-comms
machinery (SURVEY §2.10: no DP/TP/PP/SP/EP, no NCCL/MPI — its "distributed"
story is microservices over HTTP/gRPC, pkg/gofr/gofr.go:169-214). For a
TPU-native framework these are first-class: every model in ``gofr_tpu.models``
declares logical sharding rules, this module maps them onto a
``jax.sharding.Mesh``, and XLA/GSPMD inserts the ICI collectives.

Design (TPU-first, scaling-book recipe):
- one canonical mesh with named axes ``("dp", "fsdp", "tp", "sp")`` — data,
  fully-sharded-data, tensor, and sequence parallelism. Unused axes get
  size 1 so a single PartitionSpec vocabulary works at every scale.
- params are placed with ``NamedSharding`` at init; activations are
  constrained with ``with_sharding_constraint``; collectives are never
  hand-written in the model — XLA chooses psum/all-gather/reduce-scatter
  over ICI from the shardings.
- multi-host: ``jax.distributed.initialize`` bridges hosts over DCN; the
  mesh is laid out so TP rides ICI within a host/slice and DP crosses DCN
  (cheap gradient/all-reduce traffic only).
"""

from __future__ import annotations

import re
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec

__all__ = [
    "P",
    "Mesh",
    "NamedSharding",
    "MeshConfig",
    "make_mesh",
    "mesh_shape_for",
    "shard_map",
    "shard_params",
    "shard_like",
    "constrain",
    "specs_from_rules",
    "init_distributed",
    "pad_to_multiple",
]


shard_map = jax.shard_map

AXES = ("dp", "fsdp", "pp", "ep", "tp", "sp")


class MeshConfig:
    """Mesh axis sizes for the canonical 6-axis mesh: data, fully-sharded
    data, pipeline, expert, tensor, and sequence parallelism. Size-1 axes
    cost nothing, so every program shares one PartitionSpec vocabulary."""

    def __init__(self, dp: int = 1, fsdp: int = 1, tp: int = 1, sp: int = 1,
                 pp: int = 1, ep: int = 1) -> None:
        self.dp, self.fsdp, self.pp, self.ep = dp, fsdp, pp, ep
        self.tp, self.sp = tp, sp

    def sizes(self) -> tuple[int, int, int, int, int, int]:
        return (self.dp, self.fsdp, self.pp, self.ep, self.tp, self.sp)

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (f"MeshConfig(dp={self.dp}, fsdp={self.fsdp}, pp={self.pp}, "
                f"ep={self.ep}, tp={self.tp}, sp={self.sp})")


def mesh_shape_for(n_devices: int, *, tp: int | None = None, sp: int = 1,
                   fsdp: int = 1, pp: int = 1, ep: int = 1) -> MeshConfig:
    """Sensible default layout: give TP as many chips as divide evenly
    (it needs the fastest links), the other axes as requested, and let DP
    absorb the rest."""
    fixed = sp * fsdp * pp * ep
    if tp is None:
        tp = 1
        for cand in (8, 4, 2):
            if n_devices % (cand * fixed) == 0:
                tp = cand
                break
    dp = n_devices // (tp * fixed)
    if dp * tp * fixed != n_devices:
        raise ValueError(
            f"mesh dp={dp} fsdp={fsdp} pp={pp} ep={ep} tp={tp} sp={sp} "
            f"does not cover {n_devices} devices"
        )
    return MeshConfig(dp=dp, fsdp=fsdp, pp=pp, ep=ep, tp=tp, sp=sp)


def make_mesh(config: MeshConfig | None = None, *, devices: Sequence | None = None) -> Mesh:
    """Build the canonical 4-axis mesh over the given (default: all) devices.

    Axis order is (dp, fsdp, tp, sp) — outermost to innermost — so the
    innermost axes (tp, sp) land on physically adjacent chips where ICI
    bandwidth is highest; dp crosses slice/host (DCN) boundaries first.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if config is None:
        config = mesh_shape_for(len(devs))
    sizes = config.sizes()
    total = int(np.prod(sizes))
    if total != len(devs):
        raise ValueError(f"mesh sizes {sizes} != {len(devs)} devices")
    grid = np.asarray(devs, dtype=object).reshape(sizes)
    return Mesh(grid, AXES)


def init_distributed(config=None) -> None:
    """Multi-host bring-up: jax.distributed over DCN (the role NCCL/MPI
    bootstrap plays in GPU frameworks; absent in the reference, SURVEY §5).
    Reads coordinator address / process counts from config and is a no-op
    when single-process."""
    coord = None
    num_procs = None
    proc_id = None
    if config is not None:
        coord = config.get("JAX_COORDINATOR_ADDRESS")
        num_procs = config.get("JAX_NUM_PROCESSES")
        proc_id = config.get("JAX_PROCESS_ID")
    if coord:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(num_procs) if num_procs else None,
            process_id=int(proc_id) if proc_id is not None else None,
        )


# ---------------------------------------------------------------------------
# Sharding rules: map pytree paths -> PartitionSpec by regex — declarative,
# the way the reference maps env-config keys to datasource construction
# (container/container.go:117-147).
# ---------------------------------------------------------------------------

def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:  # pragma: no cover - future path kinds
            parts.append(str(p))
    return "/".join(parts)


def specs_from_rules(params: Any, rules: Sequence[tuple[str, PartitionSpec]]) -> Any:
    """Pytree of PartitionSpec: first regex (searched against the
    'a/b/c'-joined tree path) wins; unmatched leaves replicate."""

    def spec_for(path, leaf):
        s = _path_str(path)
        for pat, spec in rules:
            if re.search(pat, s):
                return spec
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params)


def shard_params(params: Any, specs: Any, mesh: Mesh) -> Any:
    """Place a parameter pytree onto the mesh per its spec pytree."""
    return jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        params,
        specs,
    )


def shard_like(tree: Any, spec: PartitionSpec, mesh: Mesh) -> Any:
    """Place every leaf of ``tree`` with one spec (e.g. batch data on dp)."""
    sharding = NamedSharding(mesh, spec)
    return jax.tree.map(lambda leaf: jax.device_put(leaf, sharding), tree)


def constrain(x: Any, spec: PartitionSpec) -> Any:
    """with_sharding_constraint that is a no-op outside a mesh context
    (single-device unit tests, CPU paths). Inside a mesh, errors propagate —
    a typo'd axis or non-divisible dim must fail loudly, not silently
    replicate."""
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def pad_to_multiple(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m
