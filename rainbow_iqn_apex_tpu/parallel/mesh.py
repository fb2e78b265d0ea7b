"""Device mesh construction and learner/actor partitioning.

The reference couples 1 learner process + N actor processes through Redis TCP
(SURVEY.md §1).  The TPU-native replacement (north star BASELINE.json:5) makes
one SPMD program own the whole slice — every dispatch in the tree already
goes through the modern ``jax.jit`` + ``NamedSharding`` path (there is no
pmap anywhere; in/out shardings on named meshes, XLA inserts the
collectives):

- a **learner mesh** with axis ``dp``: the learn step runs batch-sharded over
  it (params replicated, XLA inserts the gradient all-reduce over ICI);
- an **actor mesh** with axis ``actor``: batched vector-env inference is
  sharded lane-wise across it;
- weight publish = one device_put of (optionally bf16, or int8-quantized —
  utils/quantize.py) params from the learner mesh to the actor mesh — the
  Redis weight-mailbox replaced by an ICI broadcast.

On a single chip both meshes are the same device and the roles time-multiplex;
on a pod ``Config.learner_devices`` carves the slice.

Remaining mesh work (ROADMAP "Mesh generality"): both meshes are still 1-D —
growing them into a logical 2-D ``(batch, model)`` mesh (a ``model`` axis for
head/embedding sharding, `shard_map` where XLA's sharding inference falls
short) and running the queued batch-512/1024 scaling sweep are the open
items; the jit/NamedSharding migration itself is long done.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def parse_mesh_shape(spec: str) -> List[Tuple[str, int]]:
    """Parse "dp=4,actor=4" into [("dp", 4), ("actor", 4)]."""
    if not spec:
        return []
    out = []
    for part in spec.split(","):
        name, _, num = part.partition("=")
        out.append((name.strip(), int(num)))
    return out


def split_devices(
    devices: Optional[Sequence[jax.Device]] = None, learner_devices: int = 0
) -> Tuple[List[jax.Device], List[jax.Device]]:
    """Carve the device list into (learner, actor) sets.

    learner_devices == 0 means no split: every device plays both roles
    (single-chip and small-slice mode — roles time-multiplex like the
    reference's 1-GPU learner+actor colocated runs).
    """
    devices = list(devices if devices is not None else jax.devices())
    if learner_devices <= 0 or learner_devices >= len(devices):
        return devices, devices
    return devices[:learner_devices], devices[learner_devices:]


def learner_mesh(devices: Sequence[jax.Device]) -> Mesh:
    return Mesh(np.asarray(devices), axis_names=("dp",))


def actor_mesh(devices: Sequence[jax.Device]) -> Mesh:
    return Mesh(np.asarray(devices), axis_names=("actor",))


def batch_sharding(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    """Leading-axis sharding for batches: [B, ...] split across the mesh."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def traced_under(mesh: Mesh, fn):
    """`fn`, traced with `mesh` as the context's abstract mesh: code inside
    can then see (`jax.sharding.get_abstract_mesh()`) that its arrays may be
    split over devices, which a jitted function's tracers do not say.  The
    axes stay `Auto`: partitioning is the compiler's, as without it."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args, **kwargs)

    return wrapped
