"""Collective communication over the device mesh.

TPU-native equivalent of the reference Communicator
(src/io/communicator.cc:54-260): the NCCL ring becomes XLA collectives over
ICI, MPI/NcclIdHolder process bootstrap becomes ``jax.distributed``, and the
dedicated comm streams (c1/c2/s) plus the ``wait`` stream-join op disappear —
XLA schedules and overlaps async collectives itself.

A Communicator's ops are *context sensitive*: inside a compiled step that
the Model layer has shard_map'd over the mesh, ``all_reduce`` lowers to
``lax.psum`` on the 'data' axis; outside any mesh context it degrades to the
identity (a world of one), so single-chip scripts run unchanged.

Deprecation boundary: this module is the LEGACY explicit-collective
mechanism. The GSPMD train step (``Model.compile(mesh=...)``) traces the
same step body OUTSIDE any collective context — the identity degradation
above is exactly what lets one body serve both generations — and lets XLA
insert the gradient collectives from ``NamedSharding`` annotations. The
shard_map driver, the pipeline schedules, and sync-BN's in-graph pmeans
still run through here; new sharded code should not add collectives here
(see :func:`partitioner` and docs/distributed.md "One sharding
vocabulary").
"""

from __future__ import annotations

import contextlib
import os

import jax
import jax.numpy as jnp
from jax import lax

from .mesh import make_mesh, MeshConfig

# Axis names currently live inside a shard_map body (set by the Model layer).
_ACTIVE_AXES: list[str] = []


@contextlib.contextmanager
def collective_context(*axis_names):
    """Marks that the code within runs inside shard_map over these axes."""
    _ACTIVE_AXES.extend(axis_names)
    try:
        yield
    finally:
        for _ in axis_names:
            _ACTIVE_AXES.pop()


def active_axis(axis_name: str) -> bool:
    return axis_name in _ACTIVE_AXES


def axis_size(axis_name: str) -> int:
    """Size of a bound mesh axis: a python int at trace time, so
    callers can use it in static control flow."""
    return lax.axis_size(axis_name)


# Mesh axes the BATCH dimension is sharded over inside the current
# shard_map'd step. Cross-replica statistics (sync-BN) must reduce over
# exactly these — not a hardcoded ("data",), which silently computes
# shard-local stats when the batch also shards over 'expert'/'seq' or a
# renamed axis. The Model's step body sets this from its input specs.
_BATCH_SHARD_AXES: list[tuple] = []


@contextlib.contextmanager
def batch_shard_axes(axes):
    """Declare the mesh axes sharding the batch dim for the enclosed
    trace (normally entered by Model's compiled step body)."""
    axes = tuple(axes) if isinstance(axes, (tuple, list)) else (axes,)
    _BATCH_SHARD_AXES.append(axes)
    try:
        yield
    finally:
        _BATCH_SHARD_AXES.pop()


def active_batch_axes() -> tuple:
    """Axes cross-replica batch statistics should reduce over: the
    declared batch-shard axes (default 'data') filtered to those
    actually active."""
    axes = _BATCH_SHARD_AXES[-1] if _BATCH_SHARD_AXES else ("data",)
    return tuple(a for a in axes if a is not None and active_axis(a))


_global_mesh = None


def get_mesh(config: MeshConfig | None = None, devices=None):
    """Process-wide default mesh (built over all visible devices)."""
    global _global_mesh
    if _global_mesh is None or config is not None or devices is not None:
        _global_mesh = make_mesh(devices, config)
    return _global_mesh


def set_mesh(mesh):
    global _global_mesh
    _global_mesh = mesh


def partitioner(mesh=None, batch_axis="data", model_axis="model"):
    """Deprecation-boundary shim onto the ONE sharding vocabulary.

    The communicator's explicit-collective mechanism (shard_map +
    psum/ppermute) stays for the LEGACY training driver and the
    pipeline schedules, but layouts belong to :mod:`.gspmd`: this
    returns the shared :class:`~singa_tpu.parallel.gspmd.Partitioner`
    over the given (or process-default) mesh so code still living on
    this mechanism expresses shardings through the same specs the
    GSPMD train step and serving path use. New sharded code should
    annotate with NamedSharding via gspmd and jit — not add
    hand-rolled collectives here."""
    from .gspmd import Partitioner
    return Partitioner(mesh if mesh is not None else get_mesh(),
                       batch_axis=batch_axis, model_axis=model_axis)


class NcclIdHolder:
    """Parity stub for the reference's NcclIdHolder
    (include/singa/io/communicator.h:69): with jax.distributed the
    coordinator address plays this role."""

    def __init__(self, coordinator_address: str | None = None):
        self.coordinator_address = coordinator_address or \
            os.environ.get("JAX_COORDINATOR_ADDRESS", "localhost:12345")


def init_process(nccl_id: NcclIdHolder | None = None, rank: int = 0,
                 world: int = 1):
    """Multi-host bootstrap (replaces the reference's MPI_Bcast rank
    exchange, communicator.cc:73-103).

    On TPU pods the collectives ride ICI/DCN natively; on the CPU backend
    cross-process collectives need an explicit transport, so gloo is
    enabled (this is what makes the multi-process examples and
    tests runnable on any machine — the reference needs real GPUs+NCCL)."""
    if world > 1:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=(nccl_id or NcclIdHolder()).
            coordinator_address,
            num_processes=world, process_id=rank)


def rescale_batch(manifest, new_world):
    """Data-parallel batch accounting across an elastic restart.

    A checkpoint's manifest (``DistributedCheckpointManager`` commit
    marker) records the world size it was saved at plus, when the
    caller provided them, ``per_replica_batch`` / ``global_batch``. On
    resume at a different world size the invariant kept is the
    PER-REPLICA batch — each surviving host keeps its compiled step and
    its memory footprint — so the global batch scales with the world:
    ``global = per_replica * new_world``. Returns ``(per_replica,
    new_global)`` (``(None, None)`` when the manifest carries no batch
    info). Callers that instead want fixed global batch semantics can
    derive ``per_replica = global // new_world`` themselves; that
    changes the compiled step shape, which is why it is not the default.
    """
    saved_world = max(1, int(manifest.get("world", 1)))
    per = manifest.get("per_replica_batch")
    if per is None:
        gb = manifest.get("global_batch")
        if gb is None:
            return None, None
        per = max(1, int(gb) // saved_world)
    return int(per), int(per) * int(new_world)


def replica_fingerprint(arrays, axis_name="data"):
    """In-graph cross-replica parameter fingerprint (integrity layer).

    Each replica reduces every array to two cheap scalars — sum and
    sum-of-squares in f32 — stacks them into one small vector, and
    all-gathers that vector over ``axis_name``. On healthy hardware the
    gathered rows are IDENTICAL (data-parallel params are replicated
    and every replica ran the same program); a row that differs is
    silent data corruption or a non-deterministic kernel on that
    replica. Returns ``(gathered, agree)``: ``gathered`` has shape
    ``(axis_size, 2 * len(arrays))`` and ``agree`` is a scalar bool
    (all rows BITWISE-equal the first — the vectors are compared as
    int32 bit patterns, so identical computations agree even through a
    NaN, and SDC does not need a large epsilon to be seen). Outside a
    mesh context (or on an inactive axis) there is nothing to compare
    with: the local vector comes back with ``agree=True``.

    Cost: one tiny all-gather of ``2 * n_params`` f32 scalars riding
    the step's existing collectives — cheap enough to run on a cadence.
    Limitation of the lossy reduction: two replicas whose sums both
    saturate (e.g. to the same inf) from DIFFERENT values compare
    equal; the host-side counterpart for cross-PROCESS agreement —
    :func:`singa_tpu.integrity.state_fingerprint` over the cluster
    control plane — digests every byte and has no such blind spot."""
    parts = []
    for a in arrays:
        x = jnp.asarray(getattr(a, "data", a)).astype(jnp.float32)
        parts.append(jnp.sum(x))
        parts.append(jnp.sum(x * x))
    vec = jnp.stack(parts) if parts else jnp.zeros((0,), jnp.float32)
    if active_axis(axis_name):
        gathered = lax.all_gather(vec, axis_name)
        # bitwise comparison: float == would call bit-identical NaN
        # rows "divergent" (NaN != NaN) on perfectly healthy replicas
        bits = lax.bitcast_convert_type(gathered, jnp.int32)
        agree = jnp.all(bits == bits[0:1])
        return gathered, agree
    return vec[None], jnp.asarray(True)


class Communicator:
    """All-reduce (and friends) over the mesh 'data' axis.

    Reference op mapping (src/io/communicator.cc):
      synch            -> all_reduce (lax.psum)
      fusedSynch       -> unnecessary (XLA fuses/overlaps collectives)
      synchHalf        -> all_reduce of a bf16-cast value (DistOpt does it)
      sparsification   -> masked dense psum (DistOpt does it)
      wait             -> unnecessary (async collectives are data-flow
                          ordered by XLA)
    """

    def __init__(self, axis_name: str = "data", world_size=None,
                 mesh=None, reduce_axes=None):
        self.axis_name = axis_name
        # axes gradients are summed over: the data axis plus any other
        # batch-like axis (sequence parallelism splits the token batch, so
        # 'seq' joins the reduction there)
        self.reduce_axes = tuple(reduce_axes) if reduce_axes is not None \
            else (axis_name,)
        self.mesh = mesh
        self.local_rank = jax.process_index()
        self.global_rank = jax.process_index()
        if world_size is None:
            world_size = jax.device_count()
        self.world_size = int(world_size)

    def _active_reduce_axes(self, exclude=()):
        return tuple(a for a in self.reduce_axes
                     if active_axis(a) and a not in exclude)

    def effective_world_size(self, exclude=()):
        """Replica count actually participating in the current context.
        ``exclude``: axes a parameter is SHARDED over (its per-shard values
        are distinct, not replicas — e.g. expert weights on 'expert')."""
        axes = self._active_reduce_axes(exclude)
        size = 1
        for a in axes:
            size *= axis_size(a)
        return size

    # -- collectives (identity outside a mesh context) ---------------------
    def all_reduce(self, arr, exclude=()):
        axes = self._active_reduce_axes(exclude)
        if axes:
            return lax.psum(arr, axes)
        return arr

    def all_gather(self, arr, axis=0):
        if active_axis(self.axis_name):
            return lax.all_gather(arr, self.axis_name, axis=axis,
                                  tiled=True)
        return arr

    def reduce_scatter(self, arr, axis=0):
        if active_axis(self.axis_name):
            return lax.psum_scatter(arr, self.axis_name,
                                    scatter_dimension=axis, tiled=True)
        return arr

    def broadcast(self, arr, root=0):
        if active_axis(self.axis_name):
            mask = (lax.axis_index(self.axis_name) == root)
            return lax.psum(jnp.where(mask, arr, jnp.zeros_like(arr)),
                            self.axis_name)
        return arr

    def ppermute(self, arr, perm):
        if active_axis(self.axis_name):
            return lax.ppermute(arr, self.axis_name, perm)
        return arr

    def rank(self):
        if active_axis(self.axis_name):
            return lax.axis_index(self.axis_name)
        return 0

    def wait(self):
        """Parity no-op (reference communicator.cc:169-186): XLA's async
        collectives are ordered by data flow, not stream joins."""
