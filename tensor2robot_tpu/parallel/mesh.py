"""Device mesh + sharding rules: the framework's parallelism backbone.

The reference's only sharded-compute mode is TPU data parallelism via
``TPUEstimator`` + ``CrossShardOptimizer`` (``models/tpu_model_wrapper.py:
50-54,227``), with gRPC parameter servers for async CPU/GPU training. The
TPU-native replacement is a single SPMD program over a
``jax.sharding.Mesh``: batches sharded on the data axes, parameters
replicated (pure DP) or sharded (FSDP/TP), gradients all-reduced by XLA
collectives over ICI — no NCCL/MPI and no wrapper optimizers.

Axes (all optional; size-1 axes cost nothing under GSPMD):

* ``data`` — batch sharding (the reference's cross-shard DP).
* ``fsdp`` — batch *and* parameter sharding (ZeRO-3 style).
* ``model`` — tensor parallelism over hidden dims.
* ``seq`` — sequence/context parallelism (ring attention fan-out).

``jax.distributed.initialize`` handles multi-host process groups; each host
runs this same module and the mesh spans all devices.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = 'data'
FSDP_AXIS = 'fsdp'
MODEL_AXIS = 'model'
SEQ_AXIS = 'seq'

DEFAULT_AXES = (DATA_AXIS, FSDP_AXIS, MODEL_AXIS, SEQ_AXIS)

# The axes a batch's leading dim is sharded over.
BATCH_AXES = (DATA_AXIS, FSDP_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
  """Declarative mesh layout: axis name → size (-1 = all remaining devices)."""

  data: int = -1
  fsdp: int = 1
  model: int = 1
  seq: int = 1

  def axis_sizes(self, num_devices: int) -> Dict[str, int]:
    sizes = {
        DATA_AXIS: self.data,
        FSDP_AXIS: self.fsdp,
        MODEL_AXIS: self.model,
        SEQ_AXIS: self.seq,
    }
    fixed = 1
    wildcard = None
    for name, size in sizes.items():
      if size == -1:
        if wildcard is not None:
          raise ValueError('Only one mesh axis may be -1.')
        wildcard = name
      else:
        fixed *= size
    if wildcard is not None:
      if num_devices % fixed:
        raise ValueError(
            f'{num_devices} devices not divisible by fixed axes {sizes}')
      sizes[wildcard] = num_devices // fixed
    total = int(np.prod(list(sizes.values())))
    if total != num_devices:
      raise ValueError(
          f'Mesh axes {sizes} use {total} devices, have {num_devices}.')
    return sizes

  def create(self, devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    sizes = self.axis_sizes(len(devices))
    names = tuple(sizes.keys())
    shape = tuple(sizes.values())
    # ICI topology note: jax.devices() order keeps physically-adjacent chips
    # adjacent, so the innermost (fastest-varying) axes land on neighbor
    # links. Put `model`/`seq` innermost: their collectives are per-step
    # latency-bound, while `data` all-reduces overlap with compute.
    mesh_devices = np.asarray(devices).reshape(shape)
    return Mesh(mesh_devices, names)


def create_mesh(devices: Optional[Sequence] = None,
                data: int = -1,
                fsdp: int = 1,
                model: int = 1,
                seq: int = 1) -> Mesh:
  return MeshSpec(data=data, fsdp=fsdp, model=model, seq=seq).create(devices)


def create_local_mesh(data: int = -1,
                      fsdp: int = 1,
                      model: int = 1,
                      seq: int = 1) -> Mesh:
  """A mesh over THIS process's devices only (per-host SPMD mode).

  In a multi-process job each host then runs its own replica group:
  batches are host-global, no cross-host collectives are compiled into
  the step, and cross-host agreement (preemption, checkpoint commits,
  liveness) is owned by the control plane
  (``train/distributed_resilience.py``) rather than the data plane. This
  is the layout the 2-process resilience drills run, and the fallback
  for backends whose XLA build cannot execute multi-process programs.
  """
  return MeshSpec(data=data, fsdp=fsdp, model=model,
                  seq=seq).create(jax.local_devices())


def single_device_mesh() -> Mesh:
  return Mesh(np.asarray(jax.devices()[:1]).reshape((1, 1, 1, 1)),
              DEFAULT_AXES)


def mesh_spans_processes(mesh: Mesh) -> bool:
  """Whether ``mesh`` contains devices from more than one process.

  The data-plane test multi-host code paths must branch on — NOT
  ``jax.process_count()``: a per-host mesh in a multi-process job feeds
  host-global batches exactly like a single-process run, while a global
  mesh needs per-process shard assembly.
  """
  return len({d.process_index for d in mesh.devices.flat}) > 1


# ----------------------------------------------- elastic checkpoint views

SAVE_AXIS = 'save'


def participant_devices(participants: Optional[Sequence[int]] = None):
  """All devices belonging to ``participants`` (process indices).

  ``None`` means every process in the job. Order follows
  ``jax.devices()`` (identical on every host), so the save mesh built
  from it is consistent job-wide without communication.
  """
  devices = jax.devices()
  if participants is None:
    return list(devices)
  wanted = set(int(p) for p in participants)
  return [d for d in devices if d.process_index in wanted]


def global_save_mesh(participants: Optional[Sequence[int]] = None) -> Mesh:
  """A 1-D mesh over the participants' devices, used ONLY for payload io.

  Checkpoint writes never run an XLA program over this mesh — it exists
  so each leaf can be expressed as one global ``jax.Array`` whose shards
  are distributed across hosts, letting Orbax's multiprocess writers
  stripe the payload (every host writes its own shards). That makes it
  safe on backends whose XLA build cannot execute cross-process programs
  (array construction and serialization are pure metadata + local
  device_puts).
  """
  devices = participant_devices(participants)
  return Mesh(np.asarray(devices).reshape((len(devices),)), (SAVE_AXIS,))


def save_sharding_for(mesh: Mesh, leaf) -> NamedSharding:
  """IO sharding for one state leaf on the 1-D save mesh.

  The largest dim divisible by the device count is striped over
  ``save``; leaves with no divisible dim (scalars, rng keys, small
  biases) stay replicated — Orbax then writes exactly one copy (the
  replica-0 shard), so small leaves cost one writer, big leaves cost
  every writer 1/N of the bytes.
  """
  n = mesh.devices.size
  shape = tuple(getattr(leaf, 'shape', ()) or ())
  if n <= 1 or not shape:
    return NamedSharding(mesh, P())
  candidates = [(dim, i) for i, dim in enumerate(shape) if dim % n == 0]
  if not candidates:
    return NamedSharding(mesh, P())
  _, idx = max(candidates)
  spec = [None] * len(shape)
  spec[idx] = SAVE_AXIS
  return NamedSharding(mesh, P(*spec))


def build_global_save_view(tree: Any, mesh: Mesh) -> Any:
  """Re-expresses a host-local state tree as global arrays on ``mesh``.

  Used by the sharded checkpoint path when training runs per-host
  replica groups (``create_local_mesh``): every host holds the full
  (replicated, lockstep) state, and this view assigns each host the
  slices it is responsible for WRITING. Each process materializes only
  its addressable shards (``jax.make_array_from_callback`` device_puts
  local slices; no collectives), so a 2-host job writes each striped
  leaf half-and-half. States already sharded over a process-spanning
  mesh (true FSDP on a pod) skip this view and save their arrays
  directly — re-slicing them would force an all-gather.

  Leaves must be HOST data (numpy, post ``device_get``); non-array
  leaves (python ints) pass through for Orbax's aggregate writer.
  """

  def to_global(x):
    arr = np.asarray(x)
    sharding = save_sharding_for(mesh, arr)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx, a=arr: a[idx])

  def view(x):
    if isinstance(x, (int, float)) or x is None:
      return x
    return to_global(x)

  return jax.tree_util.tree_map(view, tree)


def describe_topology(mesh: Optional[Mesh] = None, **extra) -> Dict[str, Any]:
  """The run topology a checkpoint is only valid within.

  Recorded in every checkpoint commit marker
  (``train/checkpoints.py``) and validated on restore: resuming a 2-host
  run on 1 host (or onto a different mesh shape / microbatch config)
  silently misinterprets the saved state, so the mismatch must fail
  loudly instead. ``extra`` carries trainer-level knobs
  (``grad_accum_microbatches``, ``steps_per_dispatch``).
  """
  out: Dict[str, Any] = {
      'process_count': jax.process_count(),
  }
  if mesh is not None:
    out['mesh_shape'] = {name: int(mesh.shape[name])
                         for name in mesh.axis_names}
    out['device_count'] = int(mesh.devices.size)
    out['mesh_spans_processes'] = mesh_spans_processes(mesh)
  out.update({k: v for k, v in extra.items() if v is not None})
  return out


# ---------------------------------------------------------------- shardings


def batch_sharding(mesh: Mesh) -> NamedSharding:
  """Leading dim sharded over (data, fsdp); rest replicated."""
  axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
  return NamedSharding(mesh, P(axes if axes else None))

def stacked_batch_sharding(mesh: Mesh) -> NamedSharding:
  """For ``[K, batch, ...]`` step-groups: dim 1 is the batch dim.

  ``Trainer(steps_per_dispatch=K)`` stacks K batches per dispatch; the
  scan axis (dim 0) stays unsharded, the per-step batch dim shards over
  the usual batch axes.
  """
  axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
  return NamedSharding(mesh, P(None, axes if axes else None))


def replicated(mesh: Mesh) -> NamedSharding:
  return NamedSharding(mesh, P())


def microbatch_split(tree: Any, num_microbatches: int) -> Any:
  """Reshapes batch leaves ``[B, ...]`` → ``[M, B/M, ...]`` for grad accum.

  The microbatch axis (dim 0 after the split) stays UNSHARDED — it is the
  ``lax.scan`` axis of the gradient-accumulation step — while the
  per-microbatch batch dim keeps the normal batch-axis sharding (GSPMD
  propagates it through the reshape; each microbatch still spans the
  data×fsdp axes). This mirrors ``stacked_batch_sharding``'s convention
  for ``steps_per_dispatch`` groups, so ``K`` (scan over host batches)
  and ``M`` (scan over microbatch slices) nest as one program:
  ``[K, B, ...]`` → per-step ``[B, ...]`` → ``[M, B/M, ...]``.

  Runs inside jit (pure reshape, no data movement on the host). ``B``
  must divide by ``num_microbatches``; the error names the offending
  leaf. For sharded batches, ``B / M`` should remain divisible by the
  product of the batch mesh axes or GSPMD inserts a reshard.
  """
  if num_microbatches <= 1:
    return tree

  def split(path, x):
    shape = tuple(x.shape)
    if not shape or shape[0] % num_microbatches:
      raise ValueError(
          f'grad_accum_microbatches={num_microbatches} must divide the '
          f'batch dim; got shape {shape} at '
          f'{jax.tree_util.keystr(path)}.')
    return x.reshape(
        (num_microbatches, shape[0] // num_microbatches) + shape[1:])

  return jax.tree_util.tree_map_with_path(split, tree)


def batch_shardings_for(mesh: Mesh, tree: Any) -> Any:
  """A matching tree of batch shardings for an arbitrary batch pytree."""
  sharding = batch_sharding(mesh)
  return jax.tree_util.tree_map(lambda _: sharding, tree)


def global_batch_size(per_device_batch: int, mesh: Mesh) -> int:
  n = 1
  for axis in BATCH_AXES:
    if axis in mesh.axis_names:
      n *= mesh.shape[axis]
  return per_device_batch * n


def shard_batch(batch: Any, mesh: Mesh, stacked: bool = False) -> Any:
  """Places a batch onto the mesh, sharded on the batch axes.

  Single-process: ``batch`` is the global batch; a plain sharded
  ``device_put``. Multi-host (``jax.process_count() > 1``): each process
  passes its PROCESS-LOCAL shard (fed by per-host file sharding in the
  input pipeline) and the global array is assembled with
  ``jax.make_array_from_process_local_data`` — the reference gets this
  per-host feeding from TPUEstimator's per-host ``input_fn``
  (``utils/tfdata.py:43-66``); feeding a host-global batch on every host
  would silently duplicate data across hosts.

  ``stacked``: the batch is a ``[K, batch, ...]`` step-group
  (``steps_per_dispatch``); shard dim 1 instead of dim 0.
  """
  sharding = stacked_batch_sharding(mesh) if stacked else batch_sharding(mesh)
  # Branch on the MESH spanning processes, not on process_count: a
  # per-host mesh in a multi-process job (the distributed-resilience
  # drills, per-host replica groups) feeds host-global batches exactly
  # like a single-process run.
  if mesh_spans_processes(mesh):
    return jax.tree_util.tree_map(
        lambda x: jax.make_array_from_process_local_data(
            sharding, np.asarray(x)), batch)
  return jax.tree_util.tree_map(
      lambda x: jax.device_put(x, sharding), batch)


# ------------------------------------------------- parameter sharding rules


def fsdp_param_sharding(mesh: Mesh, param) -> NamedSharding:
  """Shards the largest divisible dim over `fsdp`; replicates otherwise.

  The simple ZeRO-3 rule: parameters are split along their biggest axis so
  each device stores 1/fsdp of every weight; XLA inserts the all-gathers.
  """
  fsdp_size = mesh.shape.get(FSDP_AXIS, 1)
  shape = getattr(param, 'shape', ())
  if fsdp_size <= 1 or not shape:
    return replicated(mesh)
  # Largest dim divisible by the fsdp axis size.
  candidates = [(dim, i) for i, dim in enumerate(shape)
                if dim % fsdp_size == 0]
  if not candidates:
    return replicated(mesh)
  _, idx = max(candidates)
  spec = [None] * len(shape)
  spec[idx] = FSDP_AXIS
  return NamedSharding(mesh, P(*spec))


REPLICATED = 'replicated'


def rule_param_sharding(mesh: Mesh, path: str, param,
                        rules) -> Optional[NamedSharding]:
  """First matching (regex, spec) rule → NamedSharding, else None.

  ``rules``: sequence of ``(pattern, spec)`` where ``pattern`` is matched
  (``re.search``) against the parameter's slash-joined tree path and
  ``spec`` is a tuple of axis names / None per dimension — e.g.
  ``(r'fcgrasp/kernel', (None, 'model'))`` column-shards a Dense kernel
  over the tensor-parallel axis (Megatron-style). Axes absent from the
  mesh or not dividing the dim are dropped (replicated on that dim), so
  one rule set serves every mesh layout. A rule naming the same mesh axis
  on two dims is rejected up front (JAX's own error at jit time is
  opaque). ``spec`` may also be the sentinel string ``'replicated'`` to
  pin the param fully replicated — distinct from an all-None tuple, which
  (like a fully degenerated rule) falls through to the default fsdp rule.
  """
  import re

  shape = getattr(param, 'shape', ())
  for pattern, spec in rules:
    if re.search(pattern, path) is None:
      continue
    if isinstance(spec, str):
      if spec != REPLICATED:
        raise ValueError(
            f'Unknown sharding-rule sentinel {spec!r} for pattern '
            f'{pattern!r}; the only string spec is {REPLICATED!r}.')
      return replicated(mesh)
    if len(spec) != len(shape):
      continue
    named = [a for a in spec if a is not None]
    if len(named) != len(set(named)):
      raise ValueError(
          f'Sharding rule {pattern!r} names mesh axis more than once in '
          f'spec {spec!r} (param {path!r}); each mesh axis may shard at '
          'most one dimension.')
    fixed = []
    for dim, axis in zip(shape, spec):
      if (axis is None or axis not in mesh.axis_names or
          mesh.shape.get(axis, 1) <= 1 or dim % mesh.shape[axis]):
        fixed.append(None)
      else:
        fixed.append(axis)
    if not any(fixed):
      # Every requested axis degenerated (absent / size 1 / indivisible):
      # fall through to the default rule instead of pinning the param
      # replicated — otherwise declaring TP rules would silently disable
      # fsdp sharding on non-TP meshes.
      return None
    return NamedSharding(mesh, P(*fixed))
  return None


def state_shardings_for(mesh: Mesh, state: Any, rules=()) -> Any:
  """Sharding tree for a TrainState.

  Per-leaf: a matching model rule (tensor-parallel layouts, see
  :func:`rule_param_sharding`) wins; otherwise the ZeRO-3 fsdp rule;
  otherwise replicated. Models declare rules via
  ``AbstractT2RModel.param_sharding_rules``.
  """
  fsdp_size = mesh.shape.get(FSDP_AXIS, 1)
  rep = replicated(mesh)

  def leaf_sharding(path, leaf):
    if rules:
      name = '/'.join(str(getattr(k, 'key', getattr(k, 'name', k)))
                      for k in path)
      ruled = rule_param_sharding(mesh, name, leaf, rules)
      if ruled is not None:
        return ruled
    if fsdp_size > 1:
      return fsdp_param_sharding(mesh, leaf)
    return rep

  return jax.tree_util.tree_map_with_path(leaf_sharding, state)


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None) -> None:
  """Multi-host process-group init (the reference's TF_CONFIG equivalent)."""
  if jax.process_count() > 1:
    return  # already initialized
  if coordinator_address is None:
    return  # single-host run
  jax.distributed.initialize(
      coordinator_address=coordinator_address,
      num_processes=num_processes,
      process_id=process_id)
