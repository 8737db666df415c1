"""Model export: versioned serving artifacts + best/latest exporters.

Capability-equivalent of the reference's export stack
(``export_generators/``, ``utils/train_eval.py:206-361``,
``hooks/checkpoint_hooks.py``): the trainer writes timestamp-versioned
export directories that a robot-side predictor polls and hot-reloads.

An export directory ``<export_root>/<version>/`` contains:

* ``state/`` — Orbax checkpoint of the serving variables (EMA params when
  enabled — the reference's swapping-saver capability).
* ``assets.extra/t2r_assets.pbtxt`` (+ JSON twin) — feature/label specs and
  global_step (``hooks/async_export_hook_builder.py:66-88``).
* ``serving_fn.jax_export`` — the SELF-CONTAINED serving function
  (preprocessing + forward + export outputs) serialized with
  ``jax.export`` (StableHLO). This is the SavedModel-GraphDef equivalent:
  a robot host deserializes and calls it with only jax installed — no
  model class, no training script
  (``export_generators/default_export_generator.py:47-87``: preprocessing
  inside the serving graph).
* ``assets.extra/warmup_requests.npz`` + ``warmup_requests.tfexamples`` —
  spec-shaped warmup inputs, as numpy and as serialized tf.Example bytes
  (``abstract_export_generator.py:114-147``).
* ``export_meta.json`` — model class path + global step; the model-class
  fallback path for predictors when the StableHLO artifact is absent.

Versions are numeric timestamps exactly like SavedModel export dirs, and
old versions are GC'd to N newest (``hooks/checkpoint_hooks.py:36-53``).
"""

from __future__ import annotations

import importlib
import json
import logging
import os
import shutil
import struct
import time
from typing import Any, Callable, Dict, List, Mapping, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp

from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.observability import metrics as metrics_lib
from tensor2robot_tpu.specs import algebra
from tensor2robot_tpu.specs import assets as assets_lib
from tensor2robot_tpu.specs import numpy_gen
from tensor2robot_tpu.specs.spec_struct import SpecStruct

EXPORT_META_FILENAME = 'export_meta.json'
STATE_DIRNAME = 'state'
SERVING_FN_FILENAME = 'serving_fn.jax_export'
WARMUP_NPZ_FILENAME = 'warmup_requests.npz'
WARMUP_EXAMPLES_FILENAME = 'warmup_requests.tfexamples'
# Written LAST into every export version: a version dir without it is a
# torn/partial export (a copy or move that died mid-flight) and hot-
# reloading predictors must skip it. The local os.replace publish is
# already atomic — the marker is the cross-filesystem/rsync-era guard
# mirroring the checkpoint commit protocol (train/checkpoints.py).
EXPORT_COMMIT_FILENAME = 'export_commit.json'
# Persisted exporter position (export root, not version): survives a
# preemption so the restarted trainer/evaluator skips already-exported
# checkpoints instead of re-exporting them.
EXPORT_STATE_FILENAME = 'export_state.json'


def to_plain_tree(obj):
  """Mappings → plain dicts (stable pytree structure for jax.export)."""
  if isinstance(obj, Mapping):
    return {k: to_plain_tree(v) for k, v in obj.items()}
  return obj


def build_serving_fn(model):
  """The hermetic PREDICT chain: preprocess → network → export outputs.

  Takes/returns PLAIN dicts so the serialized calling convention doesn't
  depend on framework pytree types.
  """
  preprocessor = model.preprocessor

  def serving_fn(variables, features):
    features_p, _ = preprocessor.preprocess(
        SpecStruct(features), None, ModeKeys.PREDICT, None)
    outputs, _ = model.inference_network_fn(
        dict(variables), features_p, None, ModeKeys.PREDICT)
    return dict(model.create_export_outputs_fn(features_p, outputs))

  return serving_fn


def serialize_serving_fn(model, serving_variables,
                         batch_size: Optional[int] = None) -> bytes:
  """Serializes the serving fn with ``jax.export`` (StableHLO).

  ``batch_size=None`` exports a symbolic batch dimension (the reference's
  unknown-batch serving signature, ``README.md:180-184``); pass an int to
  pin it if a model's preprocessing can't trace symbolically.
  """
  from jax import export as jax_export

  serving_fn = build_serving_fn(model)
  in_spec = algebra.filter_required_flat_tensor_spec(
      model.preprocessor.get_in_feature_specification(ModeKeys.PREDICT))
  if batch_size is None:
    (batch,) = jax_export.symbolic_shape('b')
  else:
    batch = int(batch_size)
  feature_args = {
      key: jax.ShapeDtypeStruct((batch,) + tuple(spec.shape), spec.dtype)
      for key, spec in in_spec.items()
  }
  var_args = jax.tree_util.tree_map(
      lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
      to_plain_tree(serving_variables))
  # cpu + tpu: robots serve on CPU hosts, servers on TPU.
  platforms = sorted({'cpu', jax.default_backend()} | {'tpu'})
  try:
    exported = jax_export.export(
        jax.jit(serving_fn), platforms=platforms)(var_args, feature_args)
  except Exception as e:
    # Some lowering rules are platform-gated; fall back to the current
    # one — counted, so a check can tell the portable artifact from this.
    metrics_lib.counter('export/serving_fn_single_platform').inc()
    logging.warning(
        'Multi-platform serving export (platforms=%s) failed; retrying for '
        'the current backend only — the artifact will NOT be portable '
        'across platforms. Original error: %r', platforms, e)
    exported = jax_export.export(jax.jit(serving_fn))(var_args, feature_args)
  return exported.serialize()


def serving_program_fingerprint(exported) -> str:
  """Canonical digest of an ``Exported``'s PROGRAM (not its bytes).

  ``Exported.serialize()`` embeds MLIR ``loc(...)`` debug locations —
  call-site file:line that drifts between otherwise identical exports —
  so hashing the raw artifact makes every export version look like a new
  program and defeats serving-executable cache reuse on weights-only
  hot swaps. Hashing the location-stripped module text is stable:
  equal fingerprints <=> same compute program, only weights differ.
  """
  import hashlib
  import re

  text = exported.mlir_module()
  text = re.sub(r'(?m)^#loc.*$', '', text)  # "#locN = loc(...)" defs
  text = re.sub(r'loc\([^)]*\)', '', text)  # trailing "loc(#locN)" refs
  return hashlib.sha256(text.encode()).hexdigest()


def write_warmup_requests(export_dir: str,
                          model,
                          batch_size: int = 1,
                          num_requests: int = 2) -> None:
  """Spec-shaped warmup inputs (abstract_export_generator.py:114-147).

  Written both as an ``.npz`` of numpy feature dicts (suffix ``/<i>``)
  and as length-prefixed serialized tf.Example records, so robot hosts
  can warm up either receiver path.
  """
  in_spec = algebra.filter_required_flat_tensor_spec(
      model.preprocessor.get_in_feature_specification(ModeKeys.PREDICT))
  assets_dir = os.path.join(export_dir, assets_lib.EXTRA_ASSETS_DIRECTORY)
  os.makedirs(assets_dir, exist_ok=True)
  arrays = {}
  example_records: List[bytes] = []
  for i in range(num_requests):
    features = numpy_gen.make_random_numpy(
        in_spec, batch_size=batch_size, seed=i)
    for key, value in features.items():
      arrays[f'{key}/{i}'] = value
    try:
      from tensor2robot_tpu.data import example_codec

      for b in range(batch_size):
        single = SpecStruct(
            {k: np.asarray(v)[b] for k, v in features.items()})
        example_records.append(
            example_codec.encode_example(in_spec, single))
    except Exception:
      pass  # TF host lib unavailable: npz warmup only
  np.savez(os.path.join(assets_dir, WARMUP_NPZ_FILENAME), **arrays)
  if example_records:
    with open(os.path.join(assets_dir, WARMUP_EXAMPLES_FILENAME), 'wb') as f:
      for record in example_records:
        f.write(struct.pack('<Q', len(record)))
        f.write(record)


def read_warmup_examples(export_dir: str) -> List[bytes]:
  """Reads the length-prefixed serialized warmup examples."""
  path = os.path.join(export_dir, assets_lib.EXTRA_ASSETS_DIRECTORY,
                      WARMUP_EXAMPLES_FILENAME)
  records = []
  with open(path, 'rb') as f:
    while True:
      header = f.read(8)
      if len(header) < 8:
        break
      (length,) = struct.unpack('<Q', header)
      records.append(f.read(length))
  return records


def _numeric_version_dirs(export_root: str) -> List[str]:
  """All numeric-named child dirs, oldest → newest (predictor contract)."""
  try:
    entries = os.listdir(export_root)
  except FileNotFoundError:
    return []
  versions = [e for e in entries if e.isdigit() and
              os.path.isdir(os.path.join(export_root, e))]
  return sorted(versions, key=int)


def valid_export_dirs(export_root: str) -> List[str]:
  """Versions whose contents are complete (assets + state + meta).

  The validation-before-load contract of
  ``exported_savedmodel_predictor.py:258-274``.
  """
  valid = []
  for version in _numeric_version_dirs(export_root):
    path = os.path.join(export_root, version)
    if not os.path.exists(os.path.join(
        path, assets_lib.EXTRA_ASSETS_DIRECTORY,
        assets_lib.T2R_ASSETS_FILENAME)):
      continue
    if not os.path.exists(os.path.join(path, EXPORT_META_FILENAME)):
      continue
    if not os.path.isdir(os.path.join(path, STATE_DIRNAME)):
      continue
    valid.append(path)
  return valid


# Torn export versions already counted/warned about, so a hot-reload
# poller (the serving plane polls every reload interval) logs and counts
# each torn dir ONCE instead of once per poll.
_reported_torn_exports: set = set()


def committed_export_dirs(export_root: str,
                          dirs: Optional[List[str]] = None) -> List[str]:
  """Filters export version dirs to COMMITTED ones (legacy-aware).

  Once any version carries :data:`EXPORT_COMMIT_FILENAME`, versions
  without it are torn/partial (a copy that died mid-flight) and are
  skipped with an ``export/uncommitted_skipped`` count; marker-less
  legacy roots (exports written before the marker existed) stay fully
  visible so old artifacts keep serving.
  """
  if dirs is None:
    dirs = valid_export_dirs(export_root)
  marked = [d for d in dirs
            if os.path.exists(os.path.join(d, EXPORT_COMMIT_FILENAME))]
  if not marked:
    return dirs
  torn = [d for d in dirs if d not in marked
          and d not in _reported_torn_exports]
  if torn:
    _reported_torn_exports.update(torn)
    metrics_lib.counter('export/uncommitted_skipped').inc(len(torn))
    logging.warning(
        'Ignoring %d export version(s) under %r without a commit marker '
        '(torn/partial export): %s', len(torn), export_root,
        [os.path.basename(d) for d in torn])
  return marked


def read_export_state(export_root: str) -> Dict[str, Any]:
  """The persisted exporter position, or {} (missing/corrupt file)."""
  try:
    with open(os.path.join(export_root, EXPORT_STATE_FILENAME)) as f:
      return dict(json.load(f))
  except (OSError, ValueError, TypeError):
    return {}


def write_export_state(export_root: str, **updates) -> None:
  """Atomically merges ``updates`` into the persisted exporter state."""
  os.makedirs(export_root, exist_ok=True)
  state = read_export_state(export_root)
  state.update(updates)
  path = os.path.join(export_root, EXPORT_STATE_FILENAME)
  tmp = f'{path}.tmp{os.getpid()}'
  with open(tmp, 'w') as f:
    json.dump(state, f, indent=2)
  os.replace(tmp, path)


def gc_export_versions(export_root: str, keep: int = 5) -> None:
  """Keeps the N newest versions (``_DirectoryVersionGC``, checkpoint_hooks)."""
  versions = _numeric_version_dirs(export_root)
  for version in versions[:-keep] if keep else versions:
    shutil.rmtree(os.path.join(export_root, version), ignore_errors=True)


class ModelExporter:
  """Writes one export version from a trainer state.

  ``serialize_serving`` controls whether the self-contained StableHLO
  serving fn + warmup requests are written (slower export; on by default).
  ``serving_batch_size=None`` exports a symbolic batch dim.
  """

  def __init__(self,
               keep: int = 5,
               serialize_serving: bool = True,
               serving_batch_size: Optional[int] = None,
               warmup_batch_size: int = 1,
               saved_model: bool = False):
    self._keep = keep
    self._serialize_serving = serialize_serving
    self._serving_batch_size = serving_batch_size
    self._warmup_batch_size = warmup_batch_size
    self._saved_model = saved_model
    self._checkpointer = ocp.StandardCheckpointer()

  def export(self, model, state, export_root: str,
             version: Optional[int] = None) -> str:
    """Writes ``<export_root>/<version>`` and returns its path."""
    os.makedirs(export_root, exist_ok=True)
    if version is None:
      version = int(time.time() * 1e6)  # microseconds: unique + ordered
    final_dir = os.path.join(export_root, str(version))
    tmp_dir = os.path.join(export_root, f'.tmp_{version}')
    if os.path.exists(tmp_dir):
      shutil.rmtree(tmp_dir)
    os.makedirs(tmp_dir)

    # 1. Serving variables (EMA when enabled).
    serving_variables = jax.device_get(dict(state.eval_variables))
    self._checkpointer.save(
        os.path.abspath(os.path.join(tmp_dir, STATE_DIRNAME)),
        serving_variables)
    self._checkpointer.wait_until_finished()

    # 2. Specs + global step.
    feature_spec = model.get_feature_specification_for_packing(
        ModeKeys.PREDICT)
    label_spec = model.get_label_specification_for_packing(ModeKeys.PREDICT)
    assets_lib.write_assets_to_export_dir(
        tmp_dir, feature_spec, label_spec, global_step=int(state.step))

    # 3. Self-contained serving fn + warmup requests.
    serving_fn_ok = False
    if self._serialize_serving:
      try:
        data = serialize_serving_fn(
            model, serving_variables, batch_size=self._serving_batch_size)
        with open(os.path.join(tmp_dir, SERVING_FN_FILENAME), 'wb') as f:
          f.write(data)
        serving_fn_ok = True
      except Exception as e:
        # The model-class-import fallback still works, but the export is
        # no longer the self-contained artifact the serving contract
        # advertises (README §Serving contract) — say so loudly.
        logging.warning(
            'Self-contained StableHLO serving export FAILED for %s; the '
            'export degrades to the model-class fallback (predictors must '
            'import %s.%s). Recorded as self_contained_serving_fn=false in '
            'export_meta.json. Error: %r',
            type(model).__name__, type(model).__module__,
            type(model).__qualname__, e)
      try:
        write_warmup_requests(
            tmp_dir, model, batch_size=self._warmup_batch_size)
      except Exception as e:
        # Warmup is best-effort; never abort the export for it.
        logging.warning('Warmup request generation failed: %r', e)

    # 3.5. TF-Serving-consumable SavedModel (saved_model.pb + variables/ +
    # Servo warmup) in the same version dir. Best-effort like warmup: the
    # StableHLO artifact remains the primary serving contract.
    saved_model_ok = False
    if self._saved_model:
      try:
        from tensor2robot_tpu.export import savedmodel as savedmodel_lib

        savedmodel_lib.write_saved_model(
            model, serving_variables, tmp_dir,
            warmup_batch_sizes=(self._warmup_batch_size,))
        saved_model_ok = True
      except Exception as e:
        logging.warning(
            'TF SavedModel export failed for %s; the version still carries '
            'the StableHLO serving artifact. Error: %r',
            type(model).__name__, e)
        # A failure AFTER tf.saved_model.save would otherwise publish a
        # loadable saved_model.pb (consumers key on file presence) that
        # the meta records as failed — remove the partial artifact.
        for name in ('saved_model.pb', 'fingerprint.pb', 'variables',
                     'assets'):
          partial = os.path.join(tmp_dir, name)
          if os.path.isdir(partial):
            shutil.rmtree(partial, ignore_errors=True)
          elif os.path.exists(partial):
            os.remove(partial)

    # 4. Reconstruction metadata.
    meta = {
        'model_class': f'{type(model).__module__}.{type(model).__qualname__}',
        'global_step': int(state.step),
        'self_contained_serving_fn': serving_fn_ok,
        'tf_saved_model': saved_model_ok,
    }
    with open(os.path.join(tmp_dir, EXPORT_META_FILENAME), 'w') as f:
      json.dump(meta, f, indent=2)

    # 5. Commit marker, written LAST: a version dir missing it is a torn
    # export and hot-reloading predictors skip it (the local rename
    # below is atomic; the marker survives non-atomic replication).
    with open(os.path.join(tmp_dir, EXPORT_COMMIT_FILENAME), 'w') as f:
      json.dump({'global_step': int(state.step), 'time': time.time()}, f)
      f.flush()
      os.fsync(f.fileno())

    # Atomic publish: predictors never observe partial exports.
    os.replace(tmp_dir, final_dir)
    if self._keep:
      gc_export_versions(export_root, keep=self._keep)
    return final_dir


def load_model_from_export_dir(export_dir: str,
                               model_kwargs: Optional[Dict[str, Any]] = None):
  """Rebuilds the model object recorded in export_meta.json."""
  with open(os.path.join(export_dir, EXPORT_META_FILENAME)) as f:
    meta = json.load(f)
  module_name, _, class_name = meta['model_class'].rpartition('.')
  module = importlib.import_module(module_name)
  model_cls = getattr(module, class_name)
  return model_cls(**(model_kwargs or {}))


def load_state_from_export_dir(export_dir: str):
  """Loads the serving variables written by :class:`ModelExporter`."""
  checkpointer = ocp.StandardCheckpointer()
  return checkpointer.restore(
      os.path.abspath(os.path.join(export_dir, STATE_DIRNAME)))


def load_serving_fn_from_export_dir(export_dir: str):
  """Deserializes the self-contained serving fn, or None if absent.

  Returns ``fn(variables, features) -> outputs`` over plain dicts; needs
  only jax on the host — the SavedModel-load equivalent
  (``predictors/exported_savedmodel_predictor.py:179``).
  """
  path = os.path.join(export_dir, SERVING_FN_FILENAME)
  if not os.path.exists(path):
    return None
  from jax import export as jax_export

  with open(path, 'rb') as f:
    exported = jax_export.deserialize(f.read())
  return exported.call


# ------------------------------------------------------------ eval exporters


def create_valid_result_smaller(metric_key: str = 'loss'):
  """Best = smaller metric (train_eval.py:206-246)."""

  def compare(best: Optional[Dict], current: Dict) -> bool:
    if best is None or metric_key not in best:
      return True
    return current[metric_key] < best[metric_key]

  return compare


def create_valid_result_larger(metric_key: str):
  """Best = larger metric (train_eval.py:249-292)."""

  def compare(best: Optional[Dict], current: Dict) -> bool:
    if best is None or metric_key not in best:
      return True
    return current[metric_key] > best[metric_key]

  return compare


def _should_skip_export(trainer, export_root: str) -> bool:
  """Preemption-aware gating for step-keyed exporters (LatestExporter,
  AsyncExportCallback's root — BestExporter dedups via its persisted
  best metrics instead).

  Skips (a) non-primary processes of a multi-process job — one export
  version per job, not one per host — and (b) checkpoints at or below
  the persisted ``last_exported_step``, so a restarted run never
  re-exports what it already published (counted as
  ``export/skipped_already_exported``).
  """
  if not getattr(trainer, 'is_primary_process', True):
    return True
  last = read_export_state(export_root).get('last_exported_step')
  step = int(trainer.state.step) if trainer.state is not None else 0
  if last is not None and step <= int(last):
    metrics_lib.counter('export/skipped_already_exported').inc()
    logging.info(
        'Skipping export of step %d under %r: step %d was already '
        'exported before the restart.', step, export_root, last)
    return True
  return False


class LatestExporter:
  """Exports on every eval, keeping N newest (LatestExporter semantics).

  Preemption-aware: persists ``last_exported_step`` into the export
  root after every version, and skips checkpoints a pre-restart
  incarnation already exported.
  """

  def __init__(self, name: str = 'latest_exporter_numpy', keep: int = 5,
               saved_model: bool = False):
    self.name = name
    self._exporter = ModelExporter(keep=keep, saved_model=saved_model)

  def export(self, trainer, metrics: Dict[str, float]) -> Optional[str]:
    del metrics
    export_root = os.path.join(trainer.config.model_dir, 'export', self.name)
    if _should_skip_export(trainer, export_root):
      return None
    path = self._exporter.export(trainer.model, trainer.state, export_root)
    write_export_state(export_root,
                       last_exported_step=int(trainer.state.step))
    return path


class BestExporter:
  """Exports only when the metric improves (BestExporter semantics).

  The best-so-far metrics are PERSISTED beside the versions, so a
  restarted run keeps raising the bar instead of re-exporting the first
  post-restart eval as a fresh "best".
  """

  def __init__(self,
               name: str = 'best_exporter_numpy',
               compare_fn: Optional[Callable] = None,
               keep: int = 5,
               saved_model: bool = False):
    self.name = name
    self._compare_fn = compare_fn or create_valid_result_smaller('loss')
    self._exporter = ModelExporter(keep=keep, saved_model=saved_model)
    self._best_metrics: Optional[Dict[str, float]] = None

  def export(self, trainer, metrics: Dict[str, float]) -> Optional[str]:
    if not metrics:
      return None
    if not getattr(trainer, 'is_primary_process', True):
      return None
    export_root = os.path.join(trainer.config.model_dir, 'export', self.name)
    if self._best_metrics is None:
      # Restart dedup: the pre-preemption best is the bar to beat — a
      # restarted run re-evaluating an already-exported checkpoint gets
      # the same metrics, which are not an improvement, so nothing is
      # re-exported. (No step gate here: a better metric at the same
      # step IS a legitimate new best within a run.)
      persisted = read_export_state(export_root).get('best_metrics')
      if isinstance(persisted, dict):
        self._best_metrics = {k: float(v) for k, v in persisted.items()}
    if not self._compare_fn(self._best_metrics, metrics):
      metrics_lib.counter('export/skipped_not_improved').inc()
      return None
    self._best_metrics = dict(metrics)
    path = self._exporter.export(trainer.model, trainer.state, export_root)
    write_export_state(export_root,
                       last_exported_step=int(trainer.state.step),
                       best_metrics=self._best_metrics)
    return path


def create_default_exporters(best_metric_key: str = 'loss',
                             compare_larger: bool = False,
                             keep: int = 5,
                             saved_model: bool = False):
  """Best + latest exporter pair (train_eval.py:295-361).

  ``saved_model=True`` additionally writes the TF-Serving-consumable
  SavedModel into every export version (export/savedmodel.py).
  """

  def create_exporters_fn(model):
    del model
    compare = (create_valid_result_larger(best_metric_key) if compare_larger
               else create_valid_result_smaller(best_metric_key))
    return [
        BestExporter(compare_fn=compare, keep=keep, saved_model=saved_model),
        LatestExporter(keep=keep, saved_model=saved_model),
    ]

  return create_exporters_fn
