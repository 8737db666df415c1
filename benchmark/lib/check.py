"""What decides ``correct``: the timed object's first steps against the
plain reference, at the timed sizes.

The trainer that the window then drives has, by the time the window
opens, made its first optimizer steps on batches the feed delivered.
The check reads from it, at dispatch boundaries: each step's loss that
the program reports, the optimizer's first-moment state after the first
dispatch (Adam's ``mu`` or the momentum trace: the gradients as the
optimizer got them) and the parameters after the last check step. The
reference starts from the same weights (made by the benchmark from the
seed, not by the program), reads the same examples out of the shards
with its own record parser and PIL, follows the same steps in float32
at matmul precision ``highest``, and gives the same readings.

Numbers compared (each printed beside its limit by ``run.py``):

* ``pixel_gap``: largest difference, in grey levels, between a frame as
  the program's feed delivered it and as the reference decoded it.
* ``loss_gap``: worst relative gap of a reported step loss.
* ``grad_norm_gap`` and ``update_norm_gap``: worst leaf of
  ``|‖program‖ − ‖reference‖| / max(‖reference‖, median leaf's)``, over
  the first-moment state and over the parameters' change. Leaves whose
  reference gradient is under a thousandth of the median leaf's are left
  out of the change (they move by round-off alone under Adam).
* ``grad_median_gap`` and ``update_median_gap``: the median leaf of the
  same gaps. The worst of some hundreds of leaves is the tail of one
  small leaf's rounding noise; the median is steady from seed to seed and
  follows the precision computed in (PERF.md, section 4).

The only knowledge of the program here is its random-number contract:
``Trainer.initialize`` splits ``PRNGKey(seed)`` twice for the state's
key, and a step folds the step count in and splits once for the
preprocessing key.
"""

from __future__ import annotations

import glob
import io
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import PIL.Image

from benchmark.lib import tfrecord, traffic

NEGLIGIBLE_GRADIENT = 1e-3  # of the median leaf's norm


def state_key(trainer_seed: int):
  _, init_key = jax.random.split(jax.random.PRNGKey(trainer_seed))
  _, key = jax.random.split(init_key)
  return key


def preprocessing_key(key, step):
  pre_key, _ = jax.random.split(jax.random.fold_in(key, step))
  return pre_key


# ------------------------------------------------------------- the examples

def read_examples(pattern: str) -> List[Dict]:
  """Every example of every shard, in example order (shards were written
  in order), through the benchmark's own parser."""
  out = []
  for path in sorted(glob.glob(pattern)):
    out.extend(tfrecord.decode_example(p) for p in tfrecord.read_records(path))
  return out


def match_rows(batch: Dict[str, np.ndarray], first_frame_key: str,
               signatures: np.ndarray) -> np.ndarray:
  """Which example each row of a fed batch is, by its frame's signature."""
  rows = np.stack([traffic.signature(img) for img in batch[first_frame_key]])
  d = ((rows[:, None, :] - signatures[None, :, :]) ** 2).sum(-1)
  best = d.argmin(axis=1)
  second = np.partition(d, 1, axis=1)[:, 1]
  if not np.all(d[np.arange(len(best)), best] * 4 < second):
    raise ValueError('a fed row matches no generated example clearly')
  return best


def reference_batch(examples: List[Dict], indices: np.ndarray,
                    record_features: List[Dict],
                    pool: ThreadPoolExecutor) -> Dict[str, np.ndarray]:
  """The batch the reference computes on: decoded by PIL from the bytes
  its own parser read."""
  out = {}
  for feature in record_features:
    raws = [examples[i][feature['name']] for i in indices]
    if feature['kind'] == 'jpeg':
      out[feature['key']] = np.stack(list(pool.map(
          lambda raw: np.asarray(PIL.Image.open(io.BytesIO(raw))), raws)))
    else:
      out[feature['key']] = np.stack(
          [r.reshape(feature['shape']) for r in raws]).astype(np.float32)
  return out


def pixel_gap(fed: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
              record_features: List[Dict]) -> float:
  worst = 0.0
  for feature in record_features:
    a, b = fed[feature['key']], ref[feature['key']]
    if a.shape != b.shape:
      return float('inf')
    if feature['kind'] == 'jpeg':
      worst = max(worst, float(np.abs(
          a.astype(np.int16) - b.astype(np.int16)).max()))
    elif not np.array_equal(a.astype(np.float32), b):
      return float('inf')
  return worst


# ------------------------------------------------------- the reference steps

def _optimizer_step(opt, params, moments, grads, count):
  """Plain momentum SGD or Adam; ``moments`` is (first, second|None)."""
  lr = opt['learning_rate']
  first, second = moments
  if opt['kind'] == 'momentum':
    first = jax.tree_util.tree_map(
        lambda t, g: g + opt['momentum'] * t, first, grads)
    new = jax.tree_util.tree_map(lambda p, t: p - lr * t, params, first)
    return new, (first, None)
  b1, b2, eps = opt['b1'], opt['b2'], opt['eps']
  t = count + 1
  first = jax.tree_util.tree_map(
      lambda m, g: b1 * m + (1 - b1) * g, first, grads)
  second = jax.tree_util.tree_map(
      lambda v, g: b2 * v + (1 - b2) * g * g, second, grads)
  c1, c2 = 1 - b1 ** t, 1 - b2 ** t
  new = jax.tree_util.tree_map(
      lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
      params, first, second)
  return new, (first, second)


def follow(ref, cfg, params0: Dict[str, Any], batches: List[Dict],
           trainer_seed: int, quant: Optional[str] = None,
           fault: Optional[str] = None) -> Dict[str, Any]:
  """Runs the reference (or, with ``quant``/``fault``, a stand-in for
  the program) through ``len(batches)`` optimizer steps.

  Returns per-step losses, per-leaf norms of the first gradient, of the
  first-moment state after the first ``cfg['steps_per_dispatch']``
  steps, and of the parameters' change after the last step.
  """
  opt = ref.OPTIMIZER
  group = cfg['steps_per_dispatch']

  # Seed-dependent values are arguments, so that one compiled program
  # (in the persistent cache) serves every seed.
  def step(params, moments, batch, count, key):
    inputs = ref.preprocess(batch, preprocessing_key(key, count), cfg)
    if fault == 'half_batch':
      inputs = jax.tree_util.tree_map(lambda x: x[:x.shape[0] // 2], inputs)
    value, grads = jax.value_and_grad(ref.loss)(params, inputs, cfg, quant)
    new, moments = _optimizer_step(opt, params, moments, grads, count)
    if fault == 'unchanged_state':
      new = params
    return new, moments, value, grads

  step = jax.jit(step)
  key = state_key(trainer_seed)
  zeros = jax.tree_util.tree_map(jnp.zeros_like, params0)
  moments = (zeros, None if opt['kind'] == 'momentum' else zeros)
  params, losses = params0, []
  first_grad = first_moment = None
  with jax.default_matmul_precision('highest'):
    for count, batch in enumerate(batches):
      batch = {k: jnp.asarray(v) for k, v in batch.items()}
      params, moments, value, grads = step(
          params, moments, batch, jnp.asarray(count, jnp.int32), key)
      losses.append(float(value))
      if count == 0:
        first_grad = norms(grads)
      if count == group - 1:
        first_moment = norms(moments[0])
  change = norms(jax.tree_util.tree_map(
      lambda a, b: a - b, params, params0))
  return {'losses': losses, 'first_grad': first_grad,
          'first_moment': first_moment, 'change': change}


@jax.jit
def _norms_on_device(tree):
  return jax.tree_util.tree_map(
      lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def norms(tree: Dict[str, Any]) -> Dict[str, float]:
  return {k: float(v)
          for k, v in jax.device_get(_norms_on_device(tree)).items()}


# ---------------------------------------------------------- the comparison

def _leaf_gaps(program: Dict[str, float], reference: Dict[str, float],
               leaves: List[str]) -> Dict[str, float]:
  """Per leaf: the gap between the two norms against the reference's
  norm of that leaf or of the median leaf, whichever is larger."""
  median = float(np.median([reference[k] for k in reference]))
  return {k: abs(program[k] - reference[k]) / max(reference[k], median, 1e-30)
          for k in leaves}


def _worst_and_median(program: Dict[str, float], reference: Dict[str, float],
                      leaves: List[str]) -> Tuple[float, str, float]:
  gaps = _leaf_gaps(program, reference, leaves)
  worst, where = 0.0, ''
  for k, gap in gaps.items():
    if not gap <= worst:  # also catches NaN
      worst, where = gap, k
  median = float(np.median(list(gaps.values())))
  return worst, where, (median if median == median else float('inf'))




def compare(program: Dict[str, Any], reference: Dict[str, Any],
            loss_steps: List[int]) -> Dict[str, Dict]:
  """``program`` holds ``losses`` ({step: value}), ``first_moment`` and
  ``change`` norms by leaf; returns each number compared with the leaf
  or step that gave it."""
  out = {}
  worst, where = 0.0, ''
  for s in loss_steps:
    ref = reference['losses'][s - 1]
    gap = abs(program['losses'][s] - ref) / max(abs(ref), 1e-30)
    if not gap <= worst:
      worst, where = gap, f'step{s}'
  out['loss_gap'] = {'value': worst, 'at': where}
  leaves = list(reference['first_moment'])
  worst, where, middle = _worst_and_median(
      program['first_moment'], reference['first_moment'], leaves)
  out['grad_norm_gap'] = {'value': worst, 'at': where}
  out['grad_median_gap'] = {'value': middle}
  median = float(np.median(list(reference['first_grad'].values())))
  moved = [k for k in leaves
           if reference['first_grad'][k] >= NEGLIGIBLE_GRADIENT * median]
  worst, where, middle = _worst_and_median(
      program['change'], reference['change'], moved)
  out['update_norm_gap'] = {'value': worst, 'at': where,
                            'leaves_left_out': len(leaves) - len(moved)}
  out['update_median_gap'] = {'value': middle}
  return out
