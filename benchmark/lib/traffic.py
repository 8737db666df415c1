"""The one traffic generator: record shards of camera-like frames.

A traffic mix is a JSON file under ``benchmark/workloads/`` (how many
examples and shards, what a frame looks like); the record schema (which
features an example carries) is the configuration's. Everything is drawn
from ``--seed``: example ``i`` is a pure function of ``(seed, i)``, so
the same seed gives the same shards whatever the worker count.

Copied in spirit from ``tools/profile_record_train.py::generate_shards``
(listed in PERF.md for deletion), with the seed taken from the caller
and frames that cost a decoder what a camera frame does: a smooth
low-frequency field (what its frames were) blended with half-resolution
noise, so the DCT blocks carry real high-frequency coefficients.

Worker processes import numpy and PIL only — never jax: the chip belongs
to the parent.
"""

from __future__ import annotations

import io
import multiprocessing
import os
from typing import Dict, List, Sequence, Tuple

import numpy as np
import PIL.Image

from benchmark.lib import tfrecord

SIGNATURE_GRID = 8


def signature(image: np.ndarray) -> np.ndarray:
  """Block means of a frame on an 8x8 grid (PIL's box filter): names the
  example a decoded row came from. Decoders differ by a level or two,
  JPEG moves a block's mean by less, examples differ by tens."""
  small = PIL.Image.fromarray(image).resize(
      (SIGNATURE_GRID, SIGNATURE_GRID), PIL.Image.BOX)
  return np.asarray(small, np.float32).reshape(-1)


def make_frame(rng: np.random.Generator, shape: Sequence[int],
               frame: Dict) -> np.ndarray:
  h, w, c = shape
  gh, gw = frame['field_grid']
  field = PIL.Image.fromarray(
      rng.integers(0, 256, (gh, gw, c), dtype=np.uint8)).resize(
          (w, h), PIL.Image.BILINEAR)
  d = frame['texture_downscale']
  noise = PIL.Image.fromarray(
      rng.integers(0, 256, (h // d, w // d, c), dtype=np.uint8)).resize(
          (w, h), PIL.Image.BILINEAR)
  return np.asarray(PIL.Image.blend(field, noise, frame['texture_weight']))


def make_example(seed: int, index: int, features: List[Dict],
                 frame: Dict) -> Tuple[Dict, np.ndarray]:
  """Example ``index`` of the stream ``seed``: (tf.Example fields, the
  signature of its first frame)."""
  rng = np.random.default_rng([seed, index])
  fields: Dict = {}
  sig = None
  for feature in features:
    if feature['kind'] == 'jpeg':
      image = make_frame(rng, feature['shape'], frame)
      if sig is None:
        sig = signature(image)
      buf = io.BytesIO()
      PIL.Image.fromarray(image).save(
          buf, format='JPEG', quality=frame['jpeg_quality'])
      fields[feature['name']] = buf.getvalue()
    elif feature['kind'] == 'normal':
      fields[feature['name']] = rng.standard_normal(
          feature['shape']).astype(np.float32)
    elif feature['kind'] == 'bernoulli':
      fields[feature['name']] = rng.integers(
          0, 2, feature['shape']).astype(np.float32)
    else:
      raise ValueError(f'unknown feature kind {feature["kind"]!r}')
  return fields, sig


def _write_shard(args) -> Tuple[np.ndarray, int]:
  path, seed, start, stop, features, frame = args
  sigs, jpeg_bytes = [], 0
  with open(path, 'wb') as f:
    for index in range(start, stop):
      fields, sig = make_example(seed, index, features, frame)
      sigs.append(sig)
      jpeg_bytes += sum(len(v) for v in fields.values()
                        if isinstance(v, bytes))
      f.write(tfrecord.frame(tfrecord.encode_example(fields)))
  return np.stack(sigs), jpeg_bytes


class ShardJob:
  """Shard generation running in worker processes while the parent
  imports the program. ``result()`` waits and returns the signatures of
  all examples, in example order, and the mean encoded bytes a frame."""

  def __init__(self, out_dir: str, seed: int, mix: Dict,
               features: List[Dict], processes: int):
    os.makedirs(out_dir, exist_ok=True)
    n, shards = mix['num_examples'], mix['num_shards']
    if n % shards:
      raise ValueError('num_examples must divide into num_shards')
    per = n // shards
    self.pattern = os.path.join(out_dir, 'data-*.tfrecord')
    self.paths = [os.path.join(out_dir, f'data-{s:05d}.tfrecord')
                  for s in range(shards)]
    self._frames = n * sum(f['kind'] == 'jpeg' for f in features)
    jobs = [(path, seed, s * per, (s + 1) * per, features, mix['frame'])
            for s, path in enumerate(self.paths)]
    # spawn: the parent may already hold threads (and later the chip).
    self._pool = multiprocessing.get_context('spawn').Pool(
        min(processes, shards))
    self._async = self._pool.map_async(_write_shard, jobs, chunksize=1)

  def close(self) -> None:
    """Ends the workers, finished or not, and waits for them."""
    self._pool.terminate()
    self._pool.join()

  def result(self) -> Tuple[np.ndarray, float]:
    try:
      parts = self._async.get()
    finally:
      self.close()
    sigs = np.concatenate([p[0] for p in parts])
    return sigs, sum(p[1] for p in parts) / max(self._frames, 1)
