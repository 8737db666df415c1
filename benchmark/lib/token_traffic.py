"""Token records: packed sequences of int64 ids in TFRecord shards.

A record is one ``tf.Example`` with one feature, an ``Int64List`` of a
fixed number of ids: the wire format written out by hand, like
``lib/tfrecord.py`` (whose framing this reuses), so that neither side of
the comparison goes through the program's codec. ``Int64List`` is field
3 of ``Feature``, its values packed varints in field 1.

The ids of record ``index`` are a pure function of ``(seed, index)``:
Zipf-distributed ranks (probability of rank r proportional to
``r ** -exponent``) over the ``vocab`` ids held, id = rank - 1, drawn
through the inverse of the cumulative distribution.
"""

from __future__ import annotations

import hashlib
import os
from typing import Dict, List, Tuple

import numpy as np

from benchmark.lib import tfrecord


def draw(seed: int, index: int, length: int, vocab: int,
         exponent: float) -> np.ndarray:
  """The ``length`` int64 ids of record ``index``."""
  weights = np.arange(1, vocab + 1, dtype=np.float64) ** -exponent
  cdf = np.cumsum(weights) / np.sum(weights)
  u = np.random.default_rng([int(seed), int(index)]).random(length)
  return np.minimum(np.searchsorted(cdf, u, side='right'),
                    vocab - 1).astype(np.int64)


def digest(ids: np.ndarray) -> bytes:
  """What a fed row is matched to its generated example by."""
  return hashlib.blake2b(np.ascontiguousarray(ids, '<i8').tobytes(),
                         digest_size=16).digest()


# ------------------------------------------------------------------ the codec

def encode_varints(values: np.ndarray) -> bytes:
  """Non-negative int64 values as packed base-128 varints."""
  v = np.asarray(values, np.uint64).reshape(-1)
  if v.size == 0:
    return b''
  width = 1
  while int(v.max()) >> (7 * width):
    width += 1
  shifts = np.arange(width, dtype=np.uint64) * np.uint64(7)
  septets = (v[:, None] >> shifts[None, :]) & np.uint64(0x7F)
  # Septets a value needs: one, and one more for each it still overflows.
  used = 1 + np.sum((v[:, None] >> shifts[None, 1:]) > 0, axis=1)
  position = np.arange(width)[None, :]
  more = position < (used[:, None] - 1)
  out = (septets | np.where(more, np.uint64(0x80), np.uint64(0))
         ).astype(np.uint8)
  return out[position < used[:, None]].tobytes()


def decode_varints(buf: bytes) -> np.ndarray:
  b = np.frombuffer(buf, np.uint8)
  if b.size == 0:
    return np.zeros((0,), np.int64)
  last = (b & 0x80) == 0
  if not last[-1]:
    raise ValueError('truncated varint')
  starts = np.concatenate(([0], np.flatnonzero(last)[:-1] + 1))
  group = np.cumsum(last) - last
  position = np.arange(b.size) - starts[group]
  septets = (b & 0x7F).astype(np.uint64) << (position.astype(np.uint64)
                                              * np.uint64(7))
  return np.add.reduceat(septets, starts).astype(np.int64)


def encode_example(name: str, ids: np.ndarray) -> bytes:
  field = tfrecord._field  # pylint: disable=protected-access
  feature = field(3, field(1, encode_varints(ids)))
  entry = field(1, name.encode()) + field(2, feature)
  return field(1, field(1, entry))


def decode_example(payload: bytes) -> Dict[str, np.ndarray]:
  fields = tfrecord._fields  # pylint: disable=protected-access
  out = {}
  ((_, features),) = fields(payload)
  for _, entry in fields(features):
    parts = dict(fields(entry))
    ((kind, body),) = fields(parts[2])
    if kind != 3:
      raise ValueError(f'feature kind {kind} is no Int64List')
    ((_, packed),) = fields(body)
    out[parts[1].decode()] = decode_varints(packed)
  return out


# ----------------------------------------------------------------- the shards

def write_shards(directory: str, seed: int, mix: Dict, vocab: int
                 ) -> Tuple[str, Dict[bytes, int], float]:
  """Writes the mix's records in order over its shards; returns the file
  pattern, {digest: example index} and the mean record size in bytes."""
  os.makedirs(directory, exist_ok=True)
  total, shards = int(mix['num_examples']), int(mix['num_shards'])
  length = int(mix['sequence_length'])
  name = mix['tokens']['feature']
  exponent = float(mix['tokens']['zipf_exponent'])
  index_of: Dict[bytes, int] = {}
  size = 0
  per_shard = -(-total // shards)
  for shard in range(shards):
    path = os.path.join(
        directory, f'tokens-{shard:05d}-of-{shards:05d}.tfrecord')
    with open(path, 'wb') as f:
      for index in range(shard * per_shard,
                         min(total, (shard + 1) * per_shard)):
        ids = draw(seed, index, length, vocab, exponent)
        index_of[digest(ids)] = index
        record = tfrecord.frame(encode_example(name, ids))
        size += len(record)
        f.write(record)
  if len(index_of) != total:
    raise ValueError('two generated records hold the same ids')
  return os.path.join(directory, 'tokens-*.tfrecord'), index_of, size / total


def read_examples(pattern: str, name: str) -> List[np.ndarray]:
  """Every record's ids, in example order, through this file's parser."""
  import glob

  out = []
  for path in sorted(glob.glob(pattern)):
    out.extend(decode_example(p)[name] for p in tfrecord.read_records(path))
  return out
