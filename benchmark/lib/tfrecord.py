"""TFRecord framing and tf.Example wire format, written out by hand.

The traffic generator writes shards with this and the plain reference
reads them back with it, so neither side of the comparison goes through
the program's codec (``tensor2robot_tpu/data/example_codec.py``, the
native reader) or through TensorFlow.

Framing: ``u64 length | u32 masked_crc32c(length) | payload |
u32 masked_crc32c(payload)``. An ``Example`` is ``Features{map<string,
Feature>}``; a ``Feature`` holds one of ``BytesList`` (field 1),
``FloatList`` (2, packed) or ``Int64List`` (3, packed).
"""

from __future__ import annotations

import struct
from typing import Dict, Iterator, List, Union

import google_crc32c
import numpy as np

Value = Union[bytes, np.ndarray]


def _masked_crc(data: bytes) -> int:
  crc = google_crc32c.value(data)
  return (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF


def _varint(n: int) -> bytes:
  out = bytearray()
  while True:
    byte = n & 0x7F
    n >>= 7
    if n:
      out.append(byte | 0x80)
    else:
      out.append(byte)
      return bytes(out)


def _field(number: int, payload: bytes) -> bytes:
  """A length-delimited field (wire type 2)."""
  return _varint((number << 3) | 2) + _varint(len(payload)) + payload


def encode_example(features: Dict[str, Value]) -> bytes:
  """``bytes`` → BytesList of one value; float array → packed FloatList."""
  entries = []
  for name, value in features.items():
    if isinstance(value, (bytes, bytearray)):
      feature = _field(1, _field(1, bytes(value)))
    else:
      packed = np.asarray(value, '<f4').reshape(-1).tobytes()
      feature = _field(2, _field(1, packed))
    entry = _field(1, name.encode()) + _field(2, feature)
    entries.append(_field(1, entry))
  return _field(1, b''.join(entries))


def frame(payload: bytes) -> bytes:
  header = struct.pack('<Q', len(payload))
  return b''.join((header, struct.pack('<I', _masked_crc(header)), payload,
                   struct.pack('<I', _masked_crc(payload))))


def read_records(path: str) -> Iterator[bytes]:
  """Payloads of one shard, both checksums verified."""
  with open(path, 'rb') as f:
    data = f.read()
  pos = 0
  while pos < len(data):
    header = data[pos:pos + 8]
    (length,) = struct.unpack('<Q', header)
    (crc_len,) = struct.unpack('<I', data[pos + 8:pos + 12])
    payload = data[pos + 12:pos + 12 + length]
    (crc_payload,) = struct.unpack(
        '<I', data[pos + 12 + length:pos + 16 + length])
    if crc_len != _masked_crc(header) or crc_payload != _masked_crc(payload):
      raise ValueError(f'{path}: checksum mismatch at byte {pos}')
    yield payload
    pos += 16 + length


def _read_varint(buf: bytes, pos: int):
  shift = result = 0
  while True:
    byte = buf[pos]
    pos += 1
    result |= (byte & 0x7F) << shift
    if not byte & 0x80:
      return result, pos
    shift += 7


def _fields(buf: bytes) -> List:
  """(field number, payload) of every length-delimited field of ``buf``."""
  out, pos = [], 0
  while pos < len(buf):
    key, pos = _read_varint(buf, pos)
    if key & 7 != 2:
      raise ValueError(f'unexpected wire type {key & 7}')
    length, pos = _read_varint(buf, pos)
    out.append((key >> 3, buf[pos:pos + length]))
    pos += length
  return out


def decode_example(payload: bytes) -> Dict[str, Value]:
  """Inverse of :func:`encode_example`."""
  out: Dict[str, Value] = {}
  ((_, features),) = _fields(payload)
  for _, entry in _fields(features):
    parts = dict(_fields(entry))
    ((kind, body),) = _fields(parts[2])
    ((_, value),) = _fields(body)
    out[parts[1].decode()] = (
        value if kind == 1 else np.frombuffer(value, '<f4').copy())
  return out
