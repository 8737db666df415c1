"""Native (C++) runtime components, built on demand and bound via ctypes.

The reference rides on tf.data's C++ runtime for its data path; this
package is the TPU rebuild's own native layer: ``record_io.cpp`` provides
TFRecord-wire-format IO (CRC32C framing) plus a threaded interleaved
prefetch reader, compiled once per source revision with the system
toolchain and cached.

``load_record_io()`` returns the loaded ``ctypes.CDLL`` or ``None`` when
no toolchain is available (callers fall back to the TF path). Set
``T2R_NATIVE_DISABLE=1`` to force the fallback. A build or load that
FAILED is not the same as one that was switched off: the compiler's
output is kept in :func:`build_errors`, which chip_smoke.py prints and
fails on, so a machine that cannot build the reader is found out before
a record-fed run quietly takes the slow path.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
import threading
from typing import Dict, Optional

_SRC = os.path.join(os.path.dirname(__file__), 'record_io.cpp')
_JPEG_SRC = os.path.join(os.path.dirname(__file__), 'jpeg_decode.cpp')
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None  # GUARDED_BY(_LOCK)
_TRIED = False  # GUARDED_BY(_LOCK)
_JPEG_LIB: Optional[ctypes.CDLL] = None  # GUARDED_BY(_LOCK)
_JPEG_TRIED = False  # GUARDED_BY(_LOCK)
_BUILD_ERRORS: Dict[str, str] = {}  # GUARDED_BY(_LOCK)


def build_errors() -> Dict[str, str]:
  """``{library: compiler or loader output}`` for every library whose
  build or load failed in this process (empty when all that were asked
  for loaded)."""
  with _LOCK:
    return dict(_BUILD_ERRORS)


def _build_dir() -> str:
  cache = os.environ.get('T2R_NATIVE_CACHE') or os.path.join(
      tempfile.gettempdir(), 't2r_native')
  os.makedirs(cache, exist_ok=True)
  return cache


def _compile_src(src: str, stem: str, what: str,
                 extra_flags=()) -> Optional[str]:
  with open(src, 'rb') as f:
    digest = hashlib.sha256(f.read()).hexdigest()[:16]
  out = os.path.join(_build_dir(), f'{stem}_{digest}.so')
  if os.path.exists(out):
    return out
  tmp = out + f'.tmp{os.getpid()}'
  cmd = ['g++', '-O3', '-std=c++17', '-shared', '-fPIC', '-pthread',
         src, '-o', tmp, *extra_flags]
  try:
    subprocess.run(cmd, check=True, capture_output=True, text=True,
                   timeout=120)
  except (OSError, subprocess.SubprocessError) as e:
    _note_failure(what, f'{e}\n{getattr(e, "stderr", None) or ""}'.strip())
    return None
  os.replace(tmp, out)  # atomic: racing builders converge on one file
  return out


def _note_failure(what: str, output: str) -> None:  # HOLDS(_LOCK)
  _BUILD_ERRORS[what] = output
  logging.warning('native %s unavailable; using the fallback path:\n%s',
                  what, output)


def _compile() -> Optional[str]:
  return _compile_src(_SRC, 'libt2r_io', 'record_io')


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
  u8p = ctypes.POINTER(ctypes.c_uint8)
  lib.t2r_writer_open.restype = ctypes.c_void_p
  lib.t2r_writer_open.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
  lib.t2r_writer_write.restype = ctypes.c_int
  lib.t2r_writer_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_uint64]
  lib.t2r_writer_flush.restype = ctypes.c_int
  lib.t2r_writer_flush.argtypes = [ctypes.c_void_p]
  lib.t2r_writer_close.restype = ctypes.c_int
  lib.t2r_writer_close.argtypes = [ctypes.c_void_p]

  lib.t2r_reader_open.restype = ctypes.c_void_p
  lib.t2r_reader_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
  lib.t2r_reader_next.restype = ctypes.c_int64
  lib.t2r_reader_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(u8p)]
  lib.t2r_reader_error.restype = ctypes.c_char_p
  lib.t2r_reader_error.argtypes = [ctypes.c_void_p]
  lib.t2r_reader_seek.restype = ctypes.c_int
  lib.t2r_reader_seek.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
  lib.t2r_reader_close.restype = None
  lib.t2r_reader_close.argtypes = [ctypes.c_void_p]

  lib.t2r_interleave_open.restype = ctypes.c_void_p
  lib.t2r_interleave_open.argtypes = [ctypes.POINTER(ctypes.c_char_p),
                                      ctypes.c_int, ctypes.c_int,
                                      ctypes.c_int, ctypes.c_int]
  lib.t2r_interleave_next.restype = ctypes.c_int64
  lib.t2r_interleave_next.argtypes = [ctypes.c_void_p,
                                      ctypes.POINTER(u8p)]
  lib.t2r_interleave_error.restype = ctypes.c_char_p
  lib.t2r_interleave_error.argtypes = [ctypes.c_void_p]
  lib.t2r_interleave_close.restype = None
  lib.t2r_interleave_close.argtypes = [ctypes.c_void_p]

  lib.t2r_masked_crc32c.restype = ctypes.c_uint32
  lib.t2r_masked_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_uint64]

  lib.t2r_parser_create.restype = ctypes.c_void_p
  lib.t2r_parser_create.argtypes = [
      ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int),
      ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int),
      ctypes.POINTER(ctypes.c_int), ctypes.c_int]
  lib.t2r_parser_parse_batch.restype = ctypes.c_int
  lib.t2r_parser_parse_batch.argtypes = [
      ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p),
      ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64,
      ctypes.POINTER(ctypes.c_void_p)]
  lib.t2r_parser_error.restype = ctypes.c_char_p
  lib.t2r_parser_error.argtypes = [ctypes.c_void_p]
  lib.t2r_parser_destroy.restype = None
  lib.t2r_parser_destroy.argtypes = [ctypes.c_void_p]
  return lib


def load_record_io() -> Optional[ctypes.CDLL]:
  """Compiles (once) and loads the native record-IO library."""
  global _LIB, _TRIED
  if os.environ.get('T2R_NATIVE_DISABLE'):
    return None
  with _LOCK:
    if _TRIED:
      return _LIB
    _TRIED = True
    path = _compile()
    if path is not None:
      try:
        _LIB = _bind(ctypes.CDLL(path))
      except OSError as e:
        _note_failure('record_io', f'load failed: {e}')
        _LIB = None
    return _LIB


def _bind_jpeg(lib: ctypes.CDLL) -> ctypes.CDLL:
  lib.t2r_jpeg_decode_batch.restype = ctypes.c_int
  lib.t2r_jpeg_decode_batch.argtypes = [
      ctypes.POINTER(ctypes.c_char_p),  # bufs
      ctypes.POINTER(ctypes.c_uint64),  # lens
      ctypes.c_int,                     # n
      ctypes.POINTER(ctypes.c_uint8),   # out
      ctypes.c_int, ctypes.c_int, ctypes.c_int,  # h, w, c
      ctypes.c_int,                     # num_threads
      ctypes.POINTER(ctypes.c_int32),   # status
  ]
  return lib


def load_jpeg_decode() -> Optional[ctypes.CDLL]:
  """Compiles (once, needs libjpeg) and loads the JPEG batch decoder."""
  global _JPEG_LIB, _JPEG_TRIED
  if os.environ.get('T2R_NATIVE_DISABLE') or os.environ.get(
      'T2R_NATIVE_JPEG_DISABLE'):
    return None
  with _LOCK:
    if _JPEG_TRIED:
      return _JPEG_LIB
    _JPEG_TRIED = True
    path = _compile_src(_JPEG_SRC, 'libt2r_jpeg', 'jpeg_decode',
                        extra_flags=('-ljpeg',))
    if path is not None:
      try:
        _JPEG_LIB = _bind_jpeg(ctypes.CDLL(path))
      except OSError as e:
        _note_failure('jpeg_decode', f'load failed: {e}')
        _JPEG_LIB = None
    return _JPEG_LIB
