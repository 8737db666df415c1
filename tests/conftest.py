"""Test harness: all tests run on a virtual 8-device CPU mesh.

Multi-chip sharding (dp/fsdp/tp/sp) is validated without TPU hardware by
forcing the host platform to expose 8 XLA CPU devices (the second
rehearsal of the on-chip-measurement guide). Nothing here runs on a
chip: chip_smoke.py, through the chip tool, is where the TPU is checked.
"""

import os

import pytest

# Pin the CPU platform before jax initializes a backend: the suite must
# behave the same wherever it runs, and a chip belongs to one process.
os.environ['JAX_PLATFORMS'] = 'cpu'
xla_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in xla_flags:
  os.environ['XLA_FLAGS'] = (
      xla_flags + ' --xla_force_host_platform_device_count=8').strip()
# Keep TF (host data pipeline only) off any accelerator and quiet.
os.environ.setdefault('CUDA_VISIBLE_DEVICES', '-1')
os.environ.setdefault('TF_CPP_MIN_LOG_LEVEL', '2')
# The program turns the persistent compilation cache on by default
# (utils/compilation_cache.py). The suite keeps jax from using it, here
# and in every child process: hermetic runs, nothing written into the
# checkout, and compile counts that do not depend on an earlier run.
os.environ['JAX_ENABLE_COMPILATION_CACHE'] = 'false'


def pytest_configure(config):
  config.addinivalue_line('markers', 'slow: slower multi-process tests')


@pytest.fixture
def forced_place_stage(monkeypatch):
  """The trainer's prefetcher with its dedicated placement stage forced
  on: it is TPU-only by default."""
  import tensor2robot_tpu.train.trainer as trainer_mod

  original = trainer_mod._DevicePrefetcher

  class ForcedPlaceStage(original):

    def __init__(self, it, place, depth, place_stage=None, **kwargs):
      super().__init__(it, place, depth, place_stage=True, **kwargs)

  monkeypatch.setattr(trainer_mod, '_DevicePrefetcher', ForcedPlaceStage)
