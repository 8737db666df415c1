"""Tests of the benchmark itself; run with ``python -m pytest
benchmark/tests`` from the repository's root (CPU, a few minutes)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

CELLS = ('qtopt.train-records', 'grasp2vec.train-records')

# The QT-Opt cell ran on the chip and is not admitted to BENCHMARK.json
# (PERF.md, Open questions). Its configuration, reference and traffic mix
# stay under benchmark/ and are rehearsed here, named from this file.
QTOPT_CONFIG = {
    'name': 'qtopt-grasping44',
    'file': 'benchmark/configs/qtopt-grasping44.json',
}
QTOPT_CELL = {
    'name': 'qtopt.train-records', 'config': 'qtopt-grasping44',
    'traffic': 'train-records-4096', 'chips': 1,
}


@pytest.fixture(scope='session')
def bench_file(tmp_path_factory):
  """``BENCHMARK.json`` with the QT-Opt cell beside its own."""
  with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
    bench = json.load(f)
  bench['configs'].append(QTOPT_CONFIG)
  bench['workloads'].append(QTOPT_CELL)
  path = tmp_path_factory.mktemp('bench') / 'BENCHMARK.json'
  path.write_text(json.dumps(bench))
  return str(path)


def run_cell(*args, bench_file=None, timeout=900):
  """``benchmark/run.py`` in a process of its own, on the CPU: the
  command itself, or ``run.main`` on ``bench_file``."""
  env = dict(os.environ, JAX_PLATFORMS='cpu')
  command = [sys.executable, os.path.join(ROOT, 'benchmark', 'run.py')]
  if bench_file:
    command = [sys.executable, '-c',
               'import sys; from benchmark import run; '
               f'sys.exit(run.main(sys.argv[1:], bench_file={bench_file!r}))']
  proc = subprocess.run(
      command + list(args),
      cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)
  lines = [l for l in proc.stdout.splitlines() if l.startswith('{')]
  result = json.loads(lines[-1]) if lines and proc.returncode == 0 else None
  return proc, result


@pytest.fixture(scope='session')
def rehearsals(bench_file):
  """One rehearsal a cell, shared by the tests that read its result."""
  cache = {}

  def get(cell):
    if cell not in cache:
      cache[cell] = run_cell('--workload', cell, '--seed', '3000000011',
                             '--seconds', '2', '--rehearse',
                             bench_file=bench_file)
    return cache[cell]

  return get
