"""Device time by the program's own scopes (``benchmark/metrics/_scopes.py``
over ``ProgramRecord.op_scopes``): every op of the three token trunks'
compiled steps has a family, and the join of a trace to the record reads
a synthetic trace as written, a loop over its body included."""

import pathlib

import pytest

from benchmark.metrics import _scopes
from benchmark.run import load_reader
from tensor2robot_tpu.observability import programs
from test_programs import _HLO

import test_afmoe
import test_glm_moe_lite
import test_zaya

ARGUMENTS = ('state', 'features', 'labels')


@pytest.fixture(autouse=True)
def _clean_ledger():
  programs.clear()
  yield
  programs.clear()


def _tiny_step(module, tmp_path):
  """The trainer's record of a tiny trunk's step, one step trained."""
  from tensor2robot_tpu.data.input_generators import (
      NativeRecordInputGenerator)
  from tensor2robot_tpu.train.trainer import train_eval_model

  cfg = module.tiny_cfg()
  pattern, _, _ = test_afmoe._write_shards(pathlib.Path(tmp_path), cfg)
  train_eval_model(
      model=module.model_for(cfg), model_dir='',
      train_input_generator=NativeRecordInputGenerator(
          file_patterns=pattern, batch_size=cfg['batch_size'],
          shuffle_buffer_size=4, seed=5),
      max_train_steps=1, eval_interval_steps=0, save_interval_steps=0,
      log_interval_steps=0, seed=1)
  return programs.get('train/step')


@pytest.mark.parametrize('module, families', [
    (test_afmoe, {'attention_kernel', 'route', 'experts', 'dense', 'head',
                  'optimizer', 'layer_other'}),
    (test_zaya, {'attention_kernel', 'attention_mix', 'route', 'experts',
                 'dense', 'head', 'optimizer', 'layer_other'}),
    (test_glm_moe_lite, {'attention_mix', 'route', 'experts', 'dense',
                         'head', 'optimizer', 'layer_other'}),
], ids=['afmoe', 'zaya', 'glm'])
def test_every_named_op_of_a_tiny_trunk_has_a_family(module, families,
                                                     tmp_path):
  """Every op the step's scopes name falls in a family of the table. The
  listed few that stay unnamed: ops XLA made from nothing named (a loop's
  carried values, constants) and copies of the step's arguments."""
  record = _tiny_step(module, tmp_path)
  found, stray = set(), []
  for name, scope in record.op_scopes().items():
    family = _scopes.family_of(scope.path)
    found.add(family)
    if family == 'unnamed' and scope.path and not scope.path.startswith(
        ARGUMENTS):
      stray.append((name, scope))
  assert not stray, stray[:10]
  assert families <= found, families - found


class _Event:

  def __init__(self, name, start, end):
    self.name, self.start_ns, self.duration_ns = name, start, end - start


class _Line:

  def __init__(self, name, events):
    self.name, self.events = name, [_Event(*e) for e in events]


class _Plane:

  def __init__(self, name, lines, stats=()):
    self.name, self.lines, self.stats = name, lines, list(stats)


class _Profile:

  def __init__(self, planes):
    self.planes = planes


class _Compiled:

  def __init__(self, text):
    self._text = text

  def as_text(self):
    return self._text


def _op(name):
  return f'%{name} = f32[16,16]{{1,0}} op()'


def _synthetic_ctx():
  """Two dispatches of the step (ns): its ops, a loop and its body, an op
  the record does not hold; another program's op between them."""
  step = [
      (_op('fusion.1'), 0, 20_000),          # a gradient's product: dense
      (_op('while.5'), 20_000, 70_000),      # the loss's loop ...
      (_op('fusion.5'), 25_000, 45_000),     # ... and its body, twice
      (_op('fusion.5'), 48_000, 68_000),
      (_op('fusion.2'), 70_000, 80_000),     # Adam alone: optimizer
      (_op('copy.4'), 80_000, 85_000),       # XLA's copy of fusion.1
      (_op('custom-call.7'), 85_000, 95_000),  # the attention kernel
      (_op('mystery.9'), 95_000, 100_000),   # not in the record
  ]
  ops = step + [(n, a + 200_000, b + 200_000) for n, a, b in step] + [
      (_op('fusion.1'), 120_000, 150_000)]
  modules = [('jit_train_step', 0, 100_000), ('jit_other', 120_000, 150_000),
             ('jit_train_step', 200_000, 300_000)]
  device = _Plane('/device:TPU:0', [_Line('XLA Ops', ops),
                                    _Line('XLA Modules', modules)])
  return {'profile': _Profile([device]), 'cache': {}, 'steps_per_dispatch': 1}


def test_join_reads_a_synthetic_trace_loop_over_its_body():
  programs.record_compiled('train/step', _Compiled(_HLO), source='test')
  ctx = _synthetic_ctx()
  out = _scopes.join(ctx)
  assert out['steps'] == 2
  ms = out['ms']
  # The loop keeps what its body leaves (10 us), the body the rest (40).
  assert ms['head'] == pytest.approx(0.050)
  assert out['loops_ms'] == {'%while.5': pytest.approx(0.050)}
  assert ms['dense'] == pytest.approx(0.025)
  assert out['by_direction']['dense'] == {'backward': pytest.approx(0.025)}
  assert ms['optimizer'] == pytest.approx(0.010)
  assert ms['attention_kernel'] == pytest.approx(0.010)
  assert out['by_direction']['attention_kernel'] == {
      'recomputed': pytest.approx(0.010)}
  assert ms['unnamed'] == pytest.approx(0.005)
  # The families add up to the step's ops, nothing of the other program.
  assert sum(ms.values()) == pytest.approx(out['step_busy_ms'])
  assert out['step_busy_ms'] == pytest.approx(0.100)
  assert out['unnamed_share'] == pytest.approx(5.0)
  assert out['ops_not_in_record'] == 1
  assert out['unnamed_top'][0][0].startswith('%mystery.9')
  for name, family in (('optimizer', 'optimizer'), ('head', 'head'),
                       ('dense', 'dense')):
    assert load_reader(f'scope.{name}_device_ms')(ctx) == ms[family]
  assert load_reader('scope.unnamed_share')(ctx) == pytest.approx(5.0)


class _ParentRecord:
  """A parent's record of the step: no compiled text, no op map."""


@pytest.mark.parametrize('record', [None, _ParentRecord()],
                         ids=['no_record', 'parent_record'])
def test_readers_read_nothing_without_a_record(record, monkeypatch):
  """A program that keeps no op map of its step (a parent of PR 37, with
  or without a record): every scope reader gives None, raising nothing."""
  monkeypatch.setattr(programs, 'get', lambda name: record)
  ctx = _synthetic_ctx()
  for name in ('optimizer_device_ms', 'head_device_ms', 'route_device_ms',
               'dense_device_ms', 'unnamed_share'):
    assert load_reader(f'scope.{name}')(ctx) is None
