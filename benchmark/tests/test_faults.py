"""A run whose timed path is broken underneath reports ``correct`` false.

Drives ``run.main`` in this process with ``--rehearse`` (which skips the
look for a chip and nothing else) while the PROGRAM is broken by a
monkeypatch: an optimizer step that returns the parameters unchanged, or
a model that leaves half of every batch out and takes its means over the
rest.
"""

import json

import jax
import pytest

from benchmark import run as bench_run
from benchmark.kinds import train_records
from benchmark.tests.conftest import CELLS


def _halved(tree):
  return jax.tree_util.tree_map(lambda x: x[:x.shape[0] // 2], tree)


def _break_half_batch(monkeypatch, model_cls):
  inference = model_cls.inference_network_fn
  train_fn = model_cls.model_train_fn

  def half_inference(self, variables, features, labels, mode, rng=None):
    return inference(self, variables, _halved(features),
                     labels and _halved(labels), mode, rng)

  def half_train(self, features, labels, outputs, mode):
    return train_fn(self, _halved(features), labels and _halved(labels),
                    outputs, mode)

  monkeypatch.setattr(model_cls, 'inference_network_fn', half_inference)
  monkeypatch.setattr(model_cls, 'model_train_fn', half_train)


def _break_unchanged_state(monkeypatch):
  import optax

  monkeypatch.setattr(optax, 'apply_updates', lambda params, updates: params)


@pytest.mark.parametrize('cell', CELLS)
@pytest.mark.parametrize('fault', ['unchanged_state', 'half_batch'])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch, capsys,
                                          bench_file):
  _, _, cfg, _ = bench_run.load_cell(cell, True, bench_file)
  if fault == 'unchanged_state':
    _break_unchanged_state(monkeypatch)
  else:
    _break_half_batch(
        monkeypatch, train_records.load_symbol(cfg['program']['model']))
  rc = bench_run.main(['--workload', cell, '--seed', '123', '--seconds',
                       '1', '--rehearse'], bench_file)
  assert rc == 0
  out = capsys.readouterr().out
  result = json.loads([l for l in out.splitlines() if l.startswith('{')][-1])
  assert result['correct'] is False
  failed = [k for k, v in result['compared'].items()
            if v['limit'] is not None and v['value'] > v['limit']]
  assert failed, result['compared']
