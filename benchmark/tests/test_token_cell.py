"""The cell ``trinity-mini.train-packed-8k`` end to end on the CPU at its
rehearsal sizes: the program (float32 there) and the plain reference
agree to rounding through the normal ``train_eval_model`` path from
record shards, no device metric is printed, and the control and every
planted fault come out not correct by the limits the configuration
holds (set from chip readings: PERF.md, section 4)."""

import pytest

from benchmark.kinds import train_token_records
from benchmark.tests.conftest import run_cell

CELL = 'trinity-mini.train-packed-8k'


@pytest.fixture(scope='module')
def rehearsal():
  return run_cell('--workload', CELL, '--seed', '3000000011', '--seconds',
                  '2', '--rehearse')


@pytest.fixture(scope='module')
def stood():
  return run_cell('--workload', CELL, '--seed', '2147483659', '--seconds',
                  '1', '--rehearse', '--stand-in',
                  ','.join(train_token_records.QUANTS +
                           train_token_records.FAULTS))


def test_rehearsal_agrees_with_reference_and_emits_no_metric(rehearsal):
  proc, result = rehearsal
  assert proc.returncode == 0, proc.stderr[-3000:]
  assert result['rehearsal'] is True
  assert result['metrics'] == {}
  assert 'busy_s' not in result['device']
  assert result['attempted'] >= 2 and result['failed'] == 0
  compared = result['compared']
  assert list(result)[-1] == 'compared'
  assert compared['token_gap'] == {'value': 0, 'limit': 0}
  assert compared['rows_gap']['value'] == 0
  assert compared['loss_gap']['value'] < 1e-5
  assert compared['grad_norm_gap']['value'] < 1e-4
  assert compared['update_norm_gap']['value'] < 1e-3
  for name in ('loss_gap', 'grad_median_gap', 'update_norm_gap', 'rows_gap'):
    assert compared[name]['limit'] is not None, name
  assert result['correct'] is True
  assert 'moe/rows_dropped": 0' in proc.stdout
  assert proc.stderr.strip().splitlines()[-1] == 'correct: True'


@pytest.mark.parametrize(
    'name', train_token_records.QUANTS + train_token_records.FAULTS)
def test_control_and_planted_faults_are_not_correct(name, stood):
  proc, result = stood
  assert proc.returncode == 0, proc.stderr[-3000:]
  assert result['correct'] is True          # the program itself is sound
  stand = result['stand_ins'][name]
  assert stand['correct'] is False, stand
  failed = [k for k, v in stand['compared'].items()
            if v['limit'] is not None and v['value'] > v['limit']]
  assert failed, stand['compared']
  if name == 'unchanged_state':
    assert 'update_norm_gap' in failed


def test_a_fed_row_that_is_no_generated_example_is_not_correct(monkeypatch,
                                                               capsys):
  """A feed that alters an id is caught exactly: no digest matches."""
  import json

  from benchmark import run as bench_run
  from benchmark.lib import token_traffic

  digest = token_traffic.digest
  write = token_traffic.write_shards

  def write_then_break(*args, **kwargs):
    # The shards are written whole; from the check on, a fed row reads
    # one id higher, as a codec fault would leave it.
    out = write(*args, **kwargs)
    monkeypatch.setattr(token_traffic, 'digest',
                        lambda ids: digest(ids + 1))
    return out

  monkeypatch.setattr(token_traffic, 'write_shards', write_then_break)
  rc = bench_run.main(['--workload', CELL, '--seed', '77', '--seconds', '1',
                       '--rehearse'])
  assert rc == 0
  out = capsys.readouterr().out
  result = json.loads([l for l in out.splitlines() if l.startswith('{')][-1])
  assert result['correct'] is False
  assert result['compared']['token_gap']['value'] > 0

