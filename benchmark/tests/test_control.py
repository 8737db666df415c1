"""The control and the planted faults come out as not correct.

The control is the reference put in the program's place with every
product's operands rounded to float8 (one precision below the bfloat16
the configurations state); the faults are a batch half left out and a
state returned unchanged, planted the same way. One rehearsal a cell
reads all three after the program's own check steps. These run at the
rehearsal sizes; PERF.md holds the readings at the cells' own sizes on
the chip, which the limits were set from.
"""

import pytest

from benchmark.tests.conftest import CELLS, run_cell

STAND_INS = ('fp8', 'half_batch', 'unchanged_state')


@pytest.fixture(scope='module')
def stood(bench_file):
  cache = {}

  def get(cell):
    if cell not in cache:
      cache[cell] = run_cell('--workload', cell, '--seed', '77', '--seconds',
                             '1', '--rehearse', '--stand-in',
                             ','.join(STAND_INS), bench_file=bench_file)
    return cache[cell]

  return get


@pytest.mark.parametrize('cell', CELLS)
@pytest.mark.parametrize('stand_in', STAND_INS)
def test_stand_in_is_not_correct(cell, stand_in, stood):
  proc, result = stood(cell)
  assert proc.returncode == 0, proc.stderr[-3000:]
  assert result['metrics'] == {}
  assert result['correct'] is True  # the program itself, in float32
  verdict = result['stand_ins'][stand_in]
  assert verdict['correct'] is False
  failed = [k for k, v in verdict['compared'].items()
            if v['limit'] is not None and v['value'] > v['limit']]
  assert failed
  if stand_in == 'unchanged_state':
    assert verdict['compared']['update_norm_gap']['value'] == pytest.approx(1)
    assert 'update_norm_gap' in failed
