"""``run.py`` end to end on the CPU at the rehearsal sizes.

The rehearsal ties each plain reference to the framework's model: the
program (float32 on the CPU) and the reference start from the same
seeded weights, and the first steps' loss, the optimizer's first moment
(forward, loss, gradients) and the parameters' change (one update and
more) agree to float32 rounding. It can never emit a device metric; and
without ``--rehearse`` a run that finds no TPU fails with no result.
"""

import pytest

from benchmark.tests.conftest import CELLS, run_cell


@pytest.mark.parametrize('cell', CELLS)
def test_rehearsal_agrees_with_reference_and_emits_no_metric(
    cell, rehearsals):
  proc, result = rehearsals(cell)
  assert proc.returncode == 0, proc.stderr[-3000:]
  assert result['rehearsal'] is True
  assert result['metrics'] == {}
  assert 'busy_s' not in result['device']
  compared = result['compared']
  assert list(result)[-1] == 'compared'
  assert compared['pixel_gap']['value'] == 0
  assert compared['grad_norm_gap']['value'] < 1e-3
  # Tiny batches make the later steps chaotic (batch norm over a handful
  # of values); the first step's loss is exact, the later ones near.
  assert compared['loss_gap']['value'] < 2e-2
  assert compared['update_norm_gap']['value'] < 5e-2
  assert result['correct'] is True
  assert 'compared loss_gap' in proc.stderr
  assert proc.stderr.strip().splitlines()[-1] == 'correct: True'


def test_without_a_tpu_and_without_the_flag_the_run_fails():
  proc, result = run_cell('--workload', CELLS[1], '--seed', '1',
                          '--seconds', '1')
  assert proc.returncode != 0
  assert result is None
  assert not [l for l in proc.stdout.splitlines() if l.startswith('{')]
