"""The cell ``glm-4.7-flash.train-packed-8k`` end to end on the CPU at
its rehearsal sizes: the program (float32 there) and the plain reference
agree to rounding through the normal ``train_eval_model`` path from
record shards, no device metric is printed, the control and every
planted fault come out not correct by the limits the configuration
holds (set from chip readings: PERF.md, section 4), and the
``glm.mla_mix_device_ms`` reader tells the mixing's ops from the others
by the instruction's text."""

import pytest

from benchmark.metrics import _glm_ops
from benchmark.reference import glm_4_7_flash
from benchmark.tests.conftest import run_cell

CELL = 'glm-4.7-flash.train-packed-8k'
STAND_INS = ('fp8', 'half_batch', 'unchanged_state') + glm_4_7_flash.FAULTS


@pytest.fixture(scope='module')
def rehearsal():
  return run_cell('--workload', CELL, '--seed', '3000000011', '--seconds',
                  '2', '--rehearse')


@pytest.fixture(scope='module')
def stood():
  return run_cell('--workload', CELL, '--seed', '2147483659', '--seconds',
                  '1', '--rehearse', '--stand-in', ','.join(STAND_INS))


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


@pytest.mark.parametrize('name', STAND_INS)
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
  if name == 'no_mtp_loss':
    assert 'loss_gap' in failed


CTX = {'trunk_shapes': {
    'batch': 1, 'sequence': 8192, 'hidden': 2048, 'heads': 20, 'kv_heads': 20,
    'head_dim': 256, 'router_hidden': 0, 'experts': 64,
    'experts_per_token': 4}}
T = '{1,3,0,2:T(8,128)(2,1)S(1)}'


@pytest.mark.parametrize('name,mix', [
    # The latent norms: the query latent, the key/value latent, alone or
    # in a tuple with a scale's gradient; with and without the batch.
    (f'%fusion.1 = bf16[1,8192,768]{T} fusion(%a), kind=kLoop', 1),
    (f'%fusion.2 = (f32[512]{T}, bf16[8192,512]{T}) fusion(%a), kind=kInput',
     1),
    # Rotary on the queries' rotary part and on the one shared key.
    (f'%fusion.3 = f32[1,8192,20,32]{T} fusion(%a), kind=kLoop', 1),
    (f'%fusion.4 = bf16[1,8192,1,64]{T} fusion(%a), kind=kLoop', 1),
    # The join a head, key content and value apart, transposed or not.
    (f'%fusion.5 = bf16[1,8192,20,256]{T} fusion(%a), kind=kLoop', 1),
    (f'%fusion.6 = bf16[20,448,8192]{T} fusion(%a), kind=kLoop', 1),
    (f'%fusion.7 = bf16[8192,20,192]{T} fusion(%a), kind=kLoop', 1),
    # The layouts the kernel takes and gives, and the sums round it.
    (f'%copy.8 = bf16[20,8192,256]{T} copy(%a)', 1),
    (f'%fusion.9 = f32[20,1,8192]{T} fusion(%a), kind=kInput', 1),
    (f'%fusion.10 = bf16[1,8192,5120]{T} fusion(%a), kind=kLoop', 1),
    # The five products are no mixing, whatever their width.
    (f'%fusion.11 = bf16[1,8192,1344]{T} fusion(%a), kind=kOutput', 0),
    (f'%fusion.12 = bf16[8192,5120]{T} fusion(%a), kind=kOutput', 0),
    (f'%fusion.13 = bf16[8192,8960]{T} fusion(%a), kind=kOutput', 0),
    # Neither: the hidden stream, the router's scores (as wide as the
    # shared key once its head is squeezed out), the dense MLP, kernels,
    # copies in flight, holders.
    (f'%fusion.14 = bf16[1,8192,2048]{T} fusion(%a), kind=kLoop', 0),
    (f'%fusion.15 = f32[8192,64]{T} fusion(%a), kind=kLoop', 0),
    (f'%fusion.16 = bf16[8192,10240]{T} fusion(%a), kind=kLoop', 0),
    (f'%fusion.17 = bf16[8192,4,2048]{T} fusion(%a), kind=kLoop', 0),
    (f'%flash_attention_bwd.18 = (bf16[20,8192,256]{T}) custom-call(%a)', 0),
    (f'%ragged-dot-none.19 = bf16[8192,1536]{T} custom-call(%a)', 0),
    (f'%slice-start.20 = ((bf16[8192,768]{T}), bf16[8192,768]{T}, s32[]) '
     'slice-start(%a)', 0),
    (f'%while.21 = (s32[], bf16[1,8192,768]{T}) while(%a), body=%b', 0),
])
def test_reader_tells_the_mixing_by_the_instruction_text(name, mix):
  assert _glm_ops.is_mix(CTX)(name) == bool(mix)


def test_reader_finds_nothing_where_the_head_is_not_the_configurations():
  ctx = {'trunk_shapes': dict(CTX['trunk_shapes'], head_dim=128)}
  assert not _glm_ops.is_mix(ctx)(
      f'%fusion.1 = bf16[1,8192,768]{T} fusion(%a), kind=kLoop')
