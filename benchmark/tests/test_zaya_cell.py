"""The cell ``zaya1-8b.train-packed-8k`` end to end on the CPU at its
rehearsal sizes: the program (float32 there) and the plain reference
agree to rounding through the normal ``train_eval_model`` path from
record shards, no device metric is printed, the control and every
planted fault come out not correct by the limits the configuration
holds (set from chip readings: PERF.md, section 4), and the ``zaya.*``
readers tell the mixing's and the router's ops from the others by the
instruction's text."""

import pytest

from benchmark.metrics import _zaya_ops
from benchmark.reference import zaya1_8b
from benchmark.tests.conftest import run_cell

CELL = 'zaya1-8b.train-packed-8k'
STAND_INS = ('fp8', 'half_batch', 'unchanged_state') + zaya1_8b.FAULTS


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
  assert 'mean probability of the chosen expert' in proc.stdout
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


CTX = {'trunk_shapes': {
    'batch': 2, 'sequence': 8192, 'hidden': 2048, 'heads': 8, 'kv_heads': 2,
    'head_dim': 128, 'router_hidden': 256, 'experts': 16,
    'experts_per_token': 1}}
T = '{1,3,0,2:T(8,128)(2,1)S(1)}'


@pytest.mark.parametrize('name,mix,router', [
    # The depthwise convolution's output and its shifted slice.
    (f'%fusion.1 = bf16[2,8192,1280]{T} fusion(%a), kind=kLoop', 1, 0),
    (f'%fusion.2 = f32[2,8191,1280]{T} fusion(%a), kind=kLoop', 1, 0),
    # The q-k projection is a dense product, not the mixing.
    (f'%convolution_convert_fusion.3 = f32[2,8192,1280]{T} fusion(%a), '
     'kind=kOutput, calls=%c', 0, 0),
    # The grouped convolution, a product a head, transposed or not, alone
    # or beside its bias's gradient in a tuple with tiled layouts.
    (f'%fusion.4 = f32[10,128,2,8192]{T} fusion(%a), kind=kOutput', 1, 0),
    (f'%fusion.5 = (f32[10,128]{T}, f32[2,8192,10,128]{T}) fusion(%a), '
     'kind=kOutput', 1, 0),
    # L2 norms and rotary over latent heads; the kernel's folded layouts.
    (f'%fusion.6 = (f32[2,8192,8]{T}, f32[2,8192,8,128]{T}) fusion(%a)', 1, 0),
    (f'%fusion.7 = (f32[2,8192,2,32]{T}, f32[2,8192,2,32]{T}) fusion(%a)',
     1, 0),
    (f'%convert.8 = f32[16,8192,128]{T} convert(%a)', 1, 0),
    # The router's state and probabilities, products among them.
    (f'%fusion.9 = (f32[256]{T}, f32[2,8192,256]{T}) fusion(%a), '
     'kind=kOutput', 0, 1),
    (f'%fusion.10 = f32[2,8192,16]{T} fusion(%a), kind=kOutput', 0, 1),
    # The sort, the gathers and the weighted sum lead with the tokens.
    (f'%sort.11 = (s32[16384]{T}, s32[16384]{T}) sort(%a, %b)', 0, 1),
    (f'%fusion.12 = bf16[16384,2048]{T} fusion(%a), kind=kCustom', 0, 1),
    (f'%fusion.13 = f32[16384,1,2048]{T} fusion(%a), kind=kLoop', 0, 1),
    # Neither: the hidden stream, kernels, copies in flight, holders.
    (f'%fusion.14 = bf16[2,8192,2048]{T} fusion(%a), kind=kOutput', 0, 0),
    (f'%fusion.15 = f32[2,8192,1024]{T} fusion(%a), kind=kLoop', 0, 0),
    (f'%ragged-dot-none.16 = bf16[16384,2048]{T} custom-call(%a)', 0, 0),
    (f'%flash_attention_dq.17 = bf16[16,8192,128]{T} custom-call(%a)', 0, 0),
    (f'%custom-call.18 = f32[2,8192,1280]{T} custom-call(%a), '
     'custom_call_target="ConcatBitcast"', 0, 0),
    (f'%slice-start.19 = ((bf16[16384,2048]{T}), bf16[4096,2048]{T}, '
     's32[]) slice-start(%a)', 0, 0),
    (f'%while.20 = (s32[], f32[2,8192,256]{T}) while(%a), body=%b', 0, 0),
])
def test_readers_tell_ops_by_the_instruction_text(name, mix, router):
  assert _zaya_ops.is_mix(CTX)(name) == bool(mix)
  assert _zaya_ops.is_router(CTX)(name) == bool(router)
