"""Grasping44, the QT-Opt critic (arXiv:1806.10293, appendix, fig. 12).

Image tower: 6x6/2 conv, 64 channels, batch norm, relu, 3x3/3 max pool;
six 5x5 convs (each conv → batch norm → relu); 3x3/3 max pool. The
action (world vector 3, vertical rotation 2) goes through two dense
layers (256, 64) and is added to every position of the pooled map. Six
3x3 convs, a 2x2/2 max pool, three 3x3 VALID convs, two dense layers of
64 with batch norm, one logit; sigmoid; log loss against the grasp
outcome. Batch-norm epsilon 0.001. Convolutions followed by a batch norm
carry no bias (slim's convention with a normaliser).

Departures from the paper, each because the weights made here must map
one to one onto the program's tree: the first batch norm and the one
after the first action layer have a bias and no scale; the others have
both. Training preprocessing (one random 472x472 crop per batch, cast
to [0, 1]) is part of the timed step, so it is part of this reference.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from benchmark.reference import nn

EPS = 1e-3
OPTIMIZER = {'kind': 'momentum', 'learning_rate': 1e-4, 'momentum': 0.9}


def _conv_names(cfg):
  a, b, c = cfg['num_convs']
  first = [(f'conv{l}', 5, 'SAME') for l in range(2, 2 + a)]
  second = [(f'conv{l}', 3, 'SAME') for l in range(2 + a, 2 + a + b)]
  third = [(f'conv{l}', 3, 'VALID')
           for l in range(2 + a + b, 2 + a + b + c)]
  return first, second, third


def param_shapes(cfg) -> Dict[str, tuple]:
  ch = cfg['tower_channels']
  shapes = {'conv1_1/kernel': (6, 6, 3, ch), 'bn1/bias': (ch,)}
  first, second, third = _conv_names(cfg)
  for name, k, _ in first + second + third:
    shapes[f'{name}/kernel'] = (k, k, ch, ch)
    shapes[f'{name}/bn/scale'] = (ch,)
    shapes[f'{name}/bn/bias'] = (ch,)
  shapes['fcgrasp/kernel'] = (cfg['action_size'], cfg['action_hidden'])
  shapes['fcgrasp/bn/bias'] = (cfg['action_hidden'],)
  shapes['fcgrasp2/kernel'] = (cfg['action_hidden'], ch)
  shapes['fcgrasp2/bias'] = (ch,)
  side = _final_side(cfg)
  fan = side * side * ch
  for l in range(cfg['hid_layers']):
    shapes[f'fc{l}/kernel'] = (fan, cfg['fc_hidden'])
    shapes[f'fc{l}/bn/scale'] = (cfg['fc_hidden'],)
    shapes[f'fc{l}/bn/bias'] = (cfg['fc_hidden'],)
    fan = cfg['fc_hidden']
  shapes['logit/kernel'] = (fan, 1)
  shapes['logit/bias'] = (1,)
  return shapes


def _ceil_div(a, b):
  return -(-a // b)


def _sides(cfg):
  """Spatial side after conv1, pool1, pool2, pool3, the VALID convs."""
  s0 = _ceil_div(cfg['crop_size'][0], 2)
  s1 = _ceil_div(s0, 3)
  s2 = _ceil_div(s1, 3)
  s3 = _ceil_div(s2, 2)
  return s0, s1, s2, s3, s3 - 2 * cfg['num_convs'][2]


def _final_side(cfg):
  return _sides(cfg)[-1]


def init_params(key, cfg) -> Dict[str, jnp.ndarray]:
  """Every weight from one key: He-normal kernels, unit scales, zero
  biases. Deterministic in the order of ``param_shapes``."""
  params = {}
  for i, (name, shape) in enumerate(param_shapes(cfg).items()):
    if name.endswith('kernel'):
      params[name] = nn.he_normal(jax.random.fold_in(key, i), shape)
      mix = cfg.get('conv_noise_init', 1.0)
      if mix != 1.0 and len(shape) == 4 and shape[2] == shape[3]:
        centre = jnp.zeros(shape, jnp.float32).at[
            shape[0] // 2, shape[1] // 2].set(jnp.eye(shape[2]))
        params[name] = centre + mix * params[name]
    elif name.endswith('scale'):
      params[name] = jnp.ones(shape, jnp.float32)
    else:
      params[name] = jnp.zeros(shape, jnp.float32)
  return params


# ------------------------------------------------- the program's tree names

def _program_bn_names(cfg) -> Dict[str, str]:
  """Flax numbers the un-named BatchNorms in call order: action, fc0, fc1."""
  names = {'fcgrasp/bn': 'BatchNorm_0'}
  for l in range(cfg['hid_layers']):
    names[f'fc{l}/bn'] = f'BatchNorm_{l + 1}'
  return names


def program_path(name: str, cfg) -> tuple:
  """Where the program's ``params`` tree keeps the reference's ``name``."""
  head, leaf = name.rsplit('/', 1)
  if head.endswith('/bn'):
    if head in _program_bn_names(cfg):
      return (_program_bn_names(cfg)[head], leaf)
    return (head[:-3], 'BatchNorm_0', leaf)
  if head.startswith('conv') and head != 'conv1_1':
    return (head, 'Conv_0', leaf)
  return (head, leaf)


# ------------------------------------------------------------ the mathematics

def preprocess(batch: Dict, key, cfg) -> Dict:
  """One crop offset for the whole batch (``RandomCropImages``), then
  [0, 1]. ``key`` is the step's preprocessing key."""
  crop_key, _ = jax.random.split(key)
  key_h, key_w = jax.random.split(crop_key)
  th, tw = cfg['crop_size']
  image = batch['features/state/image']
  h, w = image.shape[1:3]
  oh = jax.random.randint(key_h, (), 0, h - th + 1)
  ow = jax.random.randint(key_w, (), 0, w - tw + 1)
  image = jax.lax.dynamic_slice(
      image, (0, oh, ow, 0), (image.shape[0], th, tw, image.shape[3]))
  image = jnp.clip(image.astype(jnp.float32) / 255.0, 0.0, 1.0)
  action = jnp.concatenate(
      [batch['features/action/world_vector'],
       batch['features/action/vertical_rotation']], axis=-1)
  return {'image': image, 'action': action.astype(jnp.float32),
          'reward': batch['labels/reward'].astype(jnp.float32)}


def loss(params: Dict, inputs: Dict, cfg,
         quant: Optional[str] = None) -> jnp.ndarray:
  p = params
  first, second, third = _conv_names(cfg)

  def conv_bn(x, name, padding):
    x = nn.conv(x, p[f'{name}/kernel'], 1, padding, quant)
    return jax.nn.relu(nn.batch_norm(
        x, p[f'{name}/bn/bias'], p[f'{name}/bn/scale'], EPS, quant))

  x = nn.conv(inputs['image'], p['conv1_1/kernel'], 2, 'SAME', quant)
  x = jax.nn.relu(nn.batch_norm(x, p['bn1/bias'], None, EPS, quant))
  x = nn.max_pool(x, 3, 3, 'SAME')
  for name, _, padding in first:
    x = conv_bn(x, name, padding)
  x = nn.max_pool(x, 3, 3, 'SAME')

  a = nn.dense(inputs['action'], p['fcgrasp/kernel'], None, quant)
  a = jax.nn.relu(nn.batch_norm(
      a, p['fcgrasp/bn/bias'], None, EPS, quant))
  a = nn.dense(a, p['fcgrasp2/kernel'], p['fcgrasp2/bias'], quant)
  x = nn.stored(x + a[:, None, None, :], quant)

  for name, _, padding in second:
    x = conv_bn(x, name, padding)
  x = nn.max_pool(x, 2, 2, 'SAME')
  for name, _, padding in third:
    x = conv_bn(x, name, padding)
  x = x.reshape((x.shape[0], -1))
  for l in range(cfg['hid_layers']):
    x = nn.dense(x, p[f'fc{l}/kernel'], None, quant)
    x = jax.nn.relu(nn.batch_norm(
        x, p[f'fc{l}/bn/bias'], p[f'fc{l}/bn/scale'], EPS, quant))
  logit = nn.dense(x, p['logit/kernel'], p['logit/bias'], quant)
  q = jnp.clip(jax.nn.sigmoid(logit[:, 0]), 1e-7, 1.0 - 1e-7)
  r = inputs['reward'].reshape(q.shape)
  return -jnp.mean(r * jnp.log(q) + (1.0 - r) * jnp.log(1.0 - q))


# ---------------------------------------------------------------- the work

def layers(cfg) -> List[Dict]:
  """Every convolution and matrix product of one example's forward pass,
  by shape: what ``benchmark/lib/flops.py`` counts."""
  ch = cfg['tower_channels']
  s0, s1, s2, s3, _ = _sides(cfg)
  first, second, third = _conv_names(cfg)
  out = [dict(name='conv1_1', out_hw=(s0, s0), k=6, cin=3, cout=ch,
              input_grad=False)]
  for name, k, _ in first:
    out.append(dict(name=name, out_hw=(s1, s1), k=k, cin=ch, cout=ch))
  for name, k, _ in second:
    out.append(dict(name=name, out_hw=(s2, s2), k=k, cin=ch, cout=ch))
  side = s3
  for name, k, _ in third:
    side -= 2
    out.append(dict(name=name, out_hw=(side, side), k=k, cin=ch, cout=ch))
  out.append(dict(name='fcgrasp', out_hw=(1, 1), k=1, cin=cfg['action_size'],
                  cout=cfg['action_hidden'], input_grad=False))
  out.append(dict(name='fcgrasp2', out_hw=(1, 1), k=1,
                  cin=cfg['action_hidden'], cout=ch))
  fan = side * side * ch
  for l in range(cfg['hid_layers']):
    out.append(dict(name=f'fc{l}', out_hw=(1, 1), k=1, cin=fan,
                    cout=cfg['fc_hidden']))
    fan = cfg['fc_hidden']
  out.append(dict(name='logit', out_hw=(1, 1), k=1, cin=fan, cout=1))
  return out
