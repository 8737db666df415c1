"""Grasp2Vec (arXiv:1811.06964): two ResNet-50 v2 towers and N-pairs.

A scene tower embeds the pre-grasp and the post-grasp frame (one batch
of 2B), a goal tower the frame of the grasped object. A tower is a
pre-activation bottleneck ResNet-50 (arXiv:1603.05027: 7x7/2 conv, 3x3/2
max pool, 3+4+6+3 blocks of 1x1 → 3x3 → 1x1 with a projection on the
first block of each stage, batch norm epsilon 1e-5), a last batch norm
and relu, and the mean over positions: a 2048-vector. The loss is
N-pairs in both directions between ``pre - post`` and ``goal``.
Training preprocessing (one crop window a batch for the scene pair and
one for the goal, one left-right and one up-down flip a frame kind,
[0, 1]) is part of the timed step, so it is part of this reference.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from benchmark.reference import nn

EPS = 1e-5
TOWERS = ('scene', 'goal')
OPTIMIZER = {'kind': 'adam', 'learning_rate': 1e-4, 'b1': 0.9, 'b2': 0.999,
             'eps': 1e-8}
FRAME_KEYS = ('pregrasp_image', 'postgrasp_image', 'goal_image')


def _blocks(cfg):
  """(name, in channels, bottleneck width, stride, has projection)."""
  out, cin = [], cfg['num_filters']
  for i, count in enumerate(cfg['block_sizes']):
    width = cfg['num_filters'] * 2 ** i
    for j in range(count):
      stride = 2 if (j == 0 and i > 0) else 1
      out.append((f'block_layer{i + 1}_block{j}', cin, width, stride, j == 0))
      cin = width * 4
  return out, cin


def _tower_shapes(cfg) -> Dict[str, tuple]:
  shapes = {'initial_conv/kernel': (7, 7, 3, cfg['num_filters'])}
  blocks, cout = _blocks(cfg)
  for name, cin, width, _, project in blocks:
    for bn, ch in (('bn0', cin), ('bn1', width), ('bn2', width)):
      shapes[f'{name}/{bn}/scale'] = (ch,)
      shapes[f'{name}/{bn}/bias'] = (ch,)
    shapes[f'{name}/conv1/kernel'] = (1, 1, cin, width)
    shapes[f'{name}/conv2/kernel'] = (3, 3, width, width)
    shapes[f'{name}/conv3/kernel'] = (1, 1, width, width * 4)
    if project:
      shapes[f'{name}/proj/kernel'] = (1, 1, cin, width * 4)
  shapes['final_bn/scale'] = (cout,)
  shapes['final_bn/bias'] = (cout,)
  return shapes


def param_shapes(cfg) -> Dict[str, tuple]:
  return {f'{tower}/{name}': shape for tower in TOWERS
          for name, shape in _tower_shapes(cfg).items()}


def init_params(key, cfg) -> Dict[str, jnp.ndarray]:
  params = {}
  for i, (name, shape) in enumerate(param_shapes(cfg).items()):
    if name.endswith('kernel'):
      params[name] = nn.he_normal(jax.random.fold_in(key, i), shape)
      if name.endswith('conv3/kernel'):
        params[name] = params[name] * cfg.get('branch_scale_init', 1.0)
    elif name.endswith('final_bn/scale'):
      params[name] = jnp.full(shape, cfg['embedding_scale_init'], jnp.float32)
    elif name.endswith('scale'):
      params[name] = jnp.ones(shape, jnp.float32)
    else:
      params[name] = jnp.zeros(shape, jnp.float32)
  return params


def program_path(name: str, cfg) -> tuple:
  del cfg
  tower, rest = name.split('/', 1)
  parts = rest.split('/')
  if parts[0] == 'final_bn':
    return (tower, 'resnet', '_BatchNorm_0', 'BatchNorm_0', parts[1])
  if len(parts) == 3 and parts[1].startswith('bn'):
    return (tower, 'resnet', parts[0], f'_BatchNorm_{parts[1][2:]}',
            'BatchNorm_0', parts[2])
  return (tower, 'resnet') + tuple(parts)


# ------------------------------------------------------------ the mathematics

def _crop(key, images, crop):
  min_oh, max_oh, th, min_ow, max_ow, tw = crop
  key_h, key_w = jax.random.split(key)
  oh = jax.random.randint(key_h, (), min_oh, max(max_oh, min_oh + 1))
  ow = jax.random.randint(key_w, (), min_ow, max(max_ow, min_ow + 1))
  return [jax.lax.dynamic_slice(
      x, (0, oh, ow, 0), (x.shape[0], th, tw, x.shape[3])) for x in images]


def preprocess(batch: Dict, key, cfg) -> Dict:
  keys = jax.random.split(key, 3)
  pre, post = _crop(keys[0], [batch['features/pregrasp_image'],
                              batch['features/postgrasp_image']],
                    cfg['scene_crop'])
  (goal,) = _crop(keys[1], [batch['features/goal_image']], cfg['goal_crop'])
  out = {}
  for i, (name, image) in enumerate(zip(FRAME_KEYS, (pre, post, goal))):
    image = image.astype(jnp.float32) / 255.0
    key_lr, key_ud = jax.random.split(jax.random.fold_in(keys[2], i))
    image = jnp.where(jax.random.bernoulli(key_lr), image[:, :, ::-1], image)
    image = jnp.where(jax.random.bernoulli(key_ud), image[:, ::-1], image)
    out[name] = image
  return out


def _strided(x, kernel, stride, quant):
  """The official model's ``fixed_padding``: explicit symmetric padding
  before a strided conv, SAME otherwise."""
  if stride == 1:
    return nn.conv(x, kernel, 1, 'SAME', quant)
  total = kernel.shape[0] - 1
  pad = (total // 2, total - total // 2)
  return nn.conv(x, kernel, stride, (pad, pad), quant)


def tower(p: Dict, prefix: str, images, cfg, quant: Optional[str]):
  x = _strided(images, p[f'{prefix}/initial_conv/kernel'], 2, quant)
  x = nn.max_pool(x, 3, 2, ((1, 1), (1, 1)))
  blocks, _ = _blocks(cfg)

  def block(x, weights, stride, project):
    pre = jax.nn.relu(nn.batch_norm(
        x, weights['bn0/bias'], weights['bn0/scale'], EPS, quant))
    shortcut = (_strided(pre, weights['proj/kernel'], stride, quant)
                if project else x)
    y = nn.conv(pre, weights['conv1/kernel'], 1, 'SAME', quant)
    y = jax.nn.relu(nn.batch_norm(
        y, weights['bn1/bias'], weights['bn1/scale'], EPS, quant))
    y = _strided(y, weights['conv2/kernel'], stride, quant)
    y = jax.nn.relu(nn.batch_norm(
        y, weights['bn2/bias'], weights['bn2/scale'], EPS, quant))
    y = nn.conv(y, weights['conv3/kernel'], 1, 'SAME', quant)
    return nn.stored(y + shortcut, quant)

  # Recompute each block's inside on the way back: the same mathematics
  # in a fraction of the memory, so that float32 at the timed batch fits
  # beside nothing else on one chip.
  block = jax.checkpoint(block, static_argnums=(2, 3))
  for name, _, _, stride, project in blocks:
    b = f'{prefix}/{name}/'
    weights = {k[len(b):]: v for k, v in p.items() if k.startswith(b)}
    x = block(x, weights, stride, project)
  x = jax.nn.relu(nn.batch_norm(
      x, p[f'{prefix}/final_bn/bias'], p[f'{prefix}/final_bn/scale'], EPS,
      quant))
  return jnp.mean(x, axis=(1, 2))


def _npairs(anchor, positive):
  logits = jnp.matmul(anchor, positive.T, precision=nn.HIGHEST)
  log_probs = jax.nn.log_softmax(logits, axis=1)
  return -jnp.mean(jnp.diagonal(log_probs))


def loss(params: Dict, inputs: Dict, cfg,
         quant: Optional[str] = None) -> jnp.ndarray:
  scene = jnp.concatenate(
      [inputs['pregrasp_image'], inputs['postgrasp_image']], axis=0)
  # One tower at a time on the way back too (see ``tower``'s blocks).
  run = jax.checkpoint(
      lambda p, prefix, x: tower(p, prefix, x, cfg, quant),
      static_argnums=(1,))
  scene_v = run(params, 'scene', scene)
  goal_v = run(params, 'goal', inputs['goal_image'])
  pre_v, post_v = jnp.split(scene_v, 2, axis=0)
  pair = pre_v - post_v
  return _npairs(pair, goal_v) + _npairs(goal_v, pair)


# ---------------------------------------------------------------- the work

def _tower_layers(cfg, side_in, frames) -> List[Dict]:
  def after(side, k, stride):  # explicit symmetric padding of k - 1
    return (side + (k - 1) - k) // stride + 1

  side = after(side_in, 7, 2)
  out = [dict(name='initial_conv', out_hw=(side, side), k=7, cin=3,
              cout=cfg['num_filters'], input_grad=False, per_example=frames)]
  side = after(side, 3, 2)
  blocks, _ = _blocks(cfg)
  for name, cin, width, stride, project in blocks:
    side_out = side if stride == 1 else after(side, 3, stride)
    if project:
      out.append(dict(name=f'{name}/proj', out_hw=(side_out, side_out), k=1,
                      cin=cin, cout=width * 4, per_example=frames))
    out.append(dict(name=f'{name}/conv1', out_hw=(side, side), k=1, cin=cin,
                    cout=width, per_example=frames))
    out.append(dict(name=f'{name}/conv2', out_hw=(side_out, side_out), k=3,
                    cin=width, cout=width, per_example=frames))
    out.append(dict(name=f'{name}/conv3', out_hw=(side_out, side_out), k=1,
                    cin=width, cout=width * 4, per_example=frames))
    side = side_out
  return out


def layers(cfg) -> List[Dict]:
  """Per example: two frames through the scene tower, one through the
  goal tower. The B x B N-pairs products are left out (under a millionth
  of the towers)."""
  scene = _tower_layers(cfg, cfg['scene_crop'][2], 2)
  goal = _tower_layers(cfg, cfg['goal_crop'][2], 1)
  return ([dict(l, name=f'scene/{l["name"]}') for l in scene] +
          [dict(l, name=f'goal/{l["name"]}') for l in goal])
