"""Fused optimizer + EMA + nonfinite-select update as ONE Pallas pass.

The stock update path is an elementwise op soup XLA leaves as several
HBM round-trips over every parameter: Adam's moment updates, the bias
corrections, the scaled apply, the EMA blend, and the nonfinite guard's
``where(ok, new, old)`` each read/write the full parameter footprint.
This module runs the whole chain — moments, update, apply, EMA,
select — as a single elementwise kernel over flattened parameter
blocks: each leaf is read once and written once.

Dispatch contract (``ops/_pallas_dispatch.py``, same as PR 15's
pool/conv kernels): the fused path is taken only when
``dispatch.kernels_enabled()`` (TPU, or ``force_kernels()`` /
``T2R_FORCE_PALLAS_KERNELS=1`` in tests); off-TPU and off-gate the
trainer keeps the stock optax path, bit for bit. Off-TPU forced runs go
through the Pallas interpreter (``dispatch.use_interpret()``), which is
how the CPU tier-1 suite drills the kernel's values.

Recognition is by TAGGING, not introspection: the factories in
``models/optimizers.py`` return a :class:`TaggedGradientTransformation`
(a duck-typed ``(init, update, fused_spec)`` NamedTuple — optax only
ever touches ``.init``/``.update``) carrying the hyperparameters the
kernel needs. Anything untagged — clipping chains, ``MultiSteps``
wrappers, custom transformations, QT-Opt's own momentum optimizer —
keeps the stock path, as does any opt-state whose structure the plan
doesn't recognize; ``plan_for`` says so with a WARNING and a
``kernels/refused`` count. Compiled for a described TPU v5e
(tests/test_chip_compile.py) the pass lowers at the Grasping44 parameter
shapes: one ``tpu_custom_call`` per leaf.

Supported optimizer kinds:

* ``'adam'`` — ``optax.adam`` (constant or schedule learning rate);
  the opt state's ``ScaleByAdamState`` (count, mu, nu) and an optional
  ``ScaleByScheduleState`` are rebuilt in their optax types, so
  checkpoints are interchangeable with stock runs.
* ``'sgd'`` — plain ``optax.sgd`` (no momentum; constant or schedule
  learning rate).

Parity: the kernel evaluates the same f32 expressions as optax's
``scale_by_adam`` + ``scale(-lr)`` + ``apply_updates`` in the same
order, but a fused single-expression evaluation is not guaranteed
bitwise against XLA's fission of the stock graph — the accepted band is
documented and pinned by tests/test_device_feed.py (atol 1e-6 /
rtol 1e-5 on f32 params after multi-step training).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import optax

from tensor2robot_tpu.ops import _pallas_dispatch as dispatch

# jax.experimental.pallas is imported lazily inside _leaf_update: this
# module rides along with models/optimizers.py into every process
# (including jax.distributed workers), and importing Pallas there is
# both wasted start-up time and fatal on worker teardown.

# Lane width of every block: the TPU vector lane count. Interpret mode
# accepts any 8-aligned block, so one geometry serves both paths.
_LANES = 128
# Rows per grid block: 1024×128×4B = 512 KiB per operand buffer; with
# Adam's 7 inputs + 4 outputs that keeps VMEM residency under ~6 MiB.
_MAX_BLOCK_ROWS = 1024


class FusedSpec(NamedTuple):
  """Hyperparameters a tagged optimizer carries for the fused kernel."""

  kind: str                                  # 'adam' | 'sgd' | ...
  learning_rate: Union[float, Callable[[Any], Any]]
  b1: float = 0.9
  b2: float = 0.999
  eps: float = 1e-8


class TaggedGradientTransformation(NamedTuple):
  """``optax.GradientTransformation`` + the fused-update spec.

  Duck-typed: optax and the trainer only use ``.init``/``.update``, so
  this composes everywhere a plain transformation does; wrapping it
  (``optax.chain``, ``MultiSteps``) drops the tag, which is correct —
  the wrapper changed the update math the kernel would have fused.
  """

  init: Callable
  update: Callable
  fused_spec: FusedSpec


def tag(optimizer: optax.GradientTransformation,
        spec: FusedSpec) -> TaggedGradientTransformation:
  return TaggedGradientTransformation(
      init=optimizer.init, update=optimizer.update, fused_spec=spec)


def spec_of(optimizer) -> Optional[FusedSpec]:
  spec = getattr(optimizer, 'fused_spec', None)
  return spec if isinstance(spec, FusedSpec) else None


@dataclasses.dataclass(frozen=True)
class FusedPlan:
  """A trace-time decision to run the fused pass (see :func:`plan_for`)."""

  spec: FusedSpec
  ema_decay: Optional[float] = None


_RECOGNIZED_STATES = (optax.ScaleByAdamState, optax.ScaleByScheduleState)


def _find_states(opt_state, state_type) -> list:
  found = []

  def visit(s):
    if isinstance(s, state_type):
      found.append(s)
    return s

  jax.tree_util.tree_map(
      visit, opt_state, is_leaf=lambda s: isinstance(s, _RECOGNIZED_STATES))
  return found


def supports_state(spec: FusedSpec, opt_state) -> bool:
  """Whether ``opt_state``'s structure matches what ``spec`` fuses.

  The kernel rebuilds the optax state types it recognizes; ANY other
  array-bearing state (a chained transform's trace buffers, MultiSteps
  accumulators) means the plan would silently drop updates — reject and
  let the stock path run.
  """
  try:
    adams = _find_states(opt_state, optax.ScaleByAdamState)
    scheds = _find_states(opt_state, optax.ScaleByScheduleState)
    if spec.kind == 'adam' and len(adams) != 1:
      return False
    if spec.kind == 'sgd' and adams:
      return False
    if len(scheds) > 1:
      return False
    if callable(spec.learning_rate) and spec.kind == 'sgd' and not scheds:
      return False
    remainder = jax.tree_util.tree_map(
        lambda s: None, opt_state,
        is_leaf=lambda s: isinstance(s, _RECOGNIZED_STATES))
    return not jax.tree_util.tree_leaves(remainder)
  except Exception:  # pylint: disable=broad-except
    return False


def plan_for(optimizer, ema_decay: Optional[float] = None,
             opt_state=None) -> Optional[FusedPlan]:
  """The fused plan for ``optimizer``, or None for the stock path.

  None whenever the kernel gate is off (``dispatch.kernels_enabled()``
  consulted at trace/build time), the optimizer is untagged or of an
  unsupported kind, or ``opt_state`` (when provided) has structure the
  kernel doesn't rebuild. The caller asked for the fused pass
  (``TrainerConfig.fused_update``), so each of these is announced
  through ``dispatch.refuse`` — a stock run is never silent about it.
  """
  if not dispatch.kernels_enabled():
    dispatch.refuse('fused_update', 'kernel gate off: no TPU and no force')
    return None
  spec = spec_of(optimizer)
  if spec is None or spec.kind not in ('adam', 'sgd'):
    dispatch.refuse(
        'fused_update',
        'optimizer is not a tagged adam/sgd factory from '
        'models/optimizers.py')
    return None
  if opt_state is not None and not supports_state(spec, opt_state):
    dispatch.refuse(
        'fused_update',
        'opt_state structure not recognized (wrapped/chained transforms)')
    return None
  return FusedPlan(spec=spec, ema_decay=ema_decay)


# ----------------------------------------------------------------- kernel


def _round_up(n: int, m: int) -> int:
  return ((n + m - 1) // m) * m


def _make_kernel(kind: str, has_ema: bool, guard: bool,
                 b1: float, b2: float, eps: float, decay: float):
  """One elementwise pass: moments → update → apply → EMA → select.

  ``refs`` order mirrors the input/output lists _leaf_update builds:
  scal, p, g[, mu, nu][, ema] → p'[, mu', nu'][, ema']. The scalar tile
  carries the TRACED values (lr, bias corrections, the guard flag);
  everything static is baked into the closure.
  """

  def kernel(scal_ref, *refs):
    lr = scal_ref[0, 0]
    i = 0
    p = refs[i][...]
    g = refs[i + 1][...]
    i += 2
    mu = nu = ema = None
    if kind == 'adam':
      mu = refs[i][...]
      nu = refs[i + 1][...]
      i += 2
    if has_ema:
      ema = refs[i][...]
      i += 1
    outs = refs[i:]
    if kind == 'adam':
      # Same expressions, same order, as optax scale_by_adam: moment
      # update (1-b)·g + b·m, bias correction by division, eps OUTSIDE
      # the sqrt (eps_root = 0).
      c1 = scal_ref[0, 1]
      c2 = scal_ref[0, 2]
      new_mu = (1.0 - b1) * g + b1 * mu
      new_nu = (1.0 - b2) * (g * g) + b2 * nu
      update = (new_mu / c1) / (jnp.sqrt(new_nu / c2) + eps)
    else:
      update = g
    new_p = p - lr * update
    results = [new_p]
    olds = [p]
    if kind == 'adam':
      results += [new_mu, new_nu]
      olds += [mu, nu]
    if has_ema:
      results.append(ema * decay + new_p * (1.0 - decay))
      olds.append(ema)
    if guard:
      ok = scal_ref[0, 3] > 0.0
      results = [jnp.where(ok, n, o) for n, o in zip(results, olds)]
    for ref, val in zip(outs, results):
      ref[...] = val

  return kernel


def _leaf_update(kind: str, guard: bool, spec: FusedSpec,
                 decay: Optional[float], scal, p, g, mu, nu, ema):
  """Runs the fused pass over one flattened, lane-padded leaf."""
  from jax.experimental import pallas as pl  # deferred: see module header

  has_ema = ema is not None
  shape, dtype = jnp.shape(p), jnp.asarray(p).dtype
  n = int(math.prod(shape)) if shape else 1
  rows = max(1, -(-n // _LANES))
  block_rows = min(_MAX_BLOCK_ROWS, _round_up(rows, 8))
  rows_padded = _round_up(rows, block_rows)
  total = rows_padded * _LANES

  def prep(x):
    flat = jnp.ravel(jnp.asarray(x)).astype(dtype)
    return jnp.pad(flat, (0, total - n)).reshape(rows_padded, _LANES)

  inputs = [scal, prep(p), prep(g)]
  if kind == 'adam':
    inputs += [prep(mu), prep(nu)]
  if has_ema:
    inputs.append(prep(ema))
  n_out = 1 + (2 if kind == 'adam' else 0) + (1 if has_ema else 0)
  block = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
  scal_spec = pl.BlockSpec((8, _LANES), lambda i: (0, 0))
  outs = pl.pallas_call(
      _make_kernel(kind, has_ema, guard, spec.b1, spec.b2, spec.eps,
                   0.0 if decay is None else float(decay)),
      grid=(rows_padded // block_rows,),
      in_specs=[scal_spec] + [block] * (len(inputs) - 1),
      out_specs=[block] * n_out,
      out_shape=[jax.ShapeDtypeStruct((rows_padded, _LANES), dtype)] * n_out,
      interpret=dispatch.use_interpret(),
  )(*inputs)
  return [jnp.ravel(o)[:n].reshape(shape) for o in outs]


def apply_update(plan: FusedPlan, params, grads, opt_state, ema_params,
                 ok=None) -> Tuple[Any, Any, Any]:
  """The fused replacement of ``optimizer.update`` + ``apply_updates`` +
  ``apply_ema`` + the guard's param/opt/EMA select.

  ``ok`` is the nonfinite guard's device-side all-finite flag (None when
  the guard is off); when given, params/moments/EMA select old-vs-new
  INSIDE the kernel and the state counts select outside, so a bad batch
  leaves everything untouched — identical semantics to the stock
  ``where(ok, new, old)`` over the whole state.

  Returns ``(new_params, new_opt_state, new_ema_params)``; the opt state
  comes back in the same optax NamedTuple types it arrived in, so
  checkpoints round-trip against stock runs.
  """
  spec = plan.spec
  guard = ok is not None
  has_ema = ema_params is not None and plan.ema_decay is not None
  safe_inc = getattr(optax, 'safe_increment', None) or (
      optax.safe_int32_increment)

  adam_state = None
  if spec.kind == 'adam':
    adam_states = _find_states(opt_state, optax.ScaleByAdamState)
    if len(adam_states) != 1:
      raise ValueError(
          f'fused adam plan needs exactly one ScaleByAdamState; found '
          f'{len(adam_states)} — was plan_for given this opt_state?')
    adam_state = adam_states[0]
  sched_states = _find_states(opt_state, optax.ScaleByScheduleState)
  sched_state = sched_states[0] if sched_states else None

  if callable(spec.learning_rate):
    # optax scale_by_schedule applies the PRE-increment count.
    lr_count = (sched_state.count if sched_state is not None
                else adam_state.count)
    lr = jnp.asarray(spec.learning_rate(lr_count), jnp.float32)
  else:
    lr = jnp.asarray(spec.learning_rate, jnp.float32)
  c1 = c2 = jnp.asarray(1.0, jnp.float32)
  count_inc = None
  if adam_state is not None:
    count_inc = safe_inc(adam_state.count)
    # optax tree_bias_correction: 1 - b**count with the float-weak
    # python-scalar power, divided INTO the moment (matched in-kernel).
    c1 = (1.0 - jnp.asarray(spec.b1, jnp.float32) ** count_inc).astype(
        jnp.float32)
    c2 = (1.0 - jnp.asarray(spec.b2, jnp.float32) ** count_inc).astype(
        jnp.float32)
  okf = (jnp.asarray(1.0, jnp.float32) if ok is None
         else ok.astype(jnp.float32))
  # One (8, 128) f32 scalar tile shared by every leaf's pallas_call: an
  # aligned VMEM block (Mosaic-friendly; SMEM would also work) holding
  # the four traced scalars the kernel reads.
  scal = jnp.zeros((8, _LANES), jnp.float32)
  scal = (scal.at[0, 0].set(lr).at[0, 1].set(c1)
          .at[0, 2].set(c2).at[0, 3].set(okf))

  p_leaves, treedef = jax.tree_util.tree_flatten(params)
  g_leaves = treedef.flatten_up_to(grads)
  mu_leaves = (treedef.flatten_up_to(adam_state.mu)
               if adam_state is not None else [None] * len(p_leaves))
  nu_leaves = (treedef.flatten_up_to(adam_state.nu)
               if adam_state is not None else [None] * len(p_leaves))
  ema_leaves = (treedef.flatten_up_to(ema_params)
                if has_ema else [None] * len(p_leaves))

  new_p, new_mu, new_nu, new_ema = [], [], [], []
  for p, g, mu, nu, ema in zip(p_leaves, g_leaves, mu_leaves, nu_leaves,
                               ema_leaves):
    outs = _leaf_update(spec.kind, guard, spec, plan.ema_decay,
                        scal, p, g, mu, nu, ema)
    new_p.append(outs[0])
    i = 1
    if spec.kind == 'adam':
      new_mu.append(outs[i])
      new_nu.append(outs[i + 1])
      i += 2
    if ema is not None:
      new_ema.append(outs[i])

  params_out = jax.tree_util.tree_unflatten(treedef, new_p)
  ema_out = (jax.tree_util.tree_unflatten(treedef, new_ema)
             if has_ema else ema_params)

  # Identity-keyed substitution pairs: the state OBJECTS found by
  # _find_states are matched with `is`, so aliasing/recycling concerns
  # of id()-keyed maps don't apply (both old and new live for the whole
  # call).
  replacements = []
  if adam_state is not None:
    count_out = (jnp.where(ok, count_inc, adam_state.count)
                 if guard else count_inc)
    replacements.append((adam_state, optax.ScaleByAdamState(
        count=count_out,
        mu=jax.tree_util.tree_unflatten(treedef, new_mu),
        nu=jax.tree_util.tree_unflatten(treedef, new_nu))))
  if sched_state is not None:
    sched_inc = safe_inc(sched_state.count)
    replacements.append((sched_state, optax.ScaleByScheduleState(
        count=jnp.where(ok, sched_inc, sched_state.count)
        if guard else sched_inc)))

  def substitute(s):
    for old, new in replacements:
      if s is old:
        return new
    return s

  opt_state_out = jax.tree_util.tree_map(
      substitute, opt_state,
      is_leaf=lambda s: isinstance(s, _RECOGNIZED_STATES))
  return params_out, opt_state_out, ema_out
