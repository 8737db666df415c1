"""Pallas TPU fused max-pool: argmax-emitting forward, gather backward.

The roofline (PERF_NOTES rounds 3–5) pinned the qtopt pool1 pair as the
single largest XLA-floor overshoot in the step: forward 0.61 ms at 2.0×
its HBM bound, backward 1.44 ms at 2.4× — the backward is a
``select-and-scatter`` that re-reads the full pre-pool activation to
re-discover which element won each window. This kernel removes that
re-discovery: the forward emits the winning *window slot* alongside the
pooled value (an int32 at OUTPUT resolution — 1/(k·k) the spatial
elements of the input the backward no longer touches), and the backward
is a pure routing pass over ``(grad, slot)`` pairs that writes dx once.
Per qtopt pool1 ([32, 236, 236, 64] bf16, 3×3/s3): select-and-scatter
moves ~482 MB; the routed backward reads 51 MB of slots + 25 MB of
cotangent and writes the 460 MB dx — at the write's bandwidth bound.

Semantics are bitwise those of ``flax.linen.max_pool`` + autodiff:

* padding contributes ``-inf`` (never selected against finite data);
* ties route the cotangent to the FIRST maximal element in row-major
  window order (XLA's select-and-scatter convention) — the forward
  updates the winner only on strictly-greater;
* overlapping windows (stride < window) accumulate their cotangents in
  ascending window order per input element (the backward iterates slots
  in reverse, which visits windows forward — f32 addition is
  commutative pairwise, so matching XLA bit-for-bit requires matching
  its order only when ≥ 3 windows select one element).

Dispatch follows the flash_attention contract (ops/_pallas_dispatch):
interpret mode off-TPU so the tier-1 suite runs this exact kernel code;
:func:`max_pool` is the size-gated entry that falls back to the stock
``lax.reduce_window`` form when the gate or :func:`is_supported` says
no (off-TPU training, exotic shapes, VMEM-overflowing blocks).

On the chip: compiled for a described TPU v5e (jax 0.9.0), this kernel
lowers only for float32, stride == window, and maps small enough for 12
tile-padded input blocks to fit 16 MB of scoped VMEM (about 46x46 at
C <= 128). Neither QT-Opt pool ([32,236,236,64], [32,79,79,64]) is
inside that, in either dtype, so ``kernel_policy='pool'`` on the
published Grasping44 runs the XLA reference and says so
(``_pallas_dispatch.refuse``). A row-tiled kernel would be needed to
change that; whether it is worth writing is a chip-number decision.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from tensor2robot_tpu.ops import _pallas_dispatch as dispatch

Pads = Tuple[Tuple[int, int], Tuple[int, int]]

# Interpret-mode block sizing only (no VMEM exists there): keeps the
# interpreter's per-instance working set small enough to stay fast.
_INTERPRET_BUDGET_BYTES = 10 * 1024 * 1024

_CHANNEL_BLOCKS = (128, 64, 32, 16, 8)

# Scoped VMEM one forward instance takes, in multiples of its padded,
# tile-aligned input block. Measured by compiling 3x3/s3 and 2x2/s2
# float32 pools for a described v5e (the compiler reports the
# allocation when it refuses one): 10.4-10.7x over H=W in 60..96, 8.3x
# for 2x2. 12 leaves Mosaic's own staging some room.
_MOSAIC_VMEM_BLOCKS = 12


def resolve_padding(padding: Union[str, Sequence[Tuple[int, int]]],
                    window: Tuple[int, int],
                    strides: Tuple[int, int],
                    spatial: Tuple[int, int]) -> Pads:
  """'SAME'/'VALID'/explicit → explicit ((lo,hi),(lo,hi)), exactly as
  ``lax.padtype_to_pads`` resolves them for ``reduce_window``."""
  if isinstance(padding, str):
    mode = padding.upper()
    if mode == 'VALID':
      return ((0, 0), (0, 0))
    if mode != 'SAME':
      raise ValueError(f'Unknown pool padding {padding!r}')
    pads = []
    for size, k, s in zip(spatial, window, strides):
      out = -(-size // s)  # ceil
      total = max((out - 1) * s + k - size, 0)
      pads.append((total // 2, total - total // 2))
    return tuple(pads)  # type: ignore[return-value]
  pads = tuple((int(lo), int(hi)) for lo, hi in padding)
  if len(pads) != 2:
    raise ValueError(f'Expected 2 spatial pad pairs, got {padding!r}')
  return pads  # type: ignore[return-value]


def _out_size(size: int, k: int, s: int, lo: int, hi: int) -> int:
  return (size + lo + hi - k) // s + 1


def _round_up(n: int, m: int) -> int:
  return -(-n // m) * m


def _interpret_channel_block(c: int, per_channel_bytes: int) -> Optional[int]:
  """Largest lane block dividing C whose working set fits the budget."""
  for cb in _CHANNEL_BLOCKS:
    if c % cb == 0 and per_channel_bytes * cb <= _INTERPRET_BUDGET_BYTES:
      return cb
  return None


def _mosaic_channel_block(c, window, strides, hp, wp, dtype) -> Optional[int]:
  """The lane block a real Mosaic lowering accepts, or None.

  What the chip's compiler showed (sandbox compile for a described v5e,
  jax 0.9.0; tests/test_chip_compile.py keeps the QT-Opt cases):

  * the lane block must be the whole of C or a multiple of 128;
  * only float32 lowers — the bf16 phase reshape is an "unsupported
    shape cast" in infer-vector-layout;
  * overlapping windows lower forward but not backward (``lax.pad``
    with interior padding is unimplemented), so stride must equal the
    window;
  * one instance's scoped VMEM is ``_MOSAIC_VMEM_BLOCKS`` tile-padded
    input blocks, against ``dispatch.MOSAIC_SCOPED_VMEM_BYTES``.
  """
  if jnp.dtype(dtype) != jnp.float32 or tuple(window) != tuple(strides):
    return None
  cb = dispatch.MOSAIC_LANES if c % dispatch.MOSAIC_LANES == 0 else c
  block_bytes = (hp * _round_up(wp, dispatch.MOSAIC_SUBLANES) *
                 _round_up(cb, dispatch.MOSAIC_LANES) * 4)
  if _MOSAIC_VMEM_BLOCKS * block_bytes > dispatch.MOSAIC_SCOPED_VMEM_BYTES:
    return None
  return cb


def _plan(shape, window, strides, pads, dtype,
          interpret: Optional[bool] = None):
  """Resolves the static kernel geometry; None when the target the call
  would lower to (the interpreter, or Mosaic on a TPU backend) cannot
  run it. ``interpret=None`` resolves from the backend."""
  if interpret is None:
    interpret = dispatch.use_interpret()
  if len(shape) != 4:
    return None
  _, h, w, c = shape
  (kh, kw), (sh, sw) = window, strides
  (plh, phh), (plw, phw) = pads
  if min(kh, kw, sh, sw) < 1 or kh * kw > 64:
    return None
  if min(plh, phh, plw, phw) < 0:
    return None
  if max(plh, phh) >= kh or max(plw, phw) >= kw:
    # A window lying fully inside padding has no data element to route
    # its (zero) cotangent to; SAME/VALID never produce such pads.
    return None
  if not np.issubdtype(np.dtype(dtype), np.floating):
    # numpy's floats only: bfloat16 is refused on both targets (Mosaic
    # cannot lower its phase reshape; the interpreter never ran it).
    return None
  oh = _out_size(h, kh, sh, plh, phh)
  ow = _out_size(w, kw, sw, plw, phw)
  if oh < 1 or ow < 1 or c % _CHANNEL_BLOCKS[-1]:
    return None
  hp, wp = oh * sh + kh - 1, ow * sw + kw - 1
  if interpret:
    itemsize = np.dtype(dtype).itemsize
    # Padded input (fwd) / assembly accumulator (bwd) dominate; slots
    # and pooled blocks ride along.
    per_channel = 3 * hp * wp * itemsize + 2 * oh * ow * (itemsize + 4)
    cb = _interpret_channel_block(c, per_channel)
  else:
    cb = _mosaic_channel_block(c, window, strides, hp, wp, dtype)
  if cb is None:
    return None
  return dict(h=h, w=w, c=c, kh=kh, kw=kw, sh=sh, sw=sw, plh=plh, plw=plw,
              oh=oh, ow=ow, hp=hp, wp=wp, cb=cb)


def is_supported(shape: Sequence[int],
                 window: Tuple[int, int],
                 strides: Tuple[int, int],
                 padding: Union[str, Sequence[Tuple[int, int]]] = 'VALID',
                 dtype=jnp.float32,
                 interpret: Optional[bool] = None) -> bool:
  """Whether the Pallas pool handles an NHWC problem on the target the
  call would lower to — the dispatch predicate :func:`max_pool` (and the
  kernel-policy towers) consult before committing to the kernel path.
  ``interpret=None`` resolves from the backend; ``False`` asks about a
  real Mosaic lowering (see :func:`_mosaic_channel_block`)."""
  shape = tuple(int(d) for d in shape)
  if len(shape) != 4:
    return False
  pads = resolve_padding(padding, window, strides, shape[1:3])
  return _plan(shape, tuple(window), tuple(strides), pads, dtype,
               interpret) is not None


# ----------------------------------------------------------------- kernels


def _neg_inf(dtype):
  return jnp.array(-jnp.inf, dtype)


def _pad_neg_inf(x, plh, plw, hp, wp):
  """[-inf]-pads an [H, W, cb] block to [hp, wp, cb] (lo = pool pad, hi
  = pool pad + slice filler; filler positions are never selected)."""
  h, w, cb = x.shape
  dt = x.dtype
  # Zero-extent pieces are skipped: Mosaic rejects a zero-size
  # broadcast, and SAME pools routinely have no low-side padding.
  def fill(shape):
    return [jnp.full(shape, _neg_inf(dt))] if min(shape) else []

  x = jnp.concatenate(
      fill((plh, w, cb)) + [x] + fill((hp - h - plh, w, cb)), axis=0)
  return jnp.concatenate(
      fill((hp, plw, cb)) + [x] + fill((hp, wp - w - plw, cb)), axis=1)


def _window_slices(xp, kh, kw, sh, sw, oh, ow, wp, cb):
  """Yields (slot, [oh, ow, cb] strided view) per window position, in
  row-major window order — the strided gather expressed as slice +
  reshape (phase decomposition), which Mosaic lowers without a
  dynamic-stride load."""
  for dy in range(kh):
    rows = xp[dy:dy + oh * sh]
    if sh > 1:
      rows = rows.reshape(oh, sh, wp, cb)[:, 0]
    for dx in range(kw):
      vals = rows[:, dx:dx + ow * sw]
      if sw > 1:
        vals = vals.reshape(oh, ow, sw, cb)[:, :, 0]
      yield dy * kw + dx, vals


def _pool_fwd_kernel(x_ref, out_ref, idx_ref, *, kh, kw, sh, sw, plh, plw,
                     oh, ow, hp, wp):
  x = x_ref[0]
  cb = x.shape[-1]
  xp = _pad_neg_inf(x, plh, plw, hp, wp)
  best = jnp.full((oh, ow, cb), _neg_inf(x.dtype))
  slot_idx = jnp.zeros((oh, ow, cb), jnp.int32)
  for slot, vals in _window_slices(xp, kh, kw, sh, sw, oh, ow, wp, cb):
    if slot == 0:
      best = vals
      continue
    # Strictly-greater keeps the FIRST maximal slot — XLA's
    # select-and-scatter tie routing.
    take = vals > best
    best = jnp.where(take, vals, best)
    slot_idx = jnp.where(take, jnp.int32(slot), slot_idx)
  out_ref[0] = best
  idx_ref[0] = slot_idx


def _pool_bwd_kernel(g_ref, idx_ref, dx_ref, *, kh, kw, sh, sw, plh, plw,
                     oh, ow, h, w):
  g = g_ref[0]
  slot_idx = idx_ref[0]
  cb = g.shape[-1]
  zero = jnp.zeros((), g.dtype)

  def routed(slot):
    return jnp.where(slot_idx == slot, g, zero)

  if sh == kh and sw == kw:
    # Non-overlapping: every input element belongs to exactly one
    # window — the routed cotangents interleave straight into dx.
    row_blocks = []
    for dy in range(kh):
      cols = [routed(dy * kw + dx) for dx in range(kw)]
      row = jnp.stack(cols, axis=2).reshape(oh, ow * kw, cb)
      row_blocks.append(row)
    full = jnp.stack(row_blocks, axis=1).reshape(oh * kh, ow * kw, cb)
    need_h, need_w = plh + h, plw + w
    if full.shape[0] < need_h or full.shape[1] < need_w:
      # VALID pools whose tail elements fall in no window: those dx
      # rows/cols are zero and the interleave never produced them.
      full = jax.lax.pad(
          full, zero,
          ((0, max(0, need_h - full.shape[0]), 0),
           (0, max(0, need_w - full.shape[1]), 0), (0, 0, 0)))
    dx_ref[0] = full[plh:plh + h, plw:plw + w]
    return

  # Overlapping windows: accumulate each slot's routed cotangent into
  # the padded extent, dilated by the stride and offset by the slot.
  # Reverse slot order visits the windows covering any one input
  # element in ascending (oh, ow) order — XLA's accumulation order.
  ht, wt = oh * sh + kh - 1, ow * sw + kw - 1
  acc = jnp.zeros((ht, wt, cb), g.dtype)
  for dy in reversed(range(kh)):
    for dx in reversed(range(kw)):
      contrib = routed(dy * kw + dx)
      acc = acc + jax.lax.pad(
          contrib, zero,
          ((dy, ht - dy - (oh - 1) * sh - 1, sh - 1),
           (dx, wt - dx - (ow - 1) * sw - 1, sw - 1), (0, 0, 0)))
  dx_ref[0] = acc[plh:plh + h, plw:plw + w]


# -------------------------------------------------------------- public api


def _pool_call(x, plan):
  b, _, _, c = x.shape
  cb, oh, ow = plan['cb'], plan['oh'], plan['ow']
  kern = functools.partial(
      _pool_fwd_kernel, kh=plan['kh'], kw=plan['kw'], sh=plan['sh'],
      sw=plan['sw'], plh=plan['plh'], plw=plan['plw'], oh=oh, ow=ow,
      hp=plan['hp'], wp=plan['wp'])
  return pl.pallas_call(
      kern,
      grid=(b, c // cb),
      in_specs=[
          pl.BlockSpec((1, plan['h'], plan['w'], cb),
                       lambda i, j: (i, 0, 0, j)),
      ],
      out_specs=[
          pl.BlockSpec((1, oh, ow, cb), lambda i, j: (i, 0, 0, j)),
          pl.BlockSpec((1, oh, ow, cb), lambda i, j: (i, 0, 0, j)),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((b, oh, ow, c), x.dtype),
          jax.ShapeDtypeStruct((b, oh, ow, c), jnp.int32),
      ],
      interpret=dispatch.use_interpret(),
  )(x)


def _pool_grad_call(g, slot_idx, xshape, plan):
  b, h, w, c = xshape
  cb, oh, ow = plan['cb'], plan['oh'], plan['ow']
  kern = functools.partial(
      _pool_bwd_kernel, kh=plan['kh'], kw=plan['kw'], sh=plan['sh'],
      sw=plan['sw'], plh=plan['plh'], plw=plan['plw'], oh=oh, ow=ow,
      h=h, w=w)
  return pl.pallas_call(
      kern,
      grid=(b, c // cb),
      in_specs=[
          pl.BlockSpec((1, oh, ow, cb), lambda i, j: (i, 0, 0, j)),
          pl.BlockSpec((1, oh, ow, cb), lambda i, j: (i, 0, 0, j)),
      ],
      out_specs=pl.BlockSpec((1, h, w, cb), lambda i, j: (i, 0, 0, j)),
      out_shape=jax.ShapeDtypeStruct((b, h, w, c), g.dtype),
      interpret=dispatch.use_interpret(),
  )(g, slot_idx)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def pallas_max_pool(x, window: Tuple[int, int], strides: Tuple[int, int],
                    pads: Pads):
  """NHWC max pool via the Pallas kernel; ``pads`` explicit (resolve
  with :func:`resolve_padding`). Raises on unsupported geometry — use
  :func:`max_pool` for the gated, falling-back entry point."""
  out, _ = _pool_vjp_fwd(x, window, strides, pads)
  return out


def max_pool_argmax(x, window: Tuple[int, int], strides: Tuple[int, int],
                    pads: Pads):
  """(pooled, window-slot argmax) — the forward with its routing table
  exposed (slots are row-major window positions, int32)."""
  plan = _plan(x.shape, window, strides, pads, x.dtype)
  if plan is None:
    raise ValueError(
        f'pallas max_pool unsupported for shape {x.shape} window '
        f'{window} strides {strides} pads {pads} (see is_supported).')
  return _pool_call(x, plan)


def _pool_vjp_fwd(x, window, strides, pads):
  out, slot_idx = max_pool_argmax(x, window, strides, pads)
  return out, (slot_idx, x.shape)


def _pool_vjp_bwd(window, strides, pads, res, g):
  slot_idx, xshape = res
  plan = _plan(xshape, window, strides, pads, g.dtype)
  return (_pool_grad_call(g, slot_idx, xshape, plan),)


pallas_max_pool.defvjp(_pool_vjp_fwd, _pool_vjp_bwd)


def reference_max_pool(x, window_shape, strides=None, padding='VALID'):
  """The stock XLA form (exactly ``flax.linen.max_pool``): the fallback
  arm of the dispatch and the parity oracle for the tests."""
  strides = tuple(strides or (1,) * len(window_shape))
  dims = (1,) + tuple(window_shape) + (1,)
  steps = (1,) + strides + (1,)
  if not isinstance(padding, str):
    padding = ((0, 0),) + tuple(
        (lo, hi) for lo, hi in padding) + ((0, 0),)
  return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, dims, steps,
                               padding)


def max_pool(x, window_shape, strides=None, padding='VALID',
             enabled: Optional[bool] = None):
  """Drop-in for ``nn.max_pool`` call sites behind the kernel gate.

  Takes the Pallas kernel when the dispatch gate is live
  (:func:`_pallas_dispatch.kernels_enabled` — TPU, or forced for tests)
  AND the geometry is supported; otherwise the stock ``reduce_window``
  form, bitwise-identical either way. A live gate with unsupported
  geometry says so (:func:`_pallas_dispatch.refuse`): the call site
  asked for the kernel through its ``kernel_policy``.
  """
  window = tuple(window_shape)
  strides = tuple(strides or (1,) * len(window))
  if enabled is None:
    enabled = dispatch.kernels_enabled()
  if enabled and len(window) == 2 and x.ndim == 4:
    pads = resolve_padding(padding, window, strides, x.shape[1:3])
    if _plan(x.shape, window, strides, pads, x.dtype) is not None:
      return pallas_max_pool(x, window, strides, pads)
  if enabled:
    dispatch.refuse(
        'max_pool',
        f'x {x.dtype.name}{list(x.shape)} window {window} strides '
        f'{strides} padding {padding!r} is outside '
        'ops.pool.is_supported')
  return reference_max_pool(x, window_shape, strides, padding)
