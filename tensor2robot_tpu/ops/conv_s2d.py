"""Pallas space-to-depth first-layer conv: the tile load IS the im2col.

The qtopt conv1 family (6×6/s2 over [B, 472, 472, 3]) is the other
XLA-floor overshoot in the roofline: fwd 1.29 ms at 3.9× its HBM bound,
dW 1.58 ms at 2.6× — a 3-input-channel convolution is an emitter corner
case (the MXU wants ≥8 sublanes of contraction; XLA's chosen form pays
layout passes instead). The classical fix is space-to-depth: regroup
stride-sized pixel blocks into channels so the conv becomes a dense
matmul over k·k·C_in-deep patches — but expressed IN XLA the regroup is
a separate transform pass that costs back more than the matmul saves
(PERF_NOTES round 5: bare s2d conv 1.43 ms vs 1.52, +0.13 ms transform,
rejected twice). Here the transform has no kernel of its own: each
Pallas instance stages the raw image block in VMEM and assembles the
[rows, k·k·C_in] patch matrix *in registers while loading tiles* (slice
+ phase-reshape per tap — the s2d regroup, fused into the load), then
runs one MXU matmul against the [k·k·C_in, C_out] reshaped kernel. The
backward follows the same recipe: dW is the patch-matrixᵀ·cotangent
matmul accumulated across the grid, dx a phase-decomposed transposed
conv (s2d duality: one small matmul per stride phase, interleaved back
on the way out).

Numerics: matmuls accumulate in f32 (``preferred_element_type``) like
XLA's conv emitter; results are banded — not bitwise — against
``lax.conv_general_dilated`` (reassociated reductions), tested at 1e-5
in f32.

Dispatch follows the flash_attention contract (ops/_pallas_dispatch):
interpret mode off-TPU so tier-1 runs the same kernel code;
:func:`conv2d` is the size-gated entry falling back to the stock
``lax.conv_general_dilated``; :class:`SpaceToDepthConv` is the flax
drop-in whose parameter tree is byte-identical to ``nn.Conv`` (kernel
``(kh, kw, cin, cout)``, optional bias), so kernel-policy-on/off
checkpoints interchange.

On the chip: this kernel has NO Mosaic lowering on the installed JAX
(0.9.0). Compiled for a described TPU v5e, every geometry is refused —
the row-block ``lax.dynamic_slice`` on a staged value is an
unimplemented primitive in the Pallas TPU lowering — and even past that
a 3-channel NHWC block pads its lane dimension 3 → 128 in VMEM, so the
QT-Opt conv1 image block alone would be 114 MB against 16 MB of scoped
VMEM. ``is_supported(interpret=False)`` is therefore False for
everything, and ``kernel_policy='pool_conv'`` on a TPU runs
``lax.conv_general_dilated`` and says so (``_pallas_dispatch.refuse``).
The kernel remains an interpret-mode numerics reference until the chip
numbers decide whether a lowering is worth writing.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tensor2robot_tpu.ops import _pallas_dispatch as dispatch
from tensor2robot_tpu.ops.pool import resolve_padding

Pads = Tuple[Tuple[int, int], Tuple[int, int]]

# Interpret-mode working-set bound (no VMEM exists there).
_INTERPRET_BUDGET_BYTES = 10 * 1024 * 1024
_ROW_BLOCKS = (16, 8, 4, 2, 1)
# The patch depth k·k·C_in this form pays off for: a deep-C_in conv is
# already MXU-shaped and XLA wins; the shallow first layer is the case.
_MAX_CIN = 8
_MAX_PATCH_DEPTH = 512


def _plan(xshape, wshape, strides, pads, interpret: Optional[bool] = None):
  """The static kernel geometry; None when the target the call would
  lower to cannot run it. Only the interpreter can (module docstring)."""
  if interpret is None:
    interpret = dispatch.use_interpret()
  if not interpret:
    return None
  if len(xshape) != 4 or len(wshape) != 4:
    return None
  _, h, w, cin = xshape
  kh, kw, wcin, cout = wshape
  (sh, sw) = strides
  (plh, phh), (plw, phw) = pads
  if wcin != cin or cin > _MAX_CIN or kh * kw * cin > _MAX_PATCH_DEPTH:
    return None
  if cout % 8 or min(sh, sw) < 1 or min(plh, phh, plw, phw) < 0:
    return None
  if max(plh, phh) >= kh or max(plw, phw) >= kw:
    return None
  oh = (h + plh + phh - kh) // sh + 1
  ow = (w + plw + phw - kw) // sw + 1
  if oh < 1 or ow < 1:
    return None
  hp, wp = oh * sh + kh - 1, ow * sw + kw - 1
  ohb = next(rb for rb in _ROW_BLOCKS if oh % rb == 0)
  patch = kh * kw * cin
  # fwd/dW stage the whole padded image + one row-block patch matrix;
  # dx stages the whole cotangent + per-phase planes, all float32 under
  # the interpreter.
  fwd_bytes = hp * wp * cin * 4 * 2 + ohb * ow * patch * 4
  dx_bytes = (oh * ow * cout + 2 * hp * wp * cin) * 4
  if max(fwd_bytes, dx_bytes) > _INTERPRET_BUDGET_BYTES:
    return None
  return dict(h=h, w=w, cin=cin, cout=cout, kh=kh, kw=kw, sh=sh, sw=sw,
              plh=plh, plw=plw, oh=oh, ow=ow, hp=hp, wp=wp, ohb=ohb,
              patch=patch)


def is_supported(xshape: Sequence[int],
                 wshape: Sequence[int],
                 strides: Tuple[int, int],
                 padding: Union[str, Sequence[Tuple[int, int]]],
                 interpret: Optional[bool] = None) -> bool:
  """Whether the s2d-matmul kernel handles an NHWC/HWIO conv problem on
  the target the call would lower to (``interpret=None`` resolves from
  the backend; ``False`` — a real Mosaic lowering — is never
  supported, see the module docstring)."""
  xshape = tuple(int(d) for d in xshape)
  if len(xshape) != 4:
    return False
  pads = resolve_padding(padding, tuple(wshape[:2]), tuple(strides),
                         xshape[1:3])
  return _plan(xshape, tuple(wshape), tuple(strides), pads,
               interpret) is not None


# ----------------------------------------------------------------- kernels


def _pad_zero(x, plh, plw, hp, wp):
  h, w, _ = x.shape
  cfg = ((plh, hp - h - plh, 0), (plw, wp - w - plw, 0), (0, 0, 0))
  if any(lo or hi for lo, hi, _ in cfg):
    return jax.lax.pad(x, jnp.zeros((), x.dtype), cfg)
  return x


def _patch_matrix(xs, kh, kw, sh, sw, rows, ow, wp, cin):
  """[rows·sh + kh - 1, wp, cin] staged input rows → [rows, ow, kh·kw·cin]
  patch tensor: the space-to-depth regroup, as slice + phase-reshape per
  tap (row-major tap order matches the kernel reshape)."""
  taps = []
  for dy in range(kh):
    r = xs[dy:dy + rows * sh]
    if sh > 1:
      r = r.reshape(rows, sh, wp, cin)[:, 0]
    for dx in range(kw):
      v = r[:, dx:dx + ow * sw]
      if sw > 1:
        v = v.reshape(rows, ow, sw, cin)[:, :, 0]
      taps.append(v)
  return jnp.concatenate(taps, axis=-1)


def _conv_fwd_kernel(x_ref, w_ref, out_ref, *, kh, kw, sh, sw, plh, plw,
                     ohb, ow, hp, wp, out_dtype):
  r = pl.program_id(1)
  x = x_ref[0]
  cin = x.shape[-1]
  xp = _pad_zero(x, plh, plw, hp, wp)
  rows_needed = ohb * sh + kh - 1
  xs = jax.lax.dynamic_slice(xp, (r * ohb * sh, 0, 0),
                             (rows_needed, wp, cin))
  xt = _patch_matrix(xs, kh, kw, sh, sw, ohb, ow, wp, cin)
  patch = xt.shape[-1]
  out = jax.lax.dot_general(
      xt.reshape(ohb * ow, patch), w_ref[...],
      (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
  out_ref[0] = out.reshape(ohb, ow, -1).astype(out_dtype)


def _conv_dw_kernel(x_ref, g_ref, dw_ref, *, kh, kw, sh, sw, plh, plw,
                    ohb, ow, hp, wp):
  b, r = pl.program_id(0), pl.program_id(1)

  @pl.when(jnp.logical_and(b == 0, r == 0))
  def _():
    dw_ref[...] = jnp.zeros_like(dw_ref)

  x = x_ref[0]
  cin = x.shape[-1]
  xp = _pad_zero(x, plh, plw, hp, wp)
  rows_needed = ohb * sh + kh - 1
  xs = jax.lax.dynamic_slice(xp, (r * ohb * sh, 0, 0),
                             (rows_needed, wp, cin))
  xt = _patch_matrix(xs, kh, kw, sh, sw, ohb, ow, wp, cin)
  patch = xt.shape[-1]
  g = g_ref[0].reshape(ohb * ow, -1)
  dw_ref[...] += jax.lax.dot_general(
      xt.reshape(ohb * ow, patch), g,
      (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _conv_dx_kernel(g_ref, w_ref, dx_ref, *, kh, kw, sh, sw, plh, plw,
                    h, w, oh, ow, cin, out_dtype):
  """Phase-decomposed transposed conv: for input phase (φh, φw) only
  taps with a ≡ φh (mod sh), b ≡ φw (mod sw) contribute — each phase
  plane is a sum of shifted cotangent·Wᵀ matmuls, and the planes
  interleave back into dx (the s2d duality, again with no transform
  kernel of its own)."""
  g = g_ref[0]
  mh = -(-(h + plh) // sh)
  mw = -(-(w + plw) // sw)
  zero = jnp.zeros((), jnp.float32)
  row_planes = []
  for ph in range(sh):
    col_planes = []
    for pw in range(sw):
      plane = jnp.zeros((mh, mw, cin), jnp.float32)
      for alpha in range(-(-(kh - ph) // sh)):
        a = ph + alpha * sh
        for beta in range(-(-(kw - pw) // sw)):
          b = pw + beta * sw
          gs = jax.lax.pad(
              g.astype(jnp.float32), zero,
              ((alpha, mh - alpha - oh, 0),
               (beta, mw - beta - ow, 0), (0, 0, 0)))
          tap = w_ref[pl.dslice((a * kw + b) * cin, cin), :]
          plane = plane + jax.lax.dot_general(
              gs, tap, (((2,), (1,)), ((), ())),
              preferred_element_type=jnp.float32)
      col_planes.append(plane)
    row = jnp.stack(col_planes, axis=2).reshape(mh, mw * sw, cin)
    row_planes.append(row)
  full = jnp.stack(row_planes, axis=1).reshape(mh * sh, mw * sw, cin)
  dx_ref[0] = full[plh:plh + h, plw:plw + w].astype(out_dtype)


# -------------------------------------------------------------- plumbing


def _wmat(w):
  kh, kw, cin, cout = w.shape
  return w.reshape(kh * kw * cin, cout)


def _fwd_call(x, w, plan):
  b = x.shape[0]
  out_dtype = jnp.result_type(x.dtype, w.dtype)
  p = plan
  kern = functools.partial(
      _conv_fwd_kernel, kh=p['kh'], kw=p['kw'], sh=p['sh'], sw=p['sw'],
      plh=p['plh'], plw=p['plw'], ohb=p['ohb'], ow=p['ow'], hp=p['hp'],
      wp=p['wp'], out_dtype=out_dtype)
  return pl.pallas_call(
      kern,
      grid=(b, p['oh'] // p['ohb']),
      in_specs=[
          pl.BlockSpec((1, p['h'], p['w'], p['cin']),
                       lambda i, j: (i, 0, 0, 0)),
          pl.BlockSpec((p['patch'], p['cout']), lambda i, j: (0, 0)),
      ],
      out_specs=pl.BlockSpec((1, p['ohb'], p['ow'], p['cout']),
                             lambda i, j: (i, j, 0, 0)),
      out_shape=jax.ShapeDtypeStruct((b, p['oh'], p['ow'], p['cout']),
                                     out_dtype),
      interpret=dispatch.use_interpret(),
  )(x, _wmat(w))


def _dw_call(x, g, plan, w_dtype):
  b = x.shape[0]
  p = plan
  kern = functools.partial(
      _conv_dw_kernel, kh=p['kh'], kw=p['kw'], sh=p['sh'], sw=p['sw'],
      plh=p['plh'], plw=p['plw'], ohb=p['ohb'], ow=p['ow'], hp=p['hp'],
      wp=p['wp'])
  dw = pl.pallas_call(
      kern,
      grid=(b, p['oh'] // p['ohb']),
      in_specs=[
          pl.BlockSpec((1, p['h'], p['w'], p['cin']),
                       lambda i, j: (i, 0, 0, 0)),
          pl.BlockSpec((1, p['ohb'], p['ow'], p['cout']),
                       lambda i, j: (i, j, 0, 0)),
      ],
      out_specs=pl.BlockSpec((p['patch'], p['cout']), lambda i, j: (0, 0)),
      out_shape=jax.ShapeDtypeStruct((p['patch'], p['cout']), jnp.float32),
      interpret=dispatch.use_interpret(),
  )(x, g)
  return dw.reshape(p['kh'], p['kw'], p['cin'], p['cout']).astype(w_dtype)


def _dx_call(g, w, plan, x_dtype):
  b = g.shape[0]
  p = plan
  kern = functools.partial(
      _conv_dx_kernel, kh=p['kh'], kw=p['kw'], sh=p['sh'], sw=p['sw'],
      plh=p['plh'], plw=p['plw'], h=p['h'], w=p['w'], oh=p['oh'],
      ow=p['ow'], cin=p['cin'], out_dtype=x_dtype)
  return pl.pallas_call(
      kern,
      grid=(b,),
      in_specs=[
          pl.BlockSpec((1, p['oh'], p['ow'], p['cout']),
                       lambda i: (i, 0, 0, 0)),
          pl.BlockSpec((p['patch'], p['cout']), lambda i: (0, 0)),
      ],
      out_specs=pl.BlockSpec((1, p['h'], p['w'], p['cin']),
                             lambda i: (i, 0, 0, 0)),
      out_shape=jax.ShapeDtypeStruct((b, p['h'], p['w'], p['cin']),
                                     x_dtype),
      interpret=dispatch.use_interpret(),
  )(g, _wmat(w))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def pallas_conv2d(x, w, strides: Tuple[int, int], pads: Pads):
  """NHWC×HWIO conv via the s2d Pallas matmul; ``pads`` explicit. Raises
  on unsupported geometry — :func:`conv2d` is the gated entry point."""
  out, _ = _conv_vjp_fwd(x, w, strides, pads)
  return out


def _conv_vjp_fwd(x, w, strides, pads):
  plan = _plan(x.shape, w.shape, strides, pads)
  if plan is None:
    raise ValueError(
        f'pallas conv2d unsupported for x {x.shape} w {w.shape} strides '
        f'{strides} pads {pads} (see is_supported).')
  return _fwd_call(x, w, plan), (x, w)


def _conv_vjp_bwd(strides, pads, res, g):
  x, w = res
  plan = _plan(x.shape, w.shape, strides, pads)
  dw = _dw_call(x, g, plan, w.dtype)
  dx = _dx_call(g, w, plan, x.dtype)
  return dx, dw


pallas_conv2d.defvjp(_conv_vjp_fwd, _conv_vjp_bwd)


def reference_conv2d(x, w, strides: Tuple[int, int],
                     padding: Union[str, Sequence[Tuple[int, int]]]):
  """The stock XLA form (what ``nn.Conv`` emits for NHWC): the fallback
  arm of the dispatch and the banding oracle for the tests."""
  if not isinstance(padding, str):
    padding = tuple((lo, hi) for lo, hi in padding)
  return jax.lax.conv_general_dilated(
      x, w, window_strides=strides, padding=padding,
      dimension_numbers=('NHWC', 'HWIO', 'NHWC'))


def conv2d(x, w, strides: Tuple[int, int],
           padding: Union[str, Sequence[Tuple[int, int]]],
           enabled: Optional[bool] = None):
  """Size-gated conv dispatch: Pallas s2d matmul when the kernel gate is
  live and the geometry fits, stock ``lax.conv_general_dilated``
  otherwise — announced through ``_pallas_dispatch.refuse`` when the
  gate was live, since the call site asked for the kernel."""
  strides = tuple(strides)
  if enabled is None:
    enabled = dispatch.kernels_enabled()
  if enabled and x.ndim == 4:
    pads = resolve_padding(padding, tuple(w.shape[:2]), strides,
                           x.shape[1:3])
    if _plan(x.shape, w.shape, strides, pads) is not None:
      return pallas_conv2d(x, w, strides, pads)
  if enabled:
    dispatch.refuse(
        'conv_s2d',
        f'x {x.dtype.name}{list(x.shape)} w {list(w.shape)} strides '
        f'{strides} padding {padding!r} is outside '
        'ops.conv_s2d.is_supported')
  return reference_conv2d(x, w, strides, padding)


class SpaceToDepthConv(nn.Module):
  """``nn.Conv`` drop-in routing through :func:`conv2d`.

  The parameter tree is byte-identical to ``nn.Conv`` (``kernel`` of
  shape (kh, kw, cin, features), optional ``bias``), so flipping
  ``kernel_policy`` on an existing checkpoint restores cleanly in both
  directions. ``quantize_cls``, when set, is a module factory whose
  instance maps ``(x, kernel) → (x, kernel)`` before the conv — the fp8
  qdq hook (``quantize.fp8_training.conv_quantize_cls``), the same
  injection idiom as flax's ``dot_general_cls``, so the s2d kernel and
  low-precision training stack.
  """

  features: int
  kernel_size: Tuple[int, int]
  strides: Tuple[int, int] = (1, 1)
  padding: Union[str, Sequence[Tuple[int, int]]] = 'SAME'
  use_bias: bool = True
  dtype: Optional[Any] = None
  param_dtype: Any = jnp.float32
  kernel_init: Callable = nn.initializers.lecun_normal()
  bias_init: Callable = nn.initializers.zeros_init()
  quantize_cls: Optional[Callable] = None

  @nn.compact
  def __call__(self, x):
    kh, kw = self.kernel_size
    kernel = self.param('kernel', self.kernel_init,
                        (kh, kw, x.shape[-1], self.features),
                        self.param_dtype)
    bias = (self.param('bias', self.bias_init, (self.features,),
                       self.param_dtype) if self.use_bias else None)
    from flax.linen import dtypes as flax_dtypes

    x, kernel, bias = flax_dtypes.promote_dtype(x, kernel, bias,
                                                dtype=self.dtype)
    if self.quantize_cls is not None:
      x, kernel = self.quantize_cls()(x, kernel)
    y = conv2d(x, kernel, tuple(self.strides), self.padding)
    if bias is not None:
      y = y + jnp.reshape(bias, (1, 1, 1, -1))
    return y
