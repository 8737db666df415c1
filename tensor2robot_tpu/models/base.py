"""Model protocol: the TPU-native equivalent of the reference's model layer.

Re-design of ``ModelInterface`` / ``AbstractT2RModel``
(``/root/reference/models/model_interface.py:53-151``,
``/root/reference/models/abstract_model.py:153-919``). The reference couples
the model to ``tf.estimator``: ``model_fn(features, labels, mode)`` builds a
graph and returns an ``EstimatorSpec``; TPU support is bolted on by wrapping
the model in ``TPUT2RModelWrapper``.

Here the model is a *functional protocol* and one generic trainer owns the
jitted step, so there is a single code path for CPU/GPU/TPU:

* ``get_feature_specification(mode)`` / ``get_label_specification(mode)``
  declare the device-side data contract (post-preprocessing).
* ``preprocessor`` pairs the model with its preprocessor, wrapped in the
  bfloat16 :class:`DtypePolicyPreprocessor` when ``device_type == 'tpu'``
  (capability of ``models/tpu_model_wrapper.py:58-314`` with no wrapper class
  for the model itself — dtype policy lives at the data boundary).
* ``init_variables(rng, features)`` / ``inference_network_fn(variables, ...)``
  replace graph building: pure functions over explicit Flax variables, safe
  to ``jax.jit`` / ``pjit`` / ``vmap`` (which is what makes MAML trivial).
* ``model_train_fn`` / ``model_eval_fn`` / ``create_export_outputs_fn``
  keep the reference's names and roles (loss, eval metrics, serving outputs).

The trainer composes these exactly like ``abstract_model.py:683-821``
composes ``model_fn``, but as jitted functions instead of graph modes.
"""

from __future__ import annotations

import abc
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple, Type

import jax

from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.preprocessors import (
    AbstractPreprocessor,
    DtypePolicyPreprocessor,
    NoOpPreprocessor,
)
from tensor2robot_tpu.specs import SpecStruct, algebra

# A model's variables: a (frozen) dict of Flax collections, always containing
# 'params' (trainable) and possibly others ('batch_stats', ...).
Variables = Mapping[str, Any]
Predictions = SpecStruct
Scalars = Dict[str, Any]

DEVICE_TYPE_CPU = 'cpu'
DEVICE_TYPE_GPU = 'gpu'
DEVICE_TYPE_TPU = 'tpu'


def split_variables(variables: Variables) -> Tuple[Any, Dict[str, Any]]:
  """Splits Flax variables into (trainable params, non-trainable state)."""
  variables = dict(variables)
  params = variables.pop('params', {})
  return params, variables


def merge_variables(params: Any, model_state: Mapping[str, Any]) -> Variables:
  merged = dict(model_state or {})
  merged['params'] = params
  return merged


class ModelInterface(abc.ABC):
  """Minimal surface the infrastructure (trainer/predictors) relies on.

  Mirrors ``models/model_interface.py:53-151``.
  """

  @property
  @abc.abstractmethod
  def preprocessor(self) -> AbstractPreprocessor:
    ...

  @abc.abstractmethod
  def get_feature_specification(self, mode: str) -> SpecStruct:
    """Device-side (post-preprocessing) feature specs."""

  @abc.abstractmethod
  def get_label_specification(self, mode: str) -> Optional[SpecStruct]:
    """Device-side (post-preprocessing) label specs."""

  def get_feature_specification_for_packing(self, mode: str) -> SpecStruct:
    """Specs used by policies to pack numpy inputs (pre-preprocessing)."""
    return self.preprocessor.get_in_feature_specification(mode)

  def get_label_specification_for_packing(
      self, mode: str) -> Optional[SpecStruct]:
    return self.preprocessor.get_in_label_specification(mode)


class AbstractT2RModel(ModelInterface):
  """Base model: spec declaration + pure network/loss/metric functions.

  Constructor flags mirror ``abstract_model.py:168-211``:

  * ``preprocessor_cls``: preprocessor type paired with this model; it is
    constructed with the model's spec getters (the spec handshake of
    ``input_generators/abstract_input_generator.py:80-103``).
  * ``create_optimizer_fn``: zero-arg factory returning an optax
    ``GradientTransformation`` (see :mod:`tensor2robot_tpu.models.optimizers`).
  * ``device_type``: 'cpu' | 'gpu' | 'tpu'. On 'tpu', the preprocessor is
    wrapped with the bfloat16 dtype policy.
  * ``use_avg_model_params``: keep an EMA of params in the train state and
    export/eval the averaged weights — capability of the reference's
    ``MovingAverageOptimizer`` + swapping saver (``models/optimizers.py:
    140-167`` in the reference) without any saver trickery.
  * ``init_from_checkpoint_fn``: ``fn(params, model_state) -> (params,
    model_state)`` warm-start hook, the equivalent of
    ``default_init_from_checkpoint_fn`` (``abstract_model.py:88-118``).
  * ``remat_policy``: activation-rematerialization policy applied around
    this model's conv towers (``'none' | 'conv_towers' | 'full'``, see
    :mod:`tensor2robot_tpu.layers.remat`). Trades activation HBM against
    recompute so larger (micro)batches fit past the memory cliff; the
    parameter tree and numerics are unchanged — only backward-pass
    scheduling differs. Models that build remat-capable towers
    (``layers.resnet.ResNet``, ``layers.vision_layers.
    ImagesToFeaturesModel``, the qtopt/grasp2vec networks) thread this
    through; models without towers accept and ignore it.
  * ``kernel_policy``: hand-written Pallas kernel routing for the conv
    towers (``'none' | 'pool' | 'pool_conv'``, see
    :mod:`tensor2robot_tpu.ops._pallas_dispatch`) — same shape as
    ``remat_policy``. ``'pool'`` routes max-pools through the
    argmax-emitting fused kernel (``ops/pool.py``); ``'pool_conv'``
    additionally routes the shallow first conv through the
    space-to-depth Pallas matmul (``ops/conv_s2d.py``). Call sites are
    size-gated and fall back to the stock XLA ops off-TPU or for
    unsupported shapes; parameter trees are identical either way, so
    checkpoints interchange. Off by default.
  * ``matmul_precision``: contraction precision for Dense/Conv
    (``'bf16' | 'fp8'``, see :mod:`tensor2robot_tpu.quantize.
    fp8_training`). ``'fp8'`` runs the matmul contractions through
    delayed-amax-scaled ``float8_e4m3fn`` quantize-dequantize (master
    weights stay f32 in the optimizer state; gradients leave the ops
    unscaled in full precision). ``TrainerConfig.matmul_precision``
    overrides this at trainer construction.
  """

  def __init__(self,
               preprocessor_cls: Optional[Type[AbstractPreprocessor]] = None,
               create_optimizer_fn: Optional[Callable[[], Any]] = None,
               device_type: str = DEVICE_TYPE_TPU,
               use_avg_model_params: bool = False,
               avg_model_params_decay: float = 0.9999,
               init_from_checkpoint_fn: Optional[Callable] = None,
               remat_policy: str = 'none',
               kernel_policy: str = 'none',
               matmul_precision: str = 'bf16'):
    from tensor2robot_tpu.layers import remat as remat_lib
    from tensor2robot_tpu.ops import _pallas_dispatch as dispatch_lib
    from tensor2robot_tpu.quantize import fp8_training as fp8_lib

    self._preprocessor_cls = preprocessor_cls
    self._create_optimizer_fn = create_optimizer_fn
    if device_type not in (DEVICE_TYPE_CPU, DEVICE_TYPE_GPU, DEVICE_TYPE_TPU):
      raise ValueError(f'Unknown device_type: {device_type}')
    self._device_type = device_type
    self.use_avg_model_params = use_avg_model_params
    self.avg_model_params_decay = avg_model_params_decay
    self.init_from_checkpoint_fn = init_from_checkpoint_fn
    self._remat_policy = remat_lib.validate_remat_policy(remat_policy)
    self._kernel_policy = dispatch_lib.validate_kernel_policy(kernel_policy)
    self._matmul_precision = fp8_lib.validate_matmul_precision(
        matmul_precision)

  # ------------------------------------------------------------------ device

  @property
  def device_type(self) -> str:
    return self._device_type

  @property
  def is_device_tpu(self) -> bool:
    return self._device_type == DEVICE_TYPE_TPU

  @property
  def remat_policy(self) -> str:
    """Activation-remat policy name ('none' | 'conv_towers' | 'full')."""
    return self._remat_policy

  @property
  def kernel_policy(self) -> str:
    """Pallas kernel routing ('none' | 'pool' | 'pool_conv')."""
    return self._kernel_policy

  @property
  def matmul_precision(self) -> str:
    """Dense/Conv contraction precision ('bf16' | 'fp8')."""
    return self._matmul_precision

  def set_matmul_precision(self, precision: str) -> None:
    """Trainer-level override (``TrainerConfig.matmul_precision``);
    validates + gates on :func:`quantize.quantization.fp8_supported`.
    Must run before :meth:`create_module`/``init_variables`` — modules
    bake the precision in at construction."""
    from tensor2robot_tpu.quantize import fp8_training as fp8_lib

    self._matmul_precision = fp8_lib.require_fp8_support(precision)

  @property
  def compute_dtype(self):
    """Activation dtype for the network (params stay float32).

    On TPU this is bfloat16 — the MXU's native input dtype — matching the
    dtype the :class:`DtypePolicyPreprocessor` delivers at the device
    boundary (capability of ``models/tpu_model_wrapper.py:105-118``: specs
    re-typed to bfloat16 so compute runs in bf16 on TPU hardware).
    """
    import jax.numpy as jnp

    return jnp.bfloat16 if self.is_device_tpu else jnp.float32

  # ------------------------------------------------------------ preprocessor

  @property
  def default_preprocessor_cls(self) -> Type[AbstractPreprocessor]:
    return NoOpPreprocessor

  @property
  def preprocessor(self) -> AbstractPreprocessor:
    preprocessor_cls = self._preprocessor_cls or self.default_preprocessor_cls
    preprocessor = preprocessor_cls(
        model_feature_specification_fn=self.get_feature_specification,
        model_label_specification_fn=self.get_label_specification)
    if self.is_device_tpu:
      preprocessor = DtypePolicyPreprocessor(preprocessor)
    return preprocessor

  def param_sharding_rules(self, mesh) -> Sequence:
    """Tensor-parallel parameter layouts for this model (optional).

    Returns ``(path_regex, per-dim axis spec)`` pairs consumed by
    ``parallel.mesh.state_shardings_for``: the first matching rule shards
    that parameter over the named mesh axes (e.g.
    ``(r'fcgrasp/kernel$', (None, 'model'))`` column-shards a Dense
    kernel, Megatron-style). Unmatched parameters fall back to the fsdp
    rule. Axes missing from ``mesh`` are ignored, so rules are
    layout-portable.
    """
    del mesh
    return ()

  # ------------------------------------------------------------- core fns

  @abc.abstractmethod
  def init_variables(self, rng: jax.Array, features: SpecStruct,
                     mode: str = ModeKeys.TRAIN) -> Variables:
    """Initializes model variables for spec-shaped ``features``."""

  @abc.abstractmethod
  def inference_network_fn(
      self,
      variables: Variables,
      features: SpecStruct,
      labels: Optional[SpecStruct],
      mode: str,
      rng: Optional[jax.Array] = None,
  ) -> Tuple[Predictions, Variables]:
    """Pure forward pass; returns (predictions, updated variables).

    Updated variables matter for stateful collections (batch norm); for
    stateless models return ``variables`` unchanged.
    """

  def model_train_fn(
      self,
      features: SpecStruct,
      labels: Optional[SpecStruct],
      inference_outputs: Predictions,
      mode: str,
  ) -> Tuple[jax.Array, Scalars]:
    """Returns (scalar loss, scalar summaries). Must be jit-traceable."""
    raise NotImplementedError(
        f'{type(self).__name__} does not implement model_train_fn.')

  def model_eval_fn(
      self,
      features: SpecStruct,
      labels: Optional[SpecStruct],
      inference_outputs: Predictions,
  ) -> Scalars:
    """Per-batch eval metrics; the trainer averages them over eval batches."""
    loss, scalars = self.model_train_fn(features, labels, inference_outputs,
                                        ModeKeys.EVAL)
    metrics = dict(scalars)
    metrics['loss'] = loss
    return metrics

  def create_export_outputs_fn(
      self,
      features: SpecStruct,
      inference_outputs: Predictions,
  ) -> Predictions:
    """Outputs exposed by exported serving models; default: all predictions."""
    del features
    return inference_outputs

  # ---------------------------------------------------------------- counts

  @property
  def counter_scalars(self) -> Sequence[str]:
    """Names of ``model_train_fn`` scalars that count what one step did
    (tokens routed, rows computed). The trainer adds each to the
    ``observability.metrics`` counter of the same name at the dispatch
    boundary, one dispatch behind, so reading them adds no wait."""
    return ()

  # ------------------------------------------------------------- optimizer

  def create_optimizer(self):
    """Optax optimizer; EMA of params is handled by the trainer state."""
    if self._create_optimizer_fn is not None:
      return self._create_optimizer_fn()
    from tensor2robot_tpu.models import optimizers

    return optimizers.default_create_optimizer_fn()

  # ----------------------------------------------------------- conveniences

  def validated_features(self, features, mode: str,
                         labels=None) -> Tuple[SpecStruct, Any]:
    """validate_and_pack against the device-side data contract.

    Mirrors ``abstract_model.py:683-691``, except validation uses the
    preprocessor *out* specs: on TPU those are the model specs with the
    bfloat16 dtype policy applied and optionals stripped — exactly what
    arrives on device (the reference gets this via ``TPUT2RModelWrapper``
    re-typing the model specs, ``tpu_model_wrapper.py:105-118``).
    """
    preprocessor = self.preprocessor
    features = algebra.validate_and_pack(
        preprocessor.get_out_feature_specification(mode), features,
        ignore_batch=True)
    label_spec = preprocessor.get_out_label_specification(mode)
    if labels is not None and label_spec is not None:
      labels = algebra.validate_and_pack(label_spec, labels, ignore_batch=True)
    return features, labels

  def pack_features(self, state, context, timestep) -> SpecStruct:
    """Packs a policy's (state, context, timestep) into model features.

    Overridden by models that drive policies (critic/regression models);
    mirrors the packing contract used by ``policies/policies.py``.
    """
    raise NotImplementedError(
        f'{type(self).__name__} does not implement pack_features.')


class FlaxModel(AbstractT2RModel):
  """Convenience base for single-``nn.Module`` models.

  Subclasses implement :meth:`create_module` and the loss; ``init_variables``
  and ``inference_network_fn`` are derived. The module's ``__call__`` must
  accept ``(features, mode)`` keyword ``train`` and return a dict-like of
  predictions.
  """

  _RNG_COLLECTIONS = ('dropout', 'sample')

  def create_module(self):
    raise NotImplementedError(
        f'{type(self).__name__} must implement create_module().')

  @property
  def module(self):
    # Linen modules are cheap immutable pytrees; construct on demand.
    return self.create_module()

  def init_variables(self, rng, features, mode=ModeKeys.TRAIN):
    features, _ = self.validated_features(features, mode)
    rngs = self._make_rngs(rng, include_params=True)
    return self.module.init(rngs, features, train=False)

  def inference_network_fn(self, variables, features, labels, mode,
                           rng=None):
    del labels
    features, _ = self.validated_features(features, mode)
    train = mode == ModeKeys.TRAIN
    mutable = [k for k in variables if k != 'params'] if train else False
    kwargs = {}
    if rng is not None:
      kwargs['rngs'] = self._make_rngs(rng, include_params=False)
    if mutable:
      outputs, mutated = self.module.apply(
          variables, features, train=train, mutable=mutable, **kwargs)
      new_variables = merge_variables(variables['params'], mutated)
    else:
      outputs = self.module.apply(variables, features, train=train, **kwargs)
      new_variables = variables
    if not isinstance(outputs, SpecStruct):
      outputs = algebra.flatten_spec_structure(outputs)
    return outputs, new_variables

  def _make_rngs(self, rng, include_params: bool):
    names = list(self._RNG_COLLECTIONS)
    if include_params:
      names = ['params'] + names
    keys = jax.random.split(rng, len(names))
    return dict(zip(names, keys))
