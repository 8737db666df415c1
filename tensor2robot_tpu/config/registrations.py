"""Registers framework symbols as configurables.

The reference gets this from ``@gin.configurable`` decorators scattered
through every module; here registration is centralized so core modules stay
config-agnostic. Idempotent: safe to call from every binary.
"""

from __future__ import annotations

from tensor2robot_tpu.config import gin_lite

_REGISTERED = False


def register() -> None:
  global _REGISTERED
  if _REGISTERED:
    return
  _REGISTERED = True

  from tensor2robot_tpu.data import input_generators as ig
  from tensor2robot_tpu.models import optimizers, warm_start
  from tensor2robot_tpu.train import callbacks as callbacks_lib
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.train import trainer as trainer_lib
  from tensor2robot_tpu.utils import mocks

  reg = gin_lite.external_configurable
  # Trainer entry points (utils/train_eval.py gin surface).
  reg(trainer_lib.train_eval_model, 'train_eval_model')
  reg(trainer_lib.predict_from_model, 'predict_from_model')
  # Input generators (input_generators/*.py).
  reg(ig.DefaultRecordInputGenerator, 'DefaultRecordInputGenerator')
  reg(ig.NativeRecordInputGenerator, 'NativeRecordInputGenerator')
  reg(ig.TaskGroupedRecordInputGenerator, 'TaskGroupedRecordInputGenerator')
  reg(ig.FractionalRecordInputGenerator, 'FractionalRecordInputGenerator')
  reg(ig.MultiEvalRecordInputGenerator, 'MultiEvalRecordInputGenerator')
  reg(ig.GeneratorInputGenerator, 'GeneratorInputGenerator')
  reg(ig.DefaultRandomInputGenerator, 'DefaultRandomInputGenerator')
  reg(ig.DefaultConstantInputGenerator, 'DefaultConstantInputGenerator')
  # Optimizer factories (models/optimizers.py gin surface).
  reg(optimizers.create_adam_optimizer, 'create_adam_optimizer')
  reg(optimizers.create_gradient_descent_optimizer,
      'create_gradient_descent_optimizer')
  reg(optimizers.create_momentum_optimizer, 'create_momentum_optimizer')
  reg(optimizers.create_rms_prop_optimizer, 'create_rms_prop_optimizer')
  reg(optimizers.create_constant_learning_rate_fn,
      'create_constant_learning_rate')
  reg(optimizers.create_exp_decaying_learning_rate_fn,
      'create_exp_decaying_learning_rate')
  # Warm start + callbacks.
  reg(warm_start.default_init_from_checkpoint_fn,
      'default_init_from_checkpoint_fn')
  reg(warm_start.create_resnet_init_from_checkpoint_fn,
      'create_resnet_init_from_checkpoint_fn')
  reg(callbacks_lib.TensorBoardCallback, 'TensorBoardCallback')
  reg(callbacks_lib.MetricsLoggerCallback, 'MetricsLoggerCallback')
  reg(callbacks_lib.VariableLoggerCallback, 'VariableLoggerCallback')
  reg(callbacks_lib.ProfilerCallback, 'ProfilerCallback')
  reg(callbacks_lib.ResilienceLoggerCallback, 'ResilienceLoggerCallback')
  # Fault tolerance (train/resilience.py): the preemption handler for
  # jobs driven by configs rather than bin/run_t2r_trainer.py; the
  # nonfinite/error-budget knobs ride on train_eval_model and the input
  # generators' own parameters.
  from tensor2robot_tpu.train import resilience as resilience_lib

  reg(resilience_lib.install_graceful_shutdown, 'install_graceful_shutdown')
  # Mesh.
  reg(mesh_lib.create_mesh, 'create_mesh')
  reg(mesh_lib.MeshSpec, 'MeshSpec')
  # Mocks (used by smoke-test configs).
  reg(mocks.MockT2RModel, 'MockT2RModel')
  reg(mocks.MockInputGenerator, 'MockInputGenerator')

  # Export / serving / policies (phase-5 surface).
  from tensor2robot_tpu import export as export_lib
  from tensor2robot_tpu import policies as policies_lib
  from tensor2robot_tpu import predictors as predictors_lib
  from tensor2robot_tpu.utils import continuous_collect_eval, writer

  reg(export_lib.create_default_exporters, 'create_default_exporters')
  reg(export_lib.AsyncExportCallback, 'AsyncExportCallback')
  reg(export_lib.TD3ExportCallback, 'TD3ExportCallback')
  reg(predictors_lib.CheckpointPredictor, 'CheckpointPredictor')
  reg(predictors_lib.ExportedModelPredictor, 'ExportedModelPredictor')
  reg(policies_lib.CEMPolicy, 'CEMPolicy')
  reg(policies_lib.LSTMCEMPolicy, 'LSTMCEMPolicy')
  reg(policies_lib.RegressionPolicy, 'RegressionPolicy')
  reg(policies_lib.SequentialRegressionPolicy, 'SequentialRegressionPolicy')
  reg(policies_lib.OUExploreRegressionPolicy, 'OUExploreRegressionPolicy')
  reg(policies_lib.ScheduledExplorationRegressionPolicy,
      'ScheduledExplorationRegressionPolicy')
  reg(policies_lib.PerEpisodeSwitchPolicy, 'PerEpisodeSwitchPolicy')
  reg(continuous_collect_eval.collect_eval_loop, 'collect_eval_loop')
  reg(writer.TFRecordReplayWriter, 'TFRecordReplayWriter')

  # Research workloads (research/*/configs/*.gin surface).
  from tensor2robot_tpu.meta_learning import maml_model as maml_model_lib
  # NOTE: the meta_learning package __init__ re-exports the *function*
  # run_meta_env under the same name as its module, so `from ... import
  # run_meta_env` yields the function itself, not the module.
  from tensor2robot_tpu.meta_learning import run_meta_env as run_meta_env_fn
  from tensor2robot_tpu.research import dql_grasping_lib
  from tensor2robot_tpu.research import grasp2vec as grasp2vec_lib
  from tensor2robot_tpu.research import pose_env as pose_env_lib
  from tensor2robot_tpu.research import qtopt as qtopt_lib
  from tensor2robot_tpu.research.token_policy import (
      afmoe_model as token_policy_lib)
  from tensor2robot_tpu.research.token_policy import (
      glm_model as glm_policy_lib)
  from tensor2robot_tpu.research.token_policy import (
      zaya_model as zaya_policy_lib)
  from tensor2robot_tpu.research import vrgripper as vrgripper_lib

  reg(maml_model_lib.MAMLModel, 'MAMLModel')
  reg(run_meta_env_fn, 'run_meta_env')
  reg(dql_grasping_lib.run_env, 'run_env')
  reg(pose_env_lib.PoseToyEnv, 'PoseToyEnv')
  reg(pose_env_lib.PoseEnvRandomPolicy, 'PoseEnvRandomPolicy')
  reg(pose_env_lib.PoseEnvRegressionModel, 'PoseEnvRegressionModel')
  reg(pose_env_lib.PoseEnvContinuousMCModel, 'PoseEnvContinuousMCModel')
  reg(pose_env_lib.PoseEnvRegressionModelMAML, 'PoseEnvRegressionModelMAML')
  reg(pose_env_lib.episode_to_transitions_pose_toy,
      'episode_to_transitions_pose_toy')
  reg(qtopt_lib.GraspingModelWrapper, 'GraspingModelWrapper')
  reg(qtopt_lib.Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom,
      'Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom')
  reg(grasp2vec_lib.Grasp2VecModel, 'Grasp2VecModel')
  reg(token_policy_lib.AfmoeTokenPolicyModel, 'AfmoeTokenPolicyModel')
  reg(zaya_policy_lib.ZayaTokenPolicyModel, 'ZayaTokenPolicyModel')
  reg(glm_policy_lib.GlmTokenPolicyModel, 'GlmTokenPolicyModel')
  reg(vrgripper_lib.VRGripperRegressionModel, 'VRGripperRegressionModel')
  reg(vrgripper_lib.VRGripperDomainAdaptiveModel,
      'VRGripperDomainAdaptiveModel')
  reg(vrgripper_lib.VRGripperEnvSimpleTrialModel,
      'VRGripperEnvSimpleTrialModel')
  reg(vrgripper_lib.VRGripperEnvVisionTrialModel,
      'VRGripperEnvVisionTrialModel')
  reg(vrgripper_lib.VRGripperEnvRegressionModelMAML,
      'VRGripperEnvRegressionModelMAML')
  reg(vrgripper_lib.VRGripperEnvTecModel, 'VRGripperEnvTecModel')
  reg(vrgripper_lib.VRGripperEnvSequentialModel,
      'VRGripperEnvSequentialModel')
  reg(vrgripper_lib.VRGripperEnvLongHorizonModel,
      'VRGripperEnvLongHorizonModel')
