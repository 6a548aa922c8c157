"""xgboost_ray_tpu: TPU-native distributed gradient-boosted-tree training.

A brand-new framework with the capabilities of ray-project/xgboost_ray,
re-designed for TPU: workers are slots of a ``jax.sharding.Mesh``, the
``gpu_hist`` CUDA tree method is replaced by a JAX/XLA ``tpu_hist``
histogram learner over HBM-resident quantile-binned feature blocks, and the
Rabit TCP allreduce becomes ``jax.lax.psum`` over ICI/DCN.

Public API mirrors ``xgboost_ray/__init__.py:1-41``.
"""

from xgboost_ray_tpu.main import (
    RayParams,
    RayXGBoostActor,
    predict,
    train,
)
from xgboost_ray_tpu.matrix import (
    Data,
    RayDMatrix,
    RayDeviceQuantileDMatrix,
    RayQuantileDMatrix,
    RayShardingMode,
    RayStreamingDMatrix,
    combine_data,
)
from xgboost_ray_tpu.data_sources import RayFileType
from xgboost_ray_tpu.models.booster import Booster, RayXGBoostBooster
from xgboost_ray_tpu.callback import DistributedCallback, TrainingCallback
from xgboost_ray_tpu import faults, obs
from xgboost_ray_tpu.obs import recovery_time_s, validate_trace_records
from xgboost_ray_tpu.launcher import (
    AsyncCheckpointWriter,
    LaunchContext,
    LaunchResult,
    launch_distributed,
    load_round_checkpoint,
    save_round_checkpoint,
)

__version__ = "0.1.0"

__all__ = [
    "__version__",
    "RayParams",
    "RayDMatrix",
    "RayDeviceQuantileDMatrix",
    "RayQuantileDMatrix",
    "RayStreamingDMatrix",
    "RayFileType",
    "RayShardingMode",
    "Data",
    "combine_data",
    "train",
    "predict",
    "Booster",
    "RayXGBoostBooster",
    "RayXGBoostActor",
    "DistributedCallback",
    "TrainingCallback",
    "faults",
    "obs",
    "validate_trace_records",
    "recovery_time_s",
    "LaunchContext",
    "LaunchResult",
    "launch_distributed",
    "load_round_checkpoint",
    "save_round_checkpoint",
    "AsyncCheckpointWriter",
]

try:
    from xgboost_ray_tpu.sklearn import (
        RayXGBClassifier,
        RayXGBRanker,
        RayXGBRegressor,
        RayXGBRFClassifier,
        RayXGBRFRegressor,
    )

    __all__ += [
        "RayXGBClassifier",
        "RayXGBRegressor",
        "RayXGBRFClassifier",
        "RayXGBRFRegressor",
        "RayXGBRanker",
    ]
except ImportError:  # sklearn facade requires scikit-learn
    pass
