from hetu_tpu.exec.executor import Executor, Trainer, TrainState
from hetu_tpu.exec.checkpoint import (
    AsyncCheckpointer,
    CheckpointCorrupt,
    CheckpointError,
    load_checkpoint,
    load_state_dict,
    save_checkpoint,
    state_dict,
)
from hetu_tpu.exec.logger import Logger, WandbLogger
from hetu_tpu.exec.profiler import audit_donation, audit_serving_donation
from hetu_tpu.exec.resilience import (
    BackendUnresponsive,
    Preempted,
    ResilientTrainer,
    TrainingDiverged,
    latest_good_checkpoint,
    list_checkpoints,
)
from hetu_tpu.exec.gang import (
    ElasticGang,
    GangCheckpointer,
    GangError,
    GangManifestError,
    GangMembership,
    gang_data_partition,
    load_gang_checkpoint,
    worker_rng_key,
)
from hetu_tpu.exec.partial import (
    GradientBoard,
    PartialReduceConfig,
    PartialReducer,
)
from hetu_tpu.exec.controller import ControllerConfig, RuntimeController
from hetu_tpu.exec import controller, faults, gang, metrics, partial
