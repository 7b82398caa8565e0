"""In-train-loop API: report(), get_context(), get_checkpoint().

reference: python/ray/train/v2/api/train_fn_utils.py (report,
get_checkpoint, get_dataset_shard) and train/v2/api/context.py.
The context is process-global inside a train worker; report() buffers
metrics for the controller and persists checkpoints rank-coordinated
(rank 0 registers; others just sync).
"""

from __future__ import annotations

import threading

import logging

from ray_tpu.devtools import locktrace
from typing import Any, Dict, Iterable, Optional

from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.util.metrics import Gauge

logger = logging.getLogger(__name__)

# Train-loop instrumentation (reference: Podracer-style TPU training
# leans on step-time + duty-cycle visibility; PAPERS.md). Step time is
# the interval between successive report() calls; MFU is estimated when
# the loop reports its per-step flops (``flops_per_step``) and a peak
# is known (``peak_flops_per_s`` in the report, or the
# RTPU_PEAK_FLOPS_PER_S env var on the worker).
TRAIN_STEP_SECONDS = Gauge(
    "ray_tpu_train_step_seconds",
    "Wall time between successive train.report() calls",
    tag_keys=("run", "rank"))
TRAIN_MFU = Gauge(
    "ray_tpu_train_mfu_ratio",
    "Estimated model flops utilization (0-1)",
    tag_keys=("run", "rank"))
TRAIN_REPORTED_STEPS = Gauge(
    "ray_tpu_train_reported_steps",
    "report() calls seen this run", tag_keys=("run", "rank"))


class TrainContext:
    def __init__(self, world_size: int, world_rank: int,
                 storage_path: str, resume_checkpoint: Optional[Checkpoint],
                 datasets: Optional[Dict[str, Any]] = None,
                 group_name: str = "train",
                 grad_compression: Optional[str] = None,
                 zero1: bool = False, pipeline_stages: int = 1,
                 microbatches: int = 1, schedule: str = "1f1b",
                 pipeline_stage: int = 0, pipeline_replica: int = 0,
                 stage_group_name: Optional[str] = None,
                 use_tpu: bool = False):
        self.world_size = world_size
        self.world_rank = world_rank
        self.storage_path = storage_path
        self.resume_checkpoint = resume_checkpoint
        self.datasets = datasets or {}
        self.group_name = group_name
        # gradient-sync flags from ScalingConfig, read by
        # train.collective.allreduce_gradients / make_optimizer
        self.grad_compression = grad_compression
        self.zero1 = zero1
        # pipeline topology (ScalingConfig.pipeline_stages > 1): this
        # worker's stage/replica, plus the cross-replica per-stage
        # collective group that gradient sync scopes itself to
        self.pipeline_stages = pipeline_stages
        self.microbatches = microbatches
        self.schedule = schedule
        self.pipeline_stage = pipeline_stage
        self.pipeline_replica = pipeline_replica
        self.stage_group_name = stage_group_name
        # ScalingConfig.use_tpu: this worker owns chips, so its first
        # report names the device it computes on
        self.use_tpu = use_tpu
        self.reported: list = []
        self.pending_checkpoint_dirs: list = []
        self._lock = locktrace.traced_lock("train.context")

    # reference API surface
    def get_world_size(self) -> int:
        return self.world_size

    def get_world_rank(self) -> int:
        return self.world_rank

    def get_local_rank(self) -> int:
        return self.world_rank  # one worker per host in this runtime

    def get_pipeline_stage(self) -> int:
        return self.pipeline_stage

    def sync_group_name(self) -> str:
        """The group gradient sync should run in: the per-stage
        cross-replica group under pipeline parallelism (replicas of the
        SAME stage hold the same parameters), the run group otherwise."""
        return self.stage_group_name or self.group_name

    def get_experiment_name(self) -> str:
        return self.storage_path.rsplit("/", 1)[-1]


_context: Optional[TrainContext] = None


def set_context(ctx: Optional[TrainContext]) -> None:
    global _context
    _context = ctx


def get_context() -> TrainContext:
    if _context is None:
        raise RuntimeError(
            "not inside a train loop (get_context/report are only valid "
            "inside train_loop_per_worker)")
    return _context


def report(metrics: Dict[str, Any],
           checkpoint: Optional[Checkpoint] = None) -> None:
    """Report metrics (+ optional checkpoint dir) from the train loop.

    All ranks should call report with the same cadence; only rank 0's
    checkpoint is persisted — and it is persisted HERE, at report time,
    so a later crash still leaves every reported checkpoint on storage
    for the failure-policy restart to resume from
    (reference: ray.train.report + sync_actor rank coordination).
    """
    import json
    import os
    import shutil
    import time

    ctx = get_context()
    persisted = None
    if checkpoint is not None and ctx.world_rank == 0:
        persisted = os.path.join(ctx.storage_path,
                                 f"checkpoint_{time.time_ns():019d}")
        shutil.copytree(checkpoint.path, persisted, dirs_exist_ok=True)
        try:
            with open(os.path.join(persisted, ".metrics.json"), "w") as f:
                json.dump({k: v for k, v in metrics.items()
                           if isinstance(v, (int, float, str, bool))}, f)
        except OSError:
            pass
    metrics = dict(metrics)
    if ctx.use_tpu and not ctx.reported:
        from ray_tpu.accelerators import jax_backend
        metrics.setdefault("device", jax_backend.device_report())
    with ctx._lock:
        ctx.reported.append((metrics, persisted))
        n_reports = len(ctx.reported)
        prev = getattr(ctx, "_last_report_t", None)
        now = time.perf_counter()
        ctx._last_report_t = now
    try:
        tags = {"run": ctx.get_experiment_name(),
                "rank": str(ctx.world_rank)}
        TRAIN_REPORTED_STEPS.set(float(n_reports), tags=tags)
        if prev is not None and now > prev:
            step_s = now - prev
            TRAIN_STEP_SECONDS.set(step_s, tags=tags)
            # estimated MFU: either reported directly, or derived from
            # flops_per_step against the hardware peak
            mfu = metrics.get("mfu")
            if mfu is None:
                flops = metrics.get("flops_per_step")
                peak = metrics.get("peak_flops_per_s") or float(
                    os.environ.get("RTPU_PEAK_FLOPS_PER_S", 0) or 0)
                if flops and peak:
                    mfu = float(flops) / (step_s * float(peak))
            if mfu is not None:
                TRAIN_MFU.set(min(max(float(mfu), 0.0), 1.0), tags=tags)
    except Exception:  # noqa: BLE001 — observability must not fail a run
        logger.debug("train step gauges not recorded", exc_info=True)


def get_checkpoint() -> Optional[Checkpoint]:
    return get_context().resume_checkpoint


def get_dataset_shard(name: str = "train"):
    """This worker's shard of a dataset passed to the trainer
    (reference: streaming_split per-worker iterators, data/dataset.py:1853)."""
    ctx = get_context()
    ds = ctx.datasets.get(name)
    if ds is None:
        return None
    from ray_tpu.data.iterator import DataIterator
    if isinstance(ds, DataIterator):
        # Already this rank's split — the trainer splits once
        # driver-side; splitting again here would execute the whole
        # dataset once per worker.
        return ds
    if hasattr(ds, "streaming_split"):
        return ds.streaming_split(ctx.world_size)[ctx.world_rank]
    return ds
