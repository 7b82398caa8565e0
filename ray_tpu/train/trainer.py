"""JaxTrainer: controller + worker group + failure policy.

reference: python/ray/train/v2 — the controller state machine
(_internal/execution/controller/controller.py:100, state.py:89-154:
Initializing→Scheduling→Running→Restarting→Finished/Errored), the
worker group (execution/worker_group/worker_group.py), the JAX backend
(v2/jax/jax_trainer.py:19, config.py:29 jax.distributed bootstrap), and
TPU slice reservation (TPUReservationCallback + reserve_tpu_slice,
_private/accelerators/tpu.py:145).

Workers are actors on the core runtime ("tpu" worker profile when
use_tpu — they see the chips; the controller and plain tasks don't).
Inside each worker the user's train_loop_per_worker runs with the
TrainContext set, so report()/get_checkpoint()/get_dataset_shard() work,
and a collective group "<run>/train" is pre-initialized for host-side
allreduce/barrier (in-graph math should use the mesh instead).
"""

from __future__ import annotations

import logging
import os
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.core import runtime as runtime_mod
from ray_tpu.core import serialization
from ray_tpu.exceptions import ActorError, RayTpuError, TaskError, WorkerCrashedError
from ray_tpu.train.checkpoint import Checkpoint, CheckpointManager
from ray_tpu.train.config import Result, RunConfig, ScalingConfig
from ray_tpu.util import flight_recorder
from ray_tpu.util.placement_group import placement_group, remove_placement_group

logger = logging.getLogger(__name__)


class _TrainWorker:
    """Actor hosting one rank of the gang (runs in a 'tpu'-profile
    worker process when TPU resources are requested)."""

    def __init__(self, rank: int, world_size: int, storage_path: str,
                 group_name: str, jax_env: Optional[dict] = None,
                 grad_compression: Optional[str] = None,
                 zero1: bool = False, pipeline_stages: int = 1,
                 microbatches: int = 1, schedule: str = "1f1b",
                 use_tpu: bool = False):
        self.rank = rank
        self.world_size = world_size
        # this worker's stall watch speaks as a train worker from here on
        flight_recorder.rename_worker("train_worker")
        self.storage_path = storage_path
        self.group_name = group_name
        self.grad_compression = grad_compression
        self.zero1 = zero1
        self.use_tpu = use_tpu
        if use_tpu:
            from ray_tpu.accelerators import jax_backend
            jax_backend.track_compile_time()
        # pipeline topology: stage-major rank layout (adjacent ranks =
        # adjacent stages of one replica), gradient sync per stage
        self.pipeline_stages = max(1, pipeline_stages)
        self.microbatches = microbatches
        self.schedule = schedule
        self.pipeline_stage = rank % self.pipeline_stages
        self.pipeline_replica = rank // self.pipeline_stages
        self.stage_group_name: Optional[str] = None
        if jax_env:
            # Multi-host bootstrap (reference: _setup_jax_tpu_environment).
            # The coordinator must bind on RANK 0's host (on a pod that's
            # a slice host the head can't predict), so rank 0 picks a
            # local port and publishes it through the GCS KV; the rest
            # of the gang polls for it.
            if jax_env.get("coordinator_address") is None:
                jax_env = dict(jax_env)
                jax_env["coordinator_address"] = \
                    self._rendezvous_coordinator(
                        jax_env.get("process_id", 0))
            from ray_tpu.parallel.mesh import initialize_distributed
            initialize_distributed(**jax_env)
            import jax
            if jax.device_count() != world_size * jax.local_device_count():
                # e.g. several one-chip workers on ONE TPU host: each
                # is bounded to its own chip and the TPU runtime does
                # not join them into one slice
                raise RuntimeError(
                    f"rank {rank}: {world_size} workers joined "
                    f"jax.distributed but see {jax.device_count()} "
                    f"devices in all, {jax.local_device_count()} local "
                    "— not one mesh. On one host, give ONE worker all "
                    "its chips (num_workers=1, tpu_chips_per_worker="
                    "<chips>); several workers are for several hosts.")
        from ray_tpu.parallel import collective
        collective.init_collective_group(world_size, rank, group_name)
        if self.pipeline_stages > 1:
            # cross-replica group per stage: DDP/ZeRO-1 allreduce of a
            # stage's grads only involves the replicas holding that
            # stage's parameters
            dp_world = world_size // self.pipeline_stages
            self.stage_group_name = \
                f"{group_name}/stage{self.pipeline_stage}"
            collective.init_collective_group(
                dp_world, self.pipeline_replica, self.stage_group_name)

    def _rendezvous_coordinator(self, process_id: int) -> str:
        import socket as _socket
        import time as _time

        from ray_tpu.core import runtime as runtime_mod
        rt = runtime_mod.get_runtime()
        key = f"jaxcoord/{self.group_name}".encode()
        if process_id == 0:
            try:
                host = _socket.gethostbyname(_socket.gethostname())
            except OSError:
                host = "127.0.0.1"
            probe = _socket.socket()
            probe.bind((host, 0))
            address = f"{host}:{probe.getsockname()[1]}"
            probe.close()
            rt.gcs_call("kv_put", key, address.encode(), "train")
            return address
        deadline = _time.monotonic() + 120.0
        while _time.monotonic() < deadline:
            value = rt.gcs_call("kv_get", key, "train")
            if value:
                return value.decode()
            _time.sleep(0.05)
        raise TimeoutError(
            "rank 0 never published the jax.distributed coordinator "
            f"address for group {self.group_name}")

    def run(self, loop_blob: bytes, loop_config: Optional[dict],
            resume_path: Optional[str], datasets_blob: Optional[bytes]):
        from ray_tpu.train import context as ctx_mod
        loop = serialization.loads(loop_blob)
        datasets = serialization.loads(datasets_blob) if datasets_blob else {}
        ctx = ctx_mod.TrainContext(
            world_size=self.world_size, world_rank=self.rank,
            storage_path=self.storage_path,
            resume_checkpoint=Checkpoint(resume_path) if resume_path else None,
            datasets=datasets, group_name=self.group_name,
            grad_compression=self.grad_compression, zero1=self.zero1,
            pipeline_stages=self.pipeline_stages,
            microbatches=self.microbatches, schedule=self.schedule,
            pipeline_stage=self.pipeline_stage,
            pipeline_replica=self.pipeline_replica,
            stage_group_name=self.stage_group_name,
            use_tpu=self.use_tpu)
        ctx_mod.set_context(ctx)
        try:
            if loop_config is not None:
                loop(loop_config)
            else:
                try:
                    loop()
                except TypeError:
                    loop({})
        finally:
            ctx_mod.set_context(None)
        return ctx.reported

    def ping(self):
        return self.rank


class JaxTrainer:
    """Gang-scheduled SPMD training driver.

    The DDP/FSDP/TP modes are not wrapper classes: the train loop builds
    a mesh (`ray_tpu.parallel.mesh`) and shards params with
    `llama_sharding_rules`/`ShardingConfig`; XLA inserts the gradient
    collectives (SURVEY.md §2.3 X2/X3).
    """

    def __init__(self, train_loop_per_worker: Callable,
                 *, train_loop_config: Optional[dict] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 datasets: Optional[Dict[str, Any]] = None):
        self.train_loop = train_loop_per_worker
        self.train_loop_config = train_loop_config
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = datasets or {}
        self.state_history: List[str] = ["INITIALIZING"]

    def _transition(self, state: str) -> None:
        self.state_history.append(state)
        # publish run state for the dashboard's train module
        # (reference: dashboard/modules/train — run states from the
        # controller); best-effort: observability must not fail a run
        try:
            import time as _time

            from ray_tpu.core import runtime as runtime_mod
            from ray_tpu.core import serialization as _ser
            rt = runtime_mod.get_runtime_or_none()
            if rt is None:
                return
            if not hasattr(self, "_run_record_id"):
                # unique per trainer: same-named (or unnamed) runs must
                # not clobber each other's dashboard records
                import uuid as _uuid
                self._run_record_id = _uuid.uuid4().hex[:8]
            record = _ser.dumps({
                "name": self.run_config.name or "train_run",
                "run_id": self._run_record_id,
                "state": state,
                "history": list(self.state_history),
                "num_workers": self.scaling_config.num_workers,
                "use_tpu": bool(getattr(self.scaling_config,
                                        "use_tpu", False)),
                "updated_at": _time.time(),
            })
            key = (f"{self.run_config.name or 'train_run'}"
                   f":{self._run_record_id}").encode()
            if rt.is_driver:
                rt.gcs.kv.put(key, record, namespace="train_runs")
                # Retention: keep the newest 50 records. Pruning only on
                # TERMINAL transitions keeps the hot path N+1-free, and
                # skipping our own key means an old-but-active run can't
                # be evicted by a flood of quick newer runs.
                if state in ("FINISHED", "ERRORED", "ABORTED"):
                    keys = rt.gcs.kv.keys(namespace="train_runs")
                    if len(keys) > 50:
                        aged = []
                        for k in keys:
                            if k == key:
                                continue
                            blob = rt.gcs.kv.get(k,
                                                 namespace="train_runs")
                            if blob is None:
                                continue
                            aged.append(
                                (_ser.loads(blob).get("updated_at", 0),
                                 k))
                        aged.sort()
                        for _ts, k in aged[:len(aged) - 49]:
                            rt.gcs.kv.delete(k, namespace="train_runs")
            else:
                rt.gcs_call("kv_put", key, record, "train_runs")
        except Exception:  # noqa: BLE001 — dashboard record is best-effort
            logger.debug("train run-state record not published",
                         exc_info=True)

    def fit(self) -> Result:
        if not ray_tpu.is_initialized():
            ray_tpu.init()
        storage = self.run_config.resolved_storage_path()
        manager = CheckpointManager(
            storage, self.run_config.checkpoint_config.num_to_keep)
        max_failures = self.run_config.failure_config.max_failures
        loop_blob = serialization.dumps(self.train_loop)
        last_error: Optional[Exception] = None

        policy = self.scaling_config.resolved_scaling_policy()
        world = self.scaling_config.num_workers
        for attempt in range(max_failures + 1):
            self._transition("SCHEDULING" if attempt == 0 else "RESTARTING")
            try:
                workers, pg, reservation = self._create_worker_group(
                    storage, world)
            except (ActorError, WorkerCrashedError, TaskError, RayTpuError,
                    TimeoutError, RuntimeError) as e:
                last_error = e
                world = self._resize_after_failure(policy, world)
                if world is None:
                    break
                continue
            resume = manager.latest()
            try:
                self._transition("RUNNING")
                # Split streaming datasets ONCE here and ship each rank
                # its own iterator: n workers each calling
                # streaming_split would spin up n coordinators, each
                # executing the whole dataset. Rebuilt per attempt so an
                # elastic resize re-splits at the new world size.
                datasets_blobs = self._rank_datasets_blobs(len(workers))
                refs = [
                    w.run.remote(loop_blob, self.train_loop_config,
                                 resume.path if resume else None,
                                 datasets_blobs[rank])
                    for rank, w in enumerate(workers)
                ]
                all_reports = ray_tpu.get(refs)
                self._transition("FINISHED")
                return self._build_result(all_reports, manager, storage)
            except (ActorError, WorkerCrashedError, TaskError,
                    RayTpuError) as e:
                last_error = e
            finally:
                for w in workers:
                    try:
                        ray_tpu.kill(w)
                    except Exception:  # noqa: BLE001 — already torn down
                        logger.debug("train worker kill failed during "
                                     "group teardown", exc_info=True)
                if pg is not None:
                    remove_placement_group(pg)
                if reservation is not None:
                    reservation.release()
            # Decide the next gang size only after the failed group's
            # reservations are released — the policy reads available
            # cluster resources.
            world = self._resize_after_failure(policy, world)
            if world is None:
                break
        self._transition("ERRORED")
        final = manager.latest()
        return Result(metrics={}, checkpoint=final, path=storage,
                      error=last_error)

    def _resize_after_failure(self, policy, world: int):
        """Scaling-policy hook: pick the next gang size (None = stop).
        A shrink is the elastic Resizing transition; training resumes
        from the last checkpoint at the new world size."""
        new_world = policy.world_size_after_failure(
            world, runtime_mod.get_runtime())
        if new_world is None or new_world < 1:
            return None
        stages = max(1, self.scaling_config.pipeline_stages)
        if stages > 1:
            # elastic shrink must keep whole pipeline replicas
            new_world -= new_world % stages
            if new_world < stages:
                return None
        if new_world != world:
            self._transition("RESIZING")
            from ray_tpu.core import events
            events.emit("TRAIN_RESIZED", "WARNING",
                        message=f"elastic resize {world} -> {new_world}",
                        data={"from": world, "to": new_world})
        return new_world

    def _rank_datasets_blobs(self, world: int) -> List[Optional[bytes]]:
        """Per-rank serialized datasets dicts: streaming datasets are
        split once driver-side into per-rank iterators sharing ONE
        coordinator/execution; non-splittable values ship whole."""
        if not self.datasets:
            return [None] * world
        per_rank: List[Dict[str, Any]] = [{} for _ in range(world)]
        for name, ds in self.datasets.items():
            if hasattr(ds, "streaming_split"):
                shards = ds.streaming_split(world)
                for rank in range(world):
                    per_rank[rank][name] = shards[rank]
            else:
                for rank in range(world):
                    per_rank[rank][name] = ds
        return [serialization.dumps(d) for d in per_rank]

    def _create_worker_group(self, storage: str,
                             num_workers: Optional[int] = None):
        scaling = self.scaling_config
        if num_workers is None:
            num_workers = scaling.num_workers
        stages = max(1, scaling.pipeline_stages)
        if stages > 1:
            from ray_tpu.train.pipeline.schedule import SCHEDULES
            if scaling.schedule not in SCHEDULES:
                raise ValueError(
                    f"unknown pipeline schedule {scaling.schedule!r}; "
                    f"expected one of {SCHEDULES}")
            if num_workers % stages:
                raise ValueError(
                    f"num_workers={num_workers} is not divisible by "
                    f"pipeline_stages={stages}: every data-parallel "
                    "replica needs a full set of stage workers")
            if scaling.microbatches < 1:
                raise ValueError(
                    f"microbatches must be >= 1, got "
                    f"{scaling.microbatches}")
        res = scaling.worker_resources()
        # Multi-host slice gang: reserve a whole slice via its head
        # resource, then pin every worker to that slice's hosts with the
        # slice-name resource + STRICT_SPREAD (one worker per host) —
        # the reference's JaxTrainer shape (reference: reserve_tpu_slice
        # tpu.py:145 + TPUReservationCallback).
        slice_name = None
        slice_reservation = None
        if (scaling.use_tpu and scaling.topology
                and scaling.accelerator_type):
            from ray_tpu.accelerators.tpu import reserve_tpu_slice
            slice_reservation = reserve_tpu_slice(scaling.topology,
                                                  scaling.accelerator_type)
            if slice_reservation is not None:
                slice_name = slice_reservation.name
                res[slice_name] = 1.0
        # Gang reservation: one bundle per worker. PACK fallback keeps
        # single-node dev boxes working.
        pg = None
        strategy = (("STRICT_SPREAD" if slice_name
                     else scaling.placement_strategy)
                    if num_workers > 1 else "PACK")
        try:
            pg = placement_group([dict(res)] * num_workers,
                                 strategy=strategy)
            # Creation queues (never raises) when the gang doesn't fit
            # yet; give the reservation a short window, then fall back
            # to loose scheduling so single-node dev boxes still train
            # (an unready queued PG must be removed, or it would grab
            # resources later with no owner).
            # NOTE: uses the module-level remove_placement_group — a
            # function-local import here would shadow it for the whole
            # function scope and break the later failure-path call.
            if not pg.ready(timeout=2.0):
                remove_placement_group(pg)
                pg = None
        except Exception:
            pg = None
        group_name = f"train/{os.path.basename(storage)}/{time.time_ns()}"
        WorkerActor = ray_tpu.remote(_TrainWorker)
        workers = []
        for rank in range(num_workers):
            opts = {"num_cpus": res.get("CPU", 1)}
            if "TPU" in res:
                opts["num_tpus"] = res["TPU"]
            if slice_name is not None:
                opts["resources"] = {slice_name: 1.0}
            if pg is not None:
                # Place each worker INSIDE its reserved bundle rather
                # than double-booking from the free pool (reference:
                # PlacementGroupSchedulingStrategy per worker rank).
                from ray_tpu.util.placement_group import (
                    PlacementGroupSchedulingStrategy)
                opts["scheduling_strategy"] = \
                    PlacementGroupSchedulingStrategy(
                        placement_group=pg,
                        placement_group_bundle_index=rank)
            env = None
            if num_workers > 1 and scaling.use_tpu:
                # coordinator_address resolves inside the gang: rank 0
                # binds locally and publishes via the GCS KV (see
                # _TrainWorker) — the head can't pick it, because on a
                # real pod rank 0 lives on a slice host, not here.
                env = {"num_processes": num_workers,
                       "process_id": rank}
            workers.append(
                WorkerActor.options(**opts).remote(
                    rank, num_workers, storage, group_name,
                    jax_env=env,
                    grad_compression=scaling.grad_compression,
                    zero1=scaling.zero1,
                    pipeline_stages=stages,
                    microbatches=scaling.microbatches,
                    schedule=scaling.schedule,
                    use_tpu=scaling.use_tpu))
        # Fail fast if any worker can't construct — and release every
        # reservation on the way out, or the next (resized) attempt sees
        # the failed gang still holding the cluster's resources.
        try:
            ray_tpu.get([w.ping.remote() for w in workers])
        except BaseException:
            for w in workers:
                try:
                    ray_tpu.kill(w)
                except Exception:  # noqa: BLE001 — fail-fast cleanup
                    logger.debug("train worker kill failed during "
                                 "fail-fast cleanup", exc_info=True)
            if pg is not None:
                remove_placement_group(pg)
            if slice_reservation is not None:
                slice_reservation.release()
            raise
        return workers, pg, slice_reservation

    def _build_result(self, all_reports, manager: CheckpointManager,
                      storage: str) -> Result:
        rank0 = all_reports[0] if all_reports else []
        checkpoint = None
        history = []
        for metrics, ckpt_path in rank0:
            history.append(metrics)
            if ckpt_path:
                checkpoint = manager.register(ckpt_path, metrics)
        final_metrics = history[-1] if history else {}
        return Result(metrics=final_metrics, checkpoint=checkpoint,
                      path=storage, metrics_history=history,
                      all_reports=list(all_reports))
