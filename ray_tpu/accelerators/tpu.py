"""TPU accelerator manager: detection, partitioning, slice metadata, gangs.

Capability parity with the reference's TPUAcceleratorManager
(reference: python/ray/_private/accelerators/tpu.py:199-578):
- chip autodetection via /dev/accel* and /dev/vfio (tpu.py:225-245)
- per-worker TPU_VISIBLE_CHIPS + host/chip-bounds env assignment
  (tpu.py:283-323)
- pod type / slice name / worker id / topology from GKE env vars or the
  GCE metadata server (tpu.py:326-433)
- the slice-head gang resource ``TPU-{pod_type}-head`` on worker 0 plus
  the slice-name resource on every host (tpu.py:482-545)
- node labels tpu-slice-name/tpu-worker-id/tpu-topology/tpu-pod-type
  (tpu.py:548-578)
- ``reserve_tpu_slice`` for JaxTrainer gang scheduling (tpu.py:145-196)

Test seam: everything environment-derived reads ordinary env vars (the
GKE names double as the fake interface — set TPU_NAME/TPU_WORKER_ID/
TPU_ACCELERATOR_TYPE/TPU_TOPOLOGY and, for chip count,
RTPU_TPU_NUM_CHIPS), so a dev box simulates any slice topology without
hardware, per SURVEY.md §7 "Testing without TPUs".
"""

from __future__ import annotations

import errno
import glob
import os
import re
import sys
import time
from typing import Dict, List, Optional

# GKE-injected env vars (and the test fake interface).
GKE_TPU_ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"
GKE_TPU_NAME_ENV = "TPU_NAME"
GKE_TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
GKE_TPU_TOPOLOGY_ENV = "TPU_TOPOLOGY"

# Worker-visibility env vars consumed by the TPU runtime / JAX
# (reference: tpu.py TPU_VISIBLE_CHIPS_ENV_VAR and bounds vars).
TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"
TPU_HOST_BOUNDS_ENV = "TPU_HOST_BOUNDS"
_SINGLE_HOST_BOUNDS = "1,1,1"
_1_CHIP_CONFIG = "1,1,1"
_2_CHIP_CONFIG = "1,2,1"

# GCE metadata server (reference: tpu.py GCE_TPU_* keys).
_GCE_METADATA_URL = ("http://metadata.google.internal/computeMetadata/v1/"
                     "instance/attributes/")
_GCE_KEYS = {
    "pod_type": "accelerator-type",
    "name": "instance-id",
    "worker_id": "agent-worker-number",
    "env": "tpu-env",
}

_POD_TYPE_RE = re.compile(r"^v\d+[a-zA-Z]*-\d+$")


import functools


@functools.lru_cache(maxsize=None)
def _gce_metadata(key: str) -> Optional[str]:
    """Poll the GCE metadata server; None off-GCE. Cached per key —
    node registration probes several keys and a non-GCE box would
    otherwise pay the connect timeout on every lookup."""
    import urllib.error
    import urllib.request

    try:
        req = urllib.request.Request(
            _GCE_METADATA_URL + key, headers={"Metadata-Flavor": "Google"})
        with urllib.request.urlopen(req, timeout=0.5) as resp:
            return resp.read().decode().strip()
    except (urllib.error.URLError, OSError, ValueError):
        return None


class TpuAcceleratorManager:
    """Google TPU accelerator manager (reference: tpu.py:199)."""

    resource_name = "TPU"

    # --- chip detection -------------------------------------------------
    @staticmethod
    def _device_nodes() -> List[str]:
        """The chips' device nodes in chip order: /dev/accel*, else
        /dev/vfio's numbered groups (reference: tpu.py:225-245)."""
        nodes = sorted(glob.glob("/dev/accel*"))
        if nodes:
            return nodes
        try:
            entries = os.listdir("/dev/vfio")
        except FileNotFoundError:
            return []
        return [f"/dev/vfio/{g}"
                for g in sorted((e for e in entries if e.isdigit()), key=int)]

    @staticmethod
    def num_chips_on_node() -> int:
        """Detect local chips by their device nodes. RTPU_TPU_NUM_CHIPS
        overrides for tests/simulation."""
        override = os.environ.get("RTPU_TPU_NUM_CHIPS")
        if override is not None:
            return int(override)
        return len(TpuAcceleratorManager._device_nodes())

    # --- hand-over of chips between processes ---------------------------
    @staticmethod
    def chip_device_paths(chips: List[int]) -> List[str]:
        """The device nodes of chips by index (an index this host has
        no node for names nothing)."""
        nodes = TpuAcceleratorManager._device_nodes()
        return [nodes[i] for i in chips if 0 <= i < len(nodes)]

    @staticmethod
    def wait_for_chips(chips: List[int], timeout_s: float = 120.0,
                       poll_s: float = 0.25) -> float:
        """Wait until the device nodes of ``chips`` open. A process that
        held them and was killed, or is exiting, keeps them until the
        kernel has taken them back, which takes seconds after it has
        stopped answering; the TPU runtime does not wait and fails with
        "Device or resource busy". Returns the seconds waited (said on
        stderr when any); raises TimeoutError naming the busy nodes."""
        paths = TpuAcceleratorManager.chip_device_paths(chips)
        start = time.monotonic()
        while True:
            busy = []
            for path in paths:
                try:
                    os.close(os.open(path, os.O_RDWR))
                except OSError as exc:
                    # any other error is the runtime's to report
                    if exc.errno == errno.EBUSY:
                        busy.append(path)
            waited = time.monotonic() - start
            if not busy:
                if waited >= poll_s:
                    print(f"ray_tpu: waited {waited:.1f} s for chips "
                          f"{chips} to come free", file=sys.stderr,
                          flush=True)
                return waited
            if waited >= timeout_s:
                raise TimeoutError(
                    f"chips {chips} still held by another process after "
                    f"{waited:.0f} s: {', '.join(busy)} busy")
            time.sleep(poll_s)

    # --- worker visibility ----------------------------------------------
    @staticmethod
    def visible_chip_env(chips: List[int],
                         total_on_node: int) -> Dict[str, Optional[str]]:
        """Env assignment giving a worker a chip subset. Returns a dict
        of env updates (None value = unset). Mirrors the reference's
        combination of visible chips + chip/host bounds so the TPU
        runtime initializes on the subset (reference: tpu.py:283-323,
        and google/jax#14977 for why the bounds are needed)."""
        n = len(chips)
        if total_on_node and n >= total_on_node:
            # full host: let the runtime use its defaults
            return {TPU_VISIBLE_CHIPS_ENV: None,
                    TPU_CHIPS_PER_HOST_BOUNDS_ENV: None,
                    TPU_HOST_BOUNDS_ENV: None}
        env: Dict[str, Optional[str]] = {
            TPU_VISIBLE_CHIPS_ENV: ",".join(str(c) for c in chips)}
        if n == 1:
            env[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = _1_CHIP_CONFIG
            env[TPU_HOST_BOUNDS_ENV] = _SINGLE_HOST_BOUNDS
        elif n == 2:
            env[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = _2_CHIP_CONFIG
            env[TPU_HOST_BOUNDS_ENV] = _SINGLE_HOST_BOUNDS
        # n == 4 on an 8-chip host: visible chips only, no bounds — the
        # reference has no bounds config beyond 1/2 chips either
        # (tpu.py:283-323); request validation limits counts to
        # {1, 2, 4, 8} (remote_function.validate_tpu_quantity).
        return env

    # --- slice metadata (GKE env first, then GCE metadata) ---------------
    @staticmethod
    def pod_type() -> Optional[str]:
        value = os.environ.get(GKE_TPU_ACCELERATOR_TYPE_ENV) or \
            _gce_metadata(_GCE_KEYS["pod_type"])
        if value and _POD_TYPE_RE.match(value):
            return value
        return None

    @staticmethod
    def slice_name() -> Optional[str]:
        return os.environ.get(GKE_TPU_NAME_ENV) or \
            _gce_metadata(_GCE_KEYS["name"])

    @staticmethod
    def worker_id() -> Optional[int]:
        raw = os.environ.get(GKE_TPU_WORKER_ID_ENV) or \
            _gce_metadata(_GCE_KEYS["worker_id"])
        try:
            return int(raw) if raw is not None and raw != "" else None
        except ValueError:
            return None

    @staticmethod
    def topology() -> Optional[str]:
        value = os.environ.get(GKE_TPU_TOPOLOGY_ENV)
        if value:
            return value
        env_blob = _gce_metadata(_GCE_KEYS["env"])
        if env_blob:
            match = re.search(r"TOPOLOGY:\s*'([^']+)'", env_blob)
            if match:
                return match.group(1)
        return None

    @staticmethod
    def accelerator_type() -> Optional[str]:
        """Generation resource string, e.g. "TPU-V5P" (tpu.py:436)."""
        pod = TpuAcceleratorManager.pod_type()
        if pod is None:
            return None
        return "TPU-" + pod.split("-")[0].upper()

    @staticmethod
    def num_workers_in_pod() -> Optional[int]:
        """Hosts in this slice: pod chip count / chips per host
        (reference: tpu.py:402-417)."""
        pod = TpuAcceleratorManager.pod_type()
        per_host = TpuAcceleratorManager.num_chips_on_node()
        if not pod or per_host <= 0:
            return None
        num_chips = int(pod.split("-")[1])
        # pod type counts cores for v2-v4 (2 cores/chip); v5e/v5p/v6e+
        # count chips. Use the topology product when available; else
        # assume the count is chips (modern generations).
        topo = TpuAcceleratorManager.topology()
        if topo:
            total = 1
            for part in topo.lower().split("x"):
                total *= int(part)
            num_chips = total
        workers = num_chips // per_host
        if num_chips % per_host:
            workers += 1
        return max(1, workers)

    # --- node registration ------------------------------------------------
    @staticmethod
    def additional_resources() -> Dict[str, float]:
        """Slice gang resources for this node: the slice name on every
        host and ``TPU-{pod_type}-head`` on worker 0, so gangs pin to one
        slice and the head is targetable (reference: tpu.py:482-545)."""
        out: Dict[str, float] = {}
        name = TpuAcceleratorManager.slice_name()
        worker = TpuAcceleratorManager.worker_id()
        pod = TpuAcceleratorManager.pod_type()
        if name and worker is not None and pod:
            out[name] = 1.0
            if worker == 0:
                out[f"TPU-{pod}-head"] = 1.0
        return out

    @staticmethod
    def node_labels() -> Dict[str, str]:
        """Topology labels for scheduling (reference: tpu.py:548-578)."""
        labels: Dict[str, str] = {}
        name = TpuAcceleratorManager.slice_name()
        if name:
            labels["ray.io/tpu-slice-name"] = name
        worker = TpuAcceleratorManager.worker_id()
        if worker is not None:
            labels["ray.io/tpu-worker-id"] = str(worker)
        topo = TpuAcceleratorManager.topology()
        if topo:
            labels["ray.io/tpu-topology"] = topo
        pod = TpuAcceleratorManager.pod_type()
        if pod:
            labels["ray.io/tpu-pod-type"] = pod
        return labels

    @staticmethod
    def augment_node(resources: Dict[str, float],
                     labels: Dict[str, str]) -> None:
        """Fill in detected TPU resources + labels on a node spec
        (called at node registration; no-ops off-TPU)."""
        chips = TpuAcceleratorManager.num_chips_on_node()
        if chips and "TPU" not in resources:
            resources["TPU"] = float(chips)
        if resources.get("TPU"):
            for key, val in TpuAcceleratorManager.additional_resources().items():
                resources.setdefault(key, val)
            for key, val in TpuAcceleratorManager.node_labels().items():
                labels.setdefault(key, val)


def infer_tpu_pod_type_from_topology(topology: str,
                                     accelerator_type: str) -> Optional[str]:
    """"2x2x2" + "TPU-V4" -> "v4-8" (reference: tpu.py:114-129)."""
    try:
        chips = 1
        for part in topology.strip().lower().split("x"):
            chips *= int(part)
        generation = accelerator_type.lower().replace("tpu-", "")
        return f"{generation}-{chips}"
    except (ValueError, AttributeError):
        return None


class SliceReservation:
    """A held slice reservation: the slice name plus the head placement
    group pinning it. ``release()`` returns the head resource (the
    reference leaves this as a TODO; keeping the PG is required so a
    second reservation doesn't deadlock on the still-consumed head)."""

    def __init__(self, name: str, pg):
        self.name = name
        self.placement_group = pg

    def release(self) -> None:
        from ray_tpu.util.placement_group import remove_placement_group
        if self.placement_group is not None:
            try:
                remove_placement_group(self.placement_group)
            finally:
                self.placement_group = None


def reserve_tpu_slice(topology: str, accelerator_type: str,
                      timeout: float = 100.0) -> Optional[SliceReservation]:
    """Reserve a slice via its head resource; returns a SliceReservation
    (``.name`` is the slice name; call ``.release()`` when done).

    Creates a placement group on ``TPU-{pod_type}-head`` with a label
    selector pinning it to a worker-0 host of a matching slice, then
    reads that node's slice-name label — the gang key JaxTrainer uses to
    put one worker on every host of the same slice (reference:
    tpu.py:145-196 reserve_tpu_slice + fetch_tpu_slice_name_from_pg).
    """
    from ray_tpu.core import runtime as runtime_mod
    from ray_tpu.util.placement_group import placement_group

    pod_type = infer_tpu_pod_type_from_topology(topology, accelerator_type)
    if pod_type is None:
        return None
    pg = placement_group(
        bundles=[{f"TPU-{pod_type}-head": 1}],
        strategy="PACK",
        bundle_label_selector=[{
            "ray.io/tpu-worker-id": "0",
            "ray.io/tpu-pod-type": pod_type,
        }])
    if not pg.ready(timeout=timeout):
        # The PG queued (creation never fails fast now) — cancel it, or
        # the abandoned gang would reserve a slice head later with no
        # owner to release it.
        from ray_tpu.util.placement_group import remove_placement_group
        remove_placement_group(pg)
        raise TimeoutError(
            f"failed to reserve a TPU slice head for pod type {pod_type}")
    try:
        rt = runtime_mod.get_runtime()
        node_ids = pg.bundle_node_ids()
        if not node_ids or node_ids[0] is None:
            raise RuntimeError("slice-head placement group has no node")
        record = rt.gcs.nodes.get(node_ids[0])
        name = (record.labels.get("ray.io/tpu-slice-name")
                if record else None)
        if name is None:
            raise RuntimeError(
                "reserved a slice head but its node carries no "
                "ray.io/tpu-slice-name label")
    except BaseException:
        from ray_tpu.util.placement_group import remove_placement_group
        remove_placement_group(pg)
        raise
    return SliceReservation(name, pg)
