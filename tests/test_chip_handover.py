"""Chips pass from one process to the next: ``Node.stop`` returns only
when the workers it started are gone (the kernel takes a dead worker's
chips back seconds after it stopped answering), and a worker that owns
chips waits for their device nodes before the TPU runtime can find them
busy. No chip here: the device nodes are faked."""

import errno
import os
import signal
import sys
import time

import pytest

import ray_tpu
from ray_tpu.accelerators import tpu as tpu_mod
from ray_tpu.accelerators.tpu import TpuAcceleratorManager


@pytest.mark.parametrize("known_as", ["in_the_pool", "already_dead"])
def test_stop_reaps_a_worker_that_ignores_shutdown(known_as):
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=2, system_config={"task_max_retries": 0})

    @ray_tpu.remote
    def pid():
        return os.getpid()

    stopped = ray_tpu.get(pid.remote(), timeout=60)
    node, = rt.nodes.values()
    workers = [w for w in node._workers.values() if w.proc is not None]
    procs = [w.proc for w in workers]
    handle, = [w for w in workers if w.proc.pid == stopped]
    # a stopped process reads no SHUTDOWN and exits by no will of its
    # own: only the SIGKILL after the grace ends it
    os.kill(stopped, signal.SIGSTOP)
    if known_as == "already_dead":
        # its connection closed and the node gave its place away, as
        # after kill_worker, while the process lingers (a chip owner
        # does for seconds): stop() still has to see it gone
        node._on_worker_death(handle)
        assert handle.worker_id not in node._workers
    t0 = time.monotonic()
    ray_tpu.shutdown()
    took = time.monotonic() - t0
    assert all(p.poll() is not None for p in procs)
    killed, = [p for p in procs if p.pid == stopped]
    assert killed.returncode == -signal.SIGKILL
    # the grace of 2 s and a reaping that takes no time for a process
    # without chips; far under the 60 s stop() would wait for one
    assert 2.0 <= took < 15.0


def test_chip_owning_worker_is_told_its_chips(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(resources={"CPU": 4, "TPU": 4})

    @ray_tpu.remote(num_cpus=0)
    def argv():
        return sys.argv

    def chips_of(args):
        return args[args.index("--chips") + 1] if "--chips" in args else None

    two = ray_tpu.get(argv.options(resources={"TPU": 2}).remote(),
                      timeout=60)
    assert chips_of(two) in ("0,1", "2,3")
    # a worker that owns no chip is told of none, and waits for none
    assert chips_of(ray_tpu.get(argv.remote(), timeout=60)) is None


@pytest.fixture
def fake_nodes(monkeypatch):
    """Four fake device nodes; ``busy[path]`` is how many more probes
    find that node held by another process."""
    busy, opened = {}, []
    monkeypatch.setattr(tpu_mod.glob, "glob", lambda pattern: [])
    real_listdir, real_open, real_close = os.listdir, os.open, os.close

    def listdir(path):
        if path == "/dev/vfio":
            return ["vfio", "3", "10", "0", "2"]    # numeric order: 0 2 3 10
        return real_listdir(path)

    def open_(path, flags, *args):
        if not str(path).startswith("/dev/vfio/"):
            return real_open(path, flags, *args)
        opened.append(path)
        left = busy.get(path, 0)
        if left:
            busy[path] = left - 1
            raise OSError(errno.EBUSY, "Device or resource busy", path)
        if path.endswith("/10"):
            raise PermissionError(errno.EACCES, "Permission denied", path)
        return -7

    monkeypatch.setattr(tpu_mod.os, "listdir", listdir)
    monkeypatch.setattr(tpu_mod.os, "open", open_)
    monkeypatch.setattr(
        tpu_mod.os, "close", lambda fd: None if fd == -7 else real_close(fd))
    # a clock that only sleeping moves
    now = [0.0]
    monkeypatch.setattr(tpu_mod.time, "monotonic", lambda: now[0])
    monkeypatch.setattr(tpu_mod.time, "sleep",
                        lambda s: now.__setitem__(0, now[0] + s))
    return busy, opened


def test_chips_are_device_nodes_in_numeric_order(fake_nodes):
    assert TpuAcceleratorManager.chip_device_paths([0, 1, 2, 3]) == [
        "/dev/vfio/0", "/dev/vfio/2", "/dev/vfio/3", "/dev/vfio/10"]
    assert TpuAcceleratorManager.chip_device_paths([2]) == ["/dev/vfio/3"]
    # an index this host has no node for names nothing to wait for
    assert TpuAcceleratorManager.chip_device_paths([7]) == []


@pytest.mark.parametrize("case", ["free", "busy_then_free", "never_free",
                                  "other_error", "no_device_nodes"])
def test_wait_for_chips(fake_nodes, monkeypatch, capsys, case):
    busy, opened = fake_nodes
    if case == "free":
        assert TpuAcceleratorManager.wait_for_chips([0, 1]) == 0.0
        assert opened == ["/dev/vfio/0", "/dev/vfio/2"]
        assert capsys.readouterr().err == ""
    elif case == "busy_then_free":
        busy.update({"/dev/vfio/2": 3, "/dev/vfio/3": 5})
        waited = TpuAcceleratorManager.wait_for_chips([0, 1, 2])
        assert waited == 5 * 0.25   # five probes found a node busy
        assert opened.count("/dev/vfio/3") == 6
        assert f"waited {waited:.1f} s for chips [0, 1, 2]" in \
            capsys.readouterr().err
    elif case == "never_free":
        busy.update({"/dev/vfio/2": 10**6, "/dev/vfio/0": 2})
        with pytest.raises(TimeoutError) as err:
            TpuAcceleratorManager.wait_for_chips([0, 1], timeout_s=3.0)
        assert "/dev/vfio/2 busy" in str(err.value)
        assert "/dev/vfio/0" not in str(err.value)
        assert len(opened) == 2 * 13    # every 0.25 s from 0 to 3 s
    elif case == "other_error":
        # a node that will not open for another reason is the TPU
        # runtime's to report, not something to wait for
        assert TpuAcceleratorManager.wait_for_chips([3]) == 0.0
        assert opened == ["/dev/vfio/10"]
    else:
        monkeypatch.setattr(tpu_mod.os, "listdir",
                            lambda path: (_ for _ in ()).throw(
                                FileNotFoundError(path)))
        assert TpuAcceleratorManager.wait_for_chips([0, 1, 2, 3]) == 0.0
        assert opened == []
