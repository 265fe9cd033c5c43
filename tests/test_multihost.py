"""Multi-host execution (VERDICT r1 #8): two real OS processes, each with
its own CPU device set, sync divergent DocSets over TCP speaking the
reference's {docId, clock, changes} protocol, then join one global
8-device mesh (jax.distributed) for a single SPMD reconcile and a
cross-host clock-union collective. The worker logic lives in
tests/multihost_worker.py; this module just orchestrates the processes."""

import os
import socket
import subprocess
import sys

import pytest

_next_port = 0


def _free_port() -> int:
    """A port the workers can bind seconds from now. Asking the kernel
    for port 0 and closing the socket hands the number back to the
    ephemeral pool, where any other test's connection can take it before
    a worker has imported jax; so probe BELOW the ephemeral range, which
    the kernel never gives out on its own, starting from an offset of
    this process's own."""
    global _next_port
    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            top = int(f.read().split()[0])
    except (OSError, ValueError):
        top = 32768
    lo = max(top - 12000, 1024)
    for _ in range(top - lo):
        port = lo + (os.getpid() * 64 + _next_port) % (top - lo)
        _next_port += 1
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue
            return port
    raise RuntimeError("no free port below the ephemeral range")


def _run_workers(worker_file: str, ok_marker: str, extra_env=None):
    worker = os.path.join(os.path.dirname(__file__), worker_file)
    coord, sync = _free_port(), _free_port()
    env = dict(os.environ)
    # a child never takes an accelerator its parent may hold; the workers
    # set their own device count
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.update(extra_env or {})

    procs = [subprocess.Popen(
        [sys.executable, worker, str(pid), str(coord), str(sync)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for pid in (0, 1)]
    outs = ["", ""]
    deadline = 240
    import time
    t0 = time.time()
    try:
        for k, p in enumerate(procs):
            left = max(1.0, deadline - (time.time() - t0))
            outs[k], _ = p.communicate(timeout=left)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        # drain whatever the killed workers managed to print
        for k, p in enumerate(procs):
            try:
                out, _ = p.communicate(timeout=10)
                outs[k] = outs[k] or out or ""
            except Exception:
                pass
        pytest.fail("multihost workers timed out:\n"
                    + "\n---\n".join(o[-3000:] for o in outs))

    for pid, (p, out) in enumerate(zip(procs, outs)):
        tail = "\n".join(out.splitlines()[-25:])
        assert p.returncode == 0, f"worker {pid} failed:\n{tail}"
        assert f"{ok_marker} p{pid}" in out, f"worker {pid} output:\n{tail}"


def test_two_process_sync_and_global_mesh():
    """Interpretive DocSets over the reference JSON protocol (r2 shape)."""
    _run_workers("multihost_worker.py", "MULTIHOST-OK")


def test_two_process_resident_columnar_sync():
    """Device-resident EngineDocSets syncing BINARY columnar frames over
    TCP, then a global-mesh SPMD reconcile + clock-union collective
    (VERDICT r2 #7)."""
    _run_workers("multihost_resident_worker.py", "MULTIHOST-RESIDENT-OK")


def test_two_process_rows_backend_columnar_sync():
    """Same protocol, but document truth in the docs-minor streaming engine
    (EngineDocSet backend="rows") on both hosts."""
    _run_workers("multihost_resident_worker.py", "MULTIHOST-RESIDENT-OK",
                 extra_env={"AMTPU_MH_BACKEND": "rows"})


def test_four_process_hub_sync_and_global_mesh():
    """Four OS processes (2 virtual devices each): hub-and-spoke TCP sync
    with Connection forwarding relaying every spoke's changes, then ONE
    global 8-device jax.distributed mesh for the SPMD reconcile and a
    clock union that must contain all four hosts' seqs."""
    worker = os.path.join(os.path.dirname(__file__),
                          "multihost_ring_worker.py")
    coord, sync = _free_port(), _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)

    nprocs = 4
    procs = [subprocess.Popen(
        [sys.executable, worker, str(pid), str(nprocs), str(coord),
         str(sync)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for pid in range(nprocs)]
    outs = [""] * nprocs
    deadline = 300
    import time
    t0 = time.time()
    try:
        for k, p in enumerate(procs):
            left = max(1.0, deadline - (time.time() - t0))
            outs[k], _ = p.communicate(timeout=left)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        for k, p in enumerate(procs):
            try:
                out, _ = p.communicate(timeout=10)
                outs[k] = outs[k] or out or ""
            except Exception:
                pass
        pytest.fail("4-process workers timed out:\n"
                    + "\n---\n".join(o[-2000:] for o in outs))

    winners = set()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        tail = "\n".join(out.splitlines()[-25:])
        assert p.returncode == 0, f"worker {pid} failed:\n{tail}"
        assert f"MULTIHOST4-OK p{pid}" in out, f"worker {pid}:\n{tail}"
        for line in out.splitlines():
            if line.startswith(f"MULTIHOST4-OK p{pid}"):
                winners.add(line.split("winner=")[1].split()[0])
    # every host agreed on the same LWW winner for the contested field
    assert len(winners) == 1, winners


def test_two_process_sharded_service_columnar_sync():
    """The sharded service node (K engine shards behind one sync surface)
    syncing binary columnar frames over TCP between two OS processes."""
    _run_workers("multihost_resident_worker.py", "MULTIHOST-RESIDENT-OK",
                 extra_env={"AMTPU_MH_BACKEND": "sharded"})
