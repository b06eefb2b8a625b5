"""Real two-process multi-controller validation (slow tier): launch two CPU
processes through ``jax.distributed`` and drive ``initialize_multihost`` +
the host-level collectives — barrier, master_only, the KV-transport host
gathers the pod resilience layer rides on, and a process-LOCAL mesh psum —
the paths every single-process test leaves cold (reference NCCL shim role,
VAR_models/dist.py).

Deliberately NOT here: a process-spanning mesh. XLA:CPU cannot compile a
cross-process program at all ("Multiprocess computations aren't implemented
on the CPU backend"), which is exactly why multi-process CPU pods run
host-sharded (pop_host_shard) with local programs + host-level gathers —
the thing this test validates.
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")

from hyperscalees_t2i_tpu.parallel import (
    initialize_multihost, is_master, barrier, make_mesh, POP_AXIS, psum_tree,
    shard_map,
)
from hyperscalees_t2i_tpu.parallel.collectives import master_only

assert initialize_multihost(), "multihost runtime failed to initialize"
assert jax.process_count() == 2
assert jax.device_count() == 4  # 2 hosts x 2 local

import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

# psum over a process-LOCAL mesh (the host-sharded pod shape: each process
# compiles over its own devices only — XLA:CPU cannot span processes)
mesh = make_mesh({POP_AXIS: 2}, devices=jax.local_devices())
x = jax.device_put(
    jnp.asarray([1.0, 2.0]), NamedSharding(mesh, P(POP_AXIS))
)
total = shard_map(
    lambda s: psum_tree(s, POP_AXIS), mesh=mesh,
    in_specs=P(POP_AXIS), out_specs=P(), check_vma=False,
)(x)
val = float(total.addressable_data(0)[0])
assert val == 3.0, val

marker = master_only(lambda: "master-ran")()
assert (marker == "master-ran") == is_master()
barrier("test-sync")

# cross-host scalar reduction (PR 2): host-local values → global means.
# On CPU this rides the coordination-service KV transport (PR 6).
from hyperscalees_t2i_tpu.parallel.collectives import (
    host_allgather_bytes, host_allgather_rows, host_flag_any,
    host_scalar_allgather, host_scalar_allmean,
)
red = host_scalar_allmean({"step_time_s": float(jax.process_index()), "const": 2.0})
assert red["step_time_s"] == 0.5, red  # mean of ranks 0 and 1
assert red["const"] == 2.0, red

# per-rank rows (the desync fingerprint path): float32 bit-exact round-trip
g = host_scalar_allgather({"fp": 1.25 + jax.process_index()})
assert g["fp"].tolist() == [1.25, 2.25], g

# fixed-length byte gather (the coordinated-commit digest vote transport)
rows = host_allgather_bytes(bytes([jax.process_index()]) * 4, 4)
assert rows == [b"\x00" * 4, b"\x01" * 4], rows

# row concatenation (the pod fitness gather): rank order, bit-exact
rank = jax.process_index()
full = host_allgather_rows({"s": np.full((2, 3), float(rank), np.float32)})
assert full["s"].shape == (4, 3)
assert full["s"][:2].sum() == 0.0 and full["s"][2:].sum() == 6.0, full["s"]

# preemption-broadcast OR: only rank 1 raises the flag; both must see it
assert host_flag_any(rank == 1) is True
assert host_flag_any(False) is False

# a second barrier must work too (unique coordination-service ids per call)
barrier("test-sync")

# per-process trace segmentation: rank 0 → trace.jsonl, rank 1 → trace.1.jsonl
from hyperscalees_t2i_tpu.obs.multihost import trace_segment_path
seg = trace_segment_path("/tmp/does-not-matter")
expect = "trace.jsonl" if jax.process_index() == 0 else f"trace.{jax.process_index()}.jsonl"
assert seg.name == expect, seg

print(f"proc{jax.process_index()} ok", flush=True)
"""


@pytest.mark.slow
def test_two_process_multihost_runtime(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    procs, outs = [], []
    try:
        # pick a free port just before spawning (small TOCTOU window remains;
        # the coordinator failing to bind surfaces as a loud worker error)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        for pid in range(2):
            env = dict(os.environ)
            env.update(
                JAX_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
                JAX_NUM_PROCESSES="2",
                JAX_PROCESS_ID=str(pid),
                PYTHONPATH=str(REPO),  # script lives in tmp; package lives here
            )
            procs.append(subprocess.Popen(
                [sys.executable, str(worker)], env=env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            ))
        for p in procs:
            outs.append(p.communicate(timeout=240)[0])
    finally:
        # one proc dying early leaves its peer blocked in distributed init —
        # reap it and surface whatever it printed instead of hiding the cause
        for p in procs:
            if p.poll() is None:
                p.kill()
                out, _ = p.communicate()
                print(f"killed stuck worker; output:\n{(out or '')[-1500:]}")
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc{pid} failed:\n{out[-2000:]}"
        assert f"proc{pid} ok" in out
