"""The REAL jax.distributed boundary: a 2-process CPU group on localhost.

VERDICT round-2 item 4: ``parallel/distributed.py`` wrapped
``jax.distributed.initialize`` but no test ever spun up an actual
2-process runtime — only the env parsing was covered. This test forks two
fresh Python processes (clean JAX runtimes), joins them through a
localhost coordinator via ``initialize_distributed()``, asserts
``jax.process_count() == 2``, and runs one cross-process ``psum`` whose
result every process must agree on — the DCN machine boundary the
reference exercises with a second physical machine and ``.env SERVER_IP``
(experiment/RunnerConfig.py:122-131, README.md:25-31).
"""

import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent

WORKER = r"""
import json, os, sys

import jax
import jax.numpy as jnp
from jax import shard_map

# clean runtime: JAX_PLATFORMS=cpu arrives through the env set below
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.distributed import (
    initialize_distributed,
    global_device_summary,
    is_coordinator,
)

joined = initialize_distributed()
assert joined, "COORDINATOR_ADDRESS was set; initialize must join"
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 2  # one CPU device per process, global view

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.experimental import multihost_utils

mesh = Mesh(jax.devices(), ("dcn",))

# each process contributes (process_index + 1); psum over the mesh axis
# must give 1 + 2 = 3 in BOTH processes.
local = jnp.asarray([float(jax.process_index() + 1)])
global_arr = multihost_utils.host_local_array_to_global_array(
    local, mesh, P("dcn")
)

summed = jax.jit(
    shard_map(
        lambda x: jax.lax.psum(x, "dcn"),
        mesh=mesh,
        in_specs=P("dcn"),
        out_specs=P(),
    )
)(global_arr)

import numpy as np

local_value = float(
    np.asarray(
        multihost_utils.global_array_to_host_local_array(summed, mesh, P())
    )[0]
)
out = {
    "process_id": jax.process_index(),
    "process_count": jax.process_count(),
    "is_coordinator": is_coordinator(),
    "psum": local_value,
    "summary": global_device_summary(),
}
print("RESULT " + json.dumps(out))
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_group_psum(tmp_path):
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update(
            COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            NUM_PROCESSES="2",
            PROCESS_ID=str(pid),
            JAX_PLATFORMS="cpu",
            # exactly one local CPU device per process (the conftest's
            # 8-virtual-device flag must not leak in)
            XLA_FLAGS="--xla_force_host_platform_device_count=1",
            PYTHONPATH=str(REPO_ROOT),
        )
        # each worker is a fresh interpreter → a fresh JAX runtime; the
        # parent process's JAX stays untouched
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", WORKER],
                env=env,
                cwd=REPO_ROOT,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    results = {}
    for p in procs:
        out, err = p.communicate(timeout=150)
        assert p.returncode == 0, f"worker failed:\n{out}\n{err}"
        line = next(
            line for line in out.splitlines() if line.startswith("RESULT ")
        )
        r = json.loads(line[len("RESULT "):])
        results[r["process_id"]] = r

    assert set(results) == {0, 1}
    for r in results.values():
        assert r["process_count"] == 2
        assert r["psum"] == pytest.approx(3.0)  # 1 + 2 across processes
    assert results[0]["is_coordinator"] and not results[1]["is_coordinator"]
    assert "2 process(es)" in results[0]["summary"]
