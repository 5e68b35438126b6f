"""Worker process for the 2-process multi-host smoke test (VERDICT r2
next#6): each process owns 2 virtual CPU devices; together they form a
4-device global mesh. Exercises the REAL multi-host wiring —
``initialize_distributed`` (jax.distributed over a local coordinator),
global-mesh construction, ``make_array_from_process_local_data``
ingestion, and a psum-backed normal-equations fit whose Gram/cross
all-reduce crosses the process boundary — the analogue of the
reference's Spark cluster attach + treeReduce
(``bin/run-pipeline.sh``, ``BlockLinearMapper.scala:234-240``).

Usage: multihost_worker.py <process_id> <num_processes> <coordinator_port>
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax  # noqa: E402
import numpy as np  # noqa: E402


def main():
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

    from keystone_tpu.parallel.mesh import initialize_distributed

    initialize_distributed(f"127.0.0.1:{port}", nproc, pid)
    assert jax.process_count() == nproc, jax.process_count()
    devices = jax.devices()
    assert len(devices) == 2 * nproc, devices  # global device view

    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.ops import linalg
    from keystone_tpu.parallel.mesh import make_mesh, mesh_scope

    n, d, k = 64, 16, 3
    rng = np.random.RandomState(0)  # same data on every host (SPMD)
    A = rng.randn(n, d).astype(np.float32)
    W = rng.randn(d, k).astype(np.float32)
    Y = (A @ W).astype(np.float32)

    mesh = make_mesh(devices)  # data axis spans BOTH processes
    with mesh_scope(mesh):
        sh = NamedSharding(mesh, P("data"))
        rows = n // (2 * nproc)  # rows per device

        def local(arr):
            # this host's contiguous row shard (device order == mesh
            # data order: process 0 owns devices 0-1, process 1 owns 2-3)
            lo = pid * 2 * rows
            return arr[lo:lo + 2 * rows]

        Ag = jax.make_array_from_process_local_data(sh, local(A), (n, d))
        Yg = jax.make_array_from_process_local_data(sh, local(Y), (n, k))

        # Gram + cross all-reduce crosses the process boundary here
        W_fit = linalg.normal_equations(Ag, Yg, lam=1e-6)
        W_np = np.linalg.solve(A.T @ A + 1e-6 * np.eye(d), A.T @ Y)
        err = np.abs(np.asarray(W_fit) - W_np).max()
        assert err < 1e-3, f"cross-process solve mismatch: {err}"

        mean = np.asarray(linalg.distributed_mean(Ag, n))
        assert np.allclose(mean, A.mean(0), atol=1e-5)

    print(f"MULTIHOST_OK pid={pid} err={err:.2e}", flush=True)


if __name__ == "__main__":
    main()
