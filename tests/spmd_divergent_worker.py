"""Deliberately DIVERGENT SPMD worker — the hazard class the
``collective-divergence`` pass (``analysis/spmd.py``) exists to catch,
reproduced for real: every host enters a matched world barrier, then
host 0 takes a barrier its peers never reach. Host 0 wedges in the
unmatched collective (the silent gang-schedule hang — no error, no
progress), its peers finish and exit, and the
:class:`~keystone_tpu.parallel.distributed.DryrunWorld` launcher's
gang grace reaps the wedged member.

Dual-use by the test suite:

* ``tests/test_spmd_passes.py`` PARSES this file and asserts the
  static pass flags the ``if process_index() == 0:`` barrier;
* the ``@slow`` divergence test in ``tests/test_elastic.py`` LAUNCHES
  it under a ``DryrunWorld`` and asserts the dynamic classification:
  the divergent host never prints its done line and is killed by gang
  grace, the straight host exits 0.

Usage (the launcher appends the positionals)::

    python tests/spmd_divergent_worker.py <process_id> <num_processes> \
        <coordinator_port>
"""
import os
import sys
import time


def main():
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from keystone_tpu.parallel.mesh import initialize_distributed

    initialize_distributed(f"127.0.0.1:{port}", nproc, pid)
    from jax.experimental.multihost_utils import sync_global_devices

    # matched on every host: proves the world is up and collectives
    # work before the deliberate divergence below
    sync_global_devices("keystone-diverge-enter")
    print(f"DIVERGE_ENTER pid={pid}", flush=True)

    if jax.process_index() == 0:
        # THE BUG UNDER TEST (never copy this shape): a collective
        # under host-divergent control flow. Peers never match it, so
        # this host wedges here until the launcher's gang grace reaps
        # it — exactly what `collective-divergence` flags statically.
        sync_global_devices("keystone-diverge-host0-only")

    # give the divergent host time to be firmly inside the unmatched
    # collective before this host's exit starts the gang-grace clock
    if pid != 0:
        time.sleep(1.0)
    print(f"DIVERGE_DONE pid={pid}", flush=True)
    sys.stdout.flush()
    # hard exit, like dryrun_worker's failure path: a normal
    # interpreter exit wedges in the distributed runtime's teardown
    # (the coordinator-client shutdown waits on the peer that is stuck
    # in the collective this test deliberately diverged), and a worker
    # that neither exits nor progresses would defeat the launcher's
    # dead-member detection this test exists to demonstrate
    os._exit(0)


if __name__ == "__main__":
    main()
