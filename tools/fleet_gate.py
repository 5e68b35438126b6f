#!/usr/bin/env python3
"""CI drill for the serving fleet (``bin/ci.sh``): kill one replica.
A CPU gate: it pins ``JAX_PLATFORMS=cpu`` for itself and every replica
it spawns, so it never sends several processes at one chip whatever the
caller exported.

End-to-end, out of process — the production topology at miniature
scale:

1. spawn THREE replica servers as SUBPROCESSES
   (``python -m keystone_tpu.serving.replica``), each a full
   ``ServingPlane`` behind the real-HTTP predict + admin surfaces;
2. register three models with the in-process ``FleetController``
   (canonical-bytes contract: one pickled working copy per model,
   sha256-stamped), solve placement under finite per-replica budgets,
   and admit every copy over ``/admin/admit`` — each replica's
   reported sha must equal the canonical sha (bit-identical
   admission, verified by the controller);
3. front the fleet with the real-HTTP ``FleetRouter`` and drive a
   seeded loadgen trace through it (``HttpServingClient`` — the
   request path is loadgen -> router socket -> replica socket ->
   plane);
4. mid-replay, SIGKILL the replica hosting the most models — no
   drain, no goodbye, a real process death;
5. the reactor tick (``FleetAutoscaler``) must classify the death,
   drop the corpse from the routing membership, re-solve placement
   over the survivors, and re-admit the lost models from canonical
   bytes — sha-verified again on the new hosts;
6. after the window: every model answers 200 through the router, the
   re-admitted copies' shas match the canonical bytes, the p99 of
   served requests stays under the drill floor, and EVERY outcome in
   the replay is classified — zero unclassified damage, zero raw
   errors (the router shields a backend death by spilling; a refusal
   reaches the client as a counted 429/503, never a stack trace).

Exit 0 clean; exit 1 with a named reason otherwise.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ["JAX_PLATFORMS"] = "cpu"

READY_TIMEOUT_S = 240.0
N_REPLICAS = 3
#: name -> (d, k): three models, distinct shapes, one hot
DIMS = {"alpha": (24, 3), "beta": (32, 4), "gamma": (16, 2)}
P99_FLOOR_MS = 500.0


def _fail(procs, reason: str) -> int:
    print(f"fleet gate: FAIL: {reason}", file=sys.stderr)
    for proc in procs:
        if proc.poll() is None:
            proc.kill()
    return 1


def _spawn_replica() -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [sys.executable, "-m", "keystone_tpu.serving.replica",
         "--port", "0", "--max-batch", "16", "--queue-depth", "128"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO, env=env)


def _read_bind_line(proc: subprocess.Popen, deadline: float):
    """The replica prints ``replica on HOST:PORT`` before anything
    else; select-gate the read so a wedged boot fails the gate, not
    the CI wall clock."""
    import select

    while time.monotonic() < deadline:
        readable, _, _ = select.select(
            [proc.stdout], [], [],
            max(0.0, min(1.0, deadline - time.monotonic())))
        if not readable:
            if proc.poll() is not None:
                return None
            continue
        line = proc.stdout.readline()
        if not line:
            return None
        print(f"  replica: {line.rstrip()}")
        m = re.match(r"replica on ([\d.]+):(\d+)", line)
        if m:
            return m.group(1), int(m.group(2))
    return None


def main() -> int:
    import threading

    import jax
    import numpy as np

    from keystone_tpu.nodes.learning.linear import LinearMapEstimator
    from keystone_tpu.observability.metrics import MetricsRegistry
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.serving.fleet import FleetAutoscaler, FleetController
    from keystone_tpu.serving.loadgen import (
        HttpServingClient,
        LoadSpec,
        generate_trace,
        replay,
    )
    from keystone_tpu.serving.router import (
        FleetRouter,
        HttpReplicaClient,
        serve_router,
    )

    reg = MetricsRegistry.get_or_create()
    deaths0 = reg.counter("fleet.replica_deaths_total").value

    # 1. three real replica processes (spawned together: the jax boot
    # cost parallelizes; binds are read one by one afterwards)
    deadline = time.monotonic() + READY_TIMEOUT_S
    procs = [_spawn_replica() for _ in range(N_REPLICAS)]
    clients = []
    for i, proc in enumerate(procs):
        bound = _read_bind_line(proc, deadline)
        if bound is None:
            return _fail(procs, f"replica {i} never printed its bind "
                                "line (boot wedge or crash)")
        host, port = bound
        clients.append(HttpReplicaClient(f"r{i}", host, port,
                                         stats_ttl_s=0.05))
    print(f"fleet gate: {N_REPLICAS} replicas up on ports "
          f"{[c.port for c in clients]}")

    router_server = None
    try:
        # 2. canonical registration + solved placement + sha-verified
        # admission over the admin surface
        router = FleetRouter(clients, spill_queue_depth=8)
        controller = FleetController(router)
        registered = {}
        for seed, (name, (d, k)) in enumerate(sorted(DIMS.items())):
            r = np.random.RandomState(seed)
            X = r.rand(96, d).astype(np.float32)
            Y = r.rand(96, k).astype(np.float32)
            fitted = LinearMapEstimator(lam=1e-3).with_data(
                ArrayDataset.from_numpy(X),
                ArrayDataset.from_numpy(Y)).fit()
            qps = 300.0 if name == "alpha" else 0.0
            registered[name] = controller.register(
                name, fitted,
                jax.ShapeDtypeStruct((d,), np.float32),
                qps=qps, warmup_s=1.0 if qps else 0.0)
        biggest = max(m.charge_nbytes for m in registered.values())
        for client in clients:
            controller.set_budget(client.replica_id, 3.3 * biggest)
        steps = controller.rebalance()
        if not steps:
            return _fail(procs, "initial rebalance applied no steps")
        canonical = {name: m.sha256 for name, m in registered.items()}
        for client in clients:
            for name, sha in client.model_shas().items():
                if sha != canonical[name]:
                    return _fail(
                        procs, f"replica {client.replica_id} hosts "
                               f"{name!r} with sha {sha[:12]} != "
                               f"canonical {canonical[name][:12]}")
        table = router.state()["models"]
        missing = [m for m in DIMS if not table.get(m)]
        if missing:
            return _fail(procs, f"models {missing} unroutable after "
                                "initial placement")
        print(f"fleet gate: placement applied ({len(steps)} steps), "
              f"table {{m: [r...]}} = "
              f"{ {m: table[m] for m in sorted(table)} }")

        # 3. the router front door + the seeded HTTP load window
        router_server = serve_router(router)
        rport = router_server.server_port
        spec = LoadSpec(seed=31, duration_s=3.0, rate_rps=90.0,
                        arrival="poisson",
                        models=tuple(sorted(DIMS)), zipf_s=1.2,
                        sizes=(1, 2, 4))
        trace = generate_trace(spec)
        data = {name: np.random.RandomState(100 + i).rand(
                    8, DIMS[name][0]).astype(np.float32)
                for i, name in enumerate(sorted(DIMS))}

        autoscaler = FleetAutoscaler(controller, sustain_ticks=10 ** 6)
        killed = {}

        def killer():
            time.sleep(1.5)
            count = {}
            for reps in controller.placement.assignments.values():
                for rid in reps:
                    count[rid] = count.get(rid, 0) + 1
            victim = max(sorted(count), key=lambda rid: count[rid])
            idx = next(i for i, c in enumerate(clients)
                       if c.replica_id == victim)
            procs[idx].kill()  # SIGKILL: no drain, no goodbye
            procs[idx].wait()
            killed["victim"] = victim
            # 4. the reactor tick IS the recovery path under test
            try:
                killed["action"] = autoscaler.tick()
            except BaseException as exc:  # noqa: BLE001 - gate verdict
                killed["error"] = f"{type(exc).__name__}: {exc}"

        thread = threading.Thread(target=killer, daemon=True,
                                  name="fleet-gate-killer")
        thread.start()
        report = replay(trace, HttpServingClient("127.0.0.1", rport),
                        lambda m, n: data[m][:n], senders=6,
                        submit_timeout_s=5.0, result_timeout_s=30.0)
        thread.join(timeout=60.0)

        # 5. recovery happened, and it was the reactor that did it
        if "error" in killed:
            return _fail(procs, f"recovery raised {killed['error']}")
        if killed.get("action") != "death":
            return _fail(procs, "reactor tick did not classify the "
                                f"kill as a death "
                                f"(got {killed.get('action')!r})")
        deaths = reg.counter("fleet.replica_deaths_total").value - deaths0
        if deaths != 1:
            return _fail(procs, f"expected exactly 1 counted death, "
                                f"got {deaths:g}")
        victim = killed["victim"]
        if victim in router.replica_ids():
            return _fail(procs, f"dead replica {victim!r} still in "
                                "the routing membership")
        table = router.state()["models"]
        missing = [m for m in DIMS if not table.get(m)]
        if missing:
            return _fail(procs, f"models {missing} unroutable after "
                                "the death — redistribution incomplete")
        # the re-admitted copies are bit-identical to canonical bytes
        for client in clients:
            if client.replica_id == victim:
                continue
            for name, sha in client.model_shas().items():
                if sha != canonical[name]:
                    return _fail(
                        procs, f"post-death copy of {name!r} on "
                               f"{client.replica_id} has sha "
                               f"{sha[:12]} != canonical "
                               f"{canonical[name][:12]} — migration "
                               "broke bit-identity")
        # every model still answers THROUGH the router
        import http.client

        for name in sorted(DIMS):
            payload = json.dumps(
                {"instances": [[0.5] * DIMS[name][0]]}).encode()
            conn = http.client.HTTPConnection("127.0.0.1", rport,
                                              timeout=10)
            conn.request("POST", f"/predict/{name}", body=payload)
            rsp = conn.getresponse()
            body = rsp.read()
            conn.close()
            if rsp.status != 200:
                return _fail(procs, f"post-death probe for {name!r} "
                                    f"answered {rsp.status}: "
                                    f"{body[:120].decode(errors='replace')}")

        # 6. the window's verdict: classified or served, nothing else
        oc = report.outcomes
        if oc["unclassified"]:
            return _fail(procs, f"{oc['unclassified']} UNCLASSIFIED "
                                f"outcome(s): {report.errors[:4]}")
        if oc["error"]:
            return _fail(procs, f"{oc['error']} raw error(s) leaked "
                                "through the router during the death "
                                f"window: {report.errors[:4]}")
        if oc["ok"] == 0:
            return _fail(procs, "no request succeeded — the fleet "
                                "never served")
        p99 = report.p99_ms()
        if p99 > P99_FLOOR_MS:
            return _fail(procs, f"p99 {p99:.1f}ms over the "
                                f"{P99_FLOOR_MS:.0f}ms drill floor")
        refused = oc["rejected"] + oc["warming"] + oc["not_admitted"]
        print(f"fleet gate: PASS (killed {victim}, "
              f"{oc['ok']} served, {refused} classified refusal(s), "
              f"p99 {p99:.1f}ms, re-placement sha-verified)")
        return 0
    finally:
        if router_server is not None:
            router_server.shutdown()
        for proc in procs:
            if proc.poll() is None:
                proc.kill()


if __name__ == "__main__":
    sys.exit(main())
