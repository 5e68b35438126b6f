#!/usr/bin/env python3
"""CI smoke gate for the serving plane (``bin/ci.sh``). A CPU gate: it
pins ``JAX_PLATFORMS=cpu`` for itself and the server it spawns, so it
never sends two processes at one chip whatever the caller exported.

End-to-end, out of process — the exact deployment shape:

1. fit two small pipelines, save them with ``utils.checkpoint.
   save_pipeline`` (the artifact format ``serve`` loads);
2. start ``python -m keystone_tpu serve`` as a SUBPROCESS on an
   ephemeral port (the server binds before admitting, so ``/healthz``
   observably reports 503 warming during the warmup compiles);
3. wait for readiness (``/healthz`` 200) with a hard timeout — a hung
   warmup fails the gate, not the CI wall clock;
4. drive requests across >= 2 request shapes (different buckets) and
   BOTH models, checking response shapes AND that every predict
   response carries a distinct non-empty ``X-Keystone-Trace`` header
   (the PR 16 request-path handle round-trips end to end);
5. scrape ``/metrics`` and assert ``keystone_compile_unexpected_total``
   is 0 — the server arms the warmup fence after admission, so ANY
   steady-state recompile shows up here — and that the serving
   counters saw the traffic;
6. scrape ``/slo`` and assert a clean run reports availability 1.0
   with zero violations;
7. IN PROCESS (FaultPlan is process-global, so the straggler cannot be
   installed in the subprocess server): run a tight-policy plane under
   a ``serve.dispatch`` straggler injection and assert the SLO trips —
   a violation is recorded naming the model and the violated window,
   and its post-mortem artifact exists on disk embedding the exemplar
   span trees.

Exit 0 clean; exit 1 with a named reason otherwise.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ["JAX_PLATFORMS"] = "cpu"

READY_TIMEOUT_S = 240.0
DIMS = {"alpha": (24, 3), "beta": (32, 4)}


def _fail(proc, reason: str) -> int:
    print(f"serving gate: FAIL: {reason}", file=sys.stderr)
    if proc is not None:
        proc.terminate()
        try:
            out = proc.stdout.read() if proc.stdout else ""
        except Exception:
            out = ""
        if out:
            print(f"server output:\n{out}", file=sys.stderr)
    return 1


def _get(url: str, timeout: float = 5.0):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as rsp:
            return rsp.status, rsp.read()
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read()


def main() -> int:
    # 0. the static precondition, BEFORE any jax/device work: the
    # request path the rest of this gate is about to exercise must be
    # statically clean — every call reachable from a @hotpath serving
    # entry point free of unallowlisted blocking/host-sync/IO/alloc
    # hazards, and every @published_by field on the swap discipline.
    # Cheap (AST-only, ~1s) and it fails the gate with named chains
    # instead of a mystery latency regression three phases later.
    import time as _time

    from keystone_tpu.analysis.hotpath import (
        HOTPATH_SCAN_BUDGET_S,
        scan_package,
    )

    t0 = _time.perf_counter()
    hotpath_hits = scan_package(os.path.join(REPO, "keystone_tpu"))
    scan_s = _time.perf_counter() - t0
    if hotpath_hits:
        for hit in hotpath_hits:
            print(f"  {hit['file']}:{hit['lineno']}: {hit['code']}: "
                  f"{hit['message']}", file=sys.stderr)
        return _fail(None, f"{len(hotpath_hits)} hot-path/publication "
                           "diagnostic(s) — fix or allowlist before "
                           "driving load")
    if scan_s > HOTPATH_SCAN_BUDGET_S:
        return _fail(None, f"hot-path scan took {scan_s:.2f}s > "
                           f"{HOTPATH_SCAN_BUDGET_S:.0f}s budget")
    print(f"serving gate: hot-path scan clean in {scan_s:.2f}s "
          f"(budget {HOTPATH_SCAN_BUDGET_S:.0f}s)")

    import numpy as np

    from keystone_tpu.nodes.learning.linear import LinearMapEstimator
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.utils.checkpoint import save_pipeline

    tmp = tempfile.mkdtemp(prefix="keystone-serving-gate-")
    specs = []
    for name, (d, k) in DIMS.items():
        r = np.random.RandomState(d)
        X = r.rand(96, d).astype(np.float32)
        Y = r.rand(96, k).astype(np.float32)
        fitted = LinearMapEstimator(lam=1e-3).with_data(
            ArrayDataset.from_numpy(X), ArrayDataset.from_numpy(Y)).fit()
        path = os.path.join(tmp, f"{name}.pkl")
        save_pipeline(fitted, path)
        specs.append(f"{name}={path}@{d}:float32")

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "keystone_tpu", "serve", *specs,
         "--port", "0", "--hbm-budget", "64MiB", "--max-batch", "16",
         "--weight-dtype", "bf16"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO, env=env)
    try:
        # 1. the bind line prints BEFORE admission. readline() alone
        # would block past the deadline if the server wedges before
        # its first line (jax init hang), so the wait is select-gated:
        # the hard timeout holds from the first byte, not the second.
        import select

        deadline = time.monotonic() + READY_TIMEOUT_S
        port = None
        while time.monotonic() < deadline:
            readable, _, _ = select.select(
                [proc.stdout], [], [],
                max(0.0, min(1.0, deadline - time.monotonic())))
            if not readable:
                if proc.poll() is not None:
                    return _fail(proc, "server exited before binding")
                continue
            line = proc.stdout.readline()
            if not line:
                return _fail(proc, "server exited before binding")
            print(f"  server: {line.rstrip()}")
            if line.startswith("serving on "):
                port = int(line.rsplit(":", 1)[1])
                break
        if port is None:
            return _fail(proc, "no 'serving on' line before timeout")
        base = f"http://127.0.0.1:{port}"

        # 2. /healthz is a REAL readiness gate: poll until 200, with
        # the not-ready phase (503 warming) logged when observed
        saw_warming = False
        while True:
            if time.monotonic() > deadline:
                return _fail(
                    proc, f"/healthz not ready in {READY_TIMEOUT_S:.0f}s")
            try:
                status, body = _get(base + "/healthz", timeout=2.0)
            except (urllib.error.URLError, OSError):
                time.sleep(0.2)
                continue
            if status == 503:
                saw_warming = True
                time.sleep(0.2)
                continue
            if status == 200:
                break
            return _fail(proc, f"/healthz returned {status}")
        print(f"serving gate: ready on port {port} "
              f"(warming observed: {saw_warming})")

        # 3. drive both models across >= 2 request shapes (buckets);
        # every response must echo a distinct trace id header
        sent = 0
        trace_ids = set()
        for name, (d, k) in DIMS.items():
            for n in (1, 3, 7, 11):  # buckets 8 and 16 on the sim mesh
                payload = json.dumps(
                    {"instances": [[0.5] * d] * n}).encode()
                req = urllib.request.Request(
                    f"{base}/predict/{name}", data=payload,
                    headers={"Content-Type": "application/json"})
                for _ in range(3):
                    with urllib.request.urlopen(req, timeout=30) as rsp:
                        out = json.loads(rsp.read())
                        trace_id = rsp.headers.get("X-Keystone-Trace")
                    preds = out.get("predictions")
                    if (out.get("rows") != n or len(preds) != n
                            or len(preds[0]) != k):
                        return _fail(
                            proc, f"bad predict response for {name} "
                                  f"n={n}: rows={out.get('rows')}")
                    if not trace_id:
                        return _fail(
                            proc, f"predict response for {name} n={n} "
                                  "carried no X-Keystone-Trace header")
                    trace_ids.add(trace_id)
                    sent += 1
        if len(trace_ids) != sent:
            return _fail(
                proc, f"trace ids not distinct: {len(trace_ids)} unique "
                      f"across {sent} requests")
        print(f"serving gate: {sent} requests served across "
              f"{len(DIMS)} models and 2 buckets "
              f"({len(trace_ids)} distinct trace ids)")

        # 4. the fence verdict: zero steady-state recompiles
        status, body = _get(base + "/metrics")
        if status != 200:
            return _fail(proc, f"/metrics returned {status}")
        metrics = {}
        for line in body.decode().splitlines():
            if line.startswith("#") or " " not in line:
                continue
            key, value = line.rsplit(" ", 1)
            try:
                metrics[key] = float(value)
            except ValueError:
                continue
        # counters gain a "_total" suffix in the exposition
        # (metrics.to_prometheus), so the dotted catalogue name
        # compile.unexpected_total scrapes as ..._total_total
        unexpected = metrics.get(
            "keystone_compile_unexpected_total_total", 0.0)
        if unexpected:
            return _fail(
                proc, f"{unexpected:.0f} fenced steady-state "
                      "recompile(s) — pad-to-bucket warmup missed a "
                      "program")
        served = metrics.get("keystone_serving_requests_total_total", 0.0)
        if served < sent:
            return _fail(
                proc, f"serving.requests_total={served:.0f} < "
                      f"{sent} requests the gate sent")

        # 5. a clean run's SLO surface: availability 1.0, no violations
        status, body = _get(base + "/slo")
        if status != 200:
            return _fail(proc, f"/slo returned {status}")
        slo = json.loads(body)
        if slo.get("availability") != 1.0:
            return _fail(
                proc, f"clean run reports availability "
                      f"{slo.get('availability')} != 1.0")
        if slo.get("violations"):
            return _fail(
                proc, f"clean run reports {len(slo['violations'])} SLO "
                      "violation(s)")
        print(f"serving gate: /slo clean (availability=1.0, "
              f"burn_rate={slo.get('burn_rate')})")
        print(f"serving gate: PASS subprocess phase "
              f"(requests={served:.0f}, unexpected recompiles=0)")
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()

    # 6. the straggler phase, in process: inject a serve.dispatch
    # straggler under a tight policy and require the SLO plane to do
    # its whole job — trip, name the model and window, write the
    # post-mortem with exemplars embedded
    return _straggler_phase()


def _straggler_phase() -> int:
    import jax

    import numpy as np

    from keystone_tpu.nodes.learning.linear import LinearMapEstimator
    from keystone_tpu.observability.slo import SloPolicy
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.resilience.faults import FaultPlan
    from keystone_tpu.serving import ServingPlane

    d, k = 16, 3
    r = np.random.RandomState(7)
    X = r.rand(96, d).astype(np.float32)
    Y = r.rand(96, k).astype(np.float32)
    fitted = LinearMapEstimator(lam=1e-3).with_data(
        ArrayDataset.from_numpy(X), ArrayDataset.from_numpy(Y)).fit()
    policy = SloPolicy(latency_threshold_ms=50.0,
                       availability_target=0.95, window=8, min_count=8)
    plane = ServingPlane(max_batch=16, slo_policy=policy)
    plane.start()
    try:
        plane.admit("straggle", fitted,
                    jax.ShapeDtypeStruct((d,), np.float32))
        plane.predict("straggle", X[:4])  # clean warm request
        with FaultPlan(0) as fp:
            fp.add("serve.dispatch", kind="straggler", delay_s=0.2)
            for _ in range(10):
                plane.predict("straggle", X[:4], timeout_s=60.0)
        violations = plane.slo.state()["violations"]
        if not violations:
            print("serving gate: FAIL: injected serve.dispatch "
                  "straggler did not trip the SLO", file=sys.stderr)
            return 1
        v = violations[0]
        if v.get("model") != "straggle" or "window" not in v:
            print(f"serving gate: FAIL: violation names neither model "
                  f"nor window: {v}", file=sys.stderr)
            return 1
        pm_path = v.get("postmortem")
        if not pm_path or not os.path.exists(pm_path):
            print(f"serving gate: FAIL: SLO violation wrote no "
                  f"post-mortem artifact ({pm_path!r})", file=sys.stderr)
            return 1
        with open(pm_path) as f:
            pm = json.load(f)
        ctx = pm.get("context", {})
        if ctx.get("model") != "straggle":
            print("serving gate: FAIL: post-mortem context does not "
                  f"name the model: {ctx.get('model')!r}",
                  file=sys.stderr)
            return 1
        if not ctx.get("window", {}).get("count"):
            print("serving gate: FAIL: post-mortem context does not "
                  "carry the violated window", file=sys.stderr)
            return 1
        exemplars = ctx.get("exemplars") or []
        if not any(e.get("model") == "straggle" and e.get("phases_ms")
                   for e in exemplars):
            print("serving gate: FAIL: post-mortem embeds no exemplar "
                  "span tree for the slow model", file=sys.stderr)
            return 1
        print(f"serving gate: PASS (straggler tripped SLO: "
              f"availability={v['window']['availability']}, "
              f"post-mortem={os.path.basename(pm_path)}, "
              f"{len(exemplars)} exemplars)")
        return 0
    finally:
        plane.close()


if __name__ == "__main__":
    sys.exit(main())
