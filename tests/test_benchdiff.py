"""The statistical bench-regression gate
(``observability/benchdiff.py`` / ``python -m keystone_tpu benchdiff``).

Synthetic-artifact tests pin the band model (median consecutive swing
x 1.5, floored at 8%), the exit codes (0 in-band/improved, 1 usage or
cross-host refusal, 2 regression), the scaled-metric exclusion, and
the cross-host refusal; the acceptance test runs the gate over the
repo's REAL ``BENCH_r03.json`` / ``BENCH_r05.json`` and requires the
76-85k e2e delta to classify as in-band noise (exit 0) — the tool form
of PERFORMANCE.md's hand argument.
"""
import json
import os

import pytest

from keystone_tpu.observability.benchdiff import (
    DEFAULT_BAND,
    compare,
    discover_history,
    load_artifact,
    lower_is_better,
    main as benchdiff_main,
    noise_band,
)



def _artifact(path, n, metrics, meta=None, scaled=()):
    """Write a driver-shaped BENCH artifact: metric lines in the tail,
    the flagship-style summary as ``parsed``."""
    lines = []
    if meta is not None:
        lines.append(json.dumps({"bench_meta": meta}))
    for name, value in metrics.items():
        line = {"metric": name, "value": value, "unit": "u",
                "vs_baseline": 1.0}
        if name in scaled:
            line["scaled"] = 0.5
        lines.append(json.dumps(line))
    first = next(iter(metrics))
    parsed = {"metric": first, "value": metrics[first], "unit": "u",
              "vs_baseline": 1.0, "summary": True}
    blob = {"n": n, "cmd": "bench", "rc": 0,
            "tail": "\n".join(lines) + "\n", "parsed": parsed}
    path.write_text(json.dumps(blob))
    return path


def _history(tmp_path, values_per_round, metric="widgets_per_sec",
             meta=None):
    paths = []
    for i, v in enumerate(values_per_round, start=1):
        paths.append(_artifact(tmp_path / f"BENCH_r{i:02d}.json", i,
                               {metric: v}, meta=meta))
    return paths


# -- artifact parsing --------------------------------------------------------

def test_load_artifact_reads_tail_lines_meta_and_parsed(tmp_path):
    meta = {"hostname": "hostA", "device_kind": "cpu"}
    p = _artifact(tmp_path / "BENCH_r01.json", 1,
                  {"widgets_per_sec": 100.0, "gadget_test_error": 0.1},
                  meta=meta, scaled=("gadget_test_error",))
    art = load_artifact(str(p))
    assert art.value("widgets_per_sec") == 100.0
    assert not art.scaled("widgets_per_sec")
    assert art.scaled("gadget_test_error")
    assert art.meta == meta
    assert art.round_n == 1


def test_load_artifact_backfills_from_parsed_summary(tmp_path):
    """Metrics whose lines scrolled out of the bounded tail survive via
    the parsed summary's extra keys (the real r03 artifact's shape)."""
    blob = {"n": 3, "rc": 0, "tail": "not json\n",
            "parsed": {"metric": "flagship_per_sec", "value": 5.0,
                       "unit": "u", "vs_baseline": 1.0, "summary": True,
                       "other_images_per_sec_per_chip": 7.0,
                       "some_test_error": 0.2,
                       "timing_spread": 0.01}}
    p = tmp_path / "BENCH_r03.json"
    p.write_text(json.dumps(blob))
    art = load_artifact(str(p))
    assert art.value("flagship_per_sec") == 5.0
    assert art.value("other_images_per_sec_per_chip") == 7.0
    assert art.value("some_test_error") == 0.2
    assert art.value("timing_spread") is None  # metadata, not a metric


def test_discover_history_excludes_current(tmp_path):
    paths = _history(tmp_path, [100, 101, 99])
    hist = discover_history(str(paths[-1]))
    assert [a.round_n for a in hist] == [1, 2]  # r03 (current) excluded


# -- band model --------------------------------------------------------------

def test_noise_band_floor_without_history(tmp_path):
    band, n = noise_band("widgets_per_sec", [])
    assert band == DEFAULT_BAND and n == 0


def test_noise_band_median_swing(tmp_path):
    # swings: 10%, ~0.9%, ~0.9% -> median 0.9% -> floor wins
    arts = [load_artifact(str(p)) for p in
            _history(tmp_path, [100.0, 110.0, 111.0, 110.0])]
    band, n = noise_band("widgets_per_sec", arts)
    assert band == DEFAULT_BAND and n == 4
    # swings: 10%, 12% -> median 11% -> 1.5x = 16.5% > floor
    arts = [load_artifact(str(p)) for p in
            _history(tmp_path, [100.0, 110.0, 96.8])]
    band, _ = noise_band("widgets_per_sec", arts[:3])
    assert band > DEFAULT_BAND


def test_direction_markers():
    assert lower_is_better("cifar_randompatch_test_error")
    assert lower_is_better("ingest_stall_share")
    assert not lower_is_better("voc_map")
    assert not lower_is_better("widgets_per_sec")
    # the PR 10 numerics-health keys are failure/cost measures
    assert lower_is_better("streamed_nan_total")
    assert lower_is_better("solver_breakdown_total")
    assert lower_is_better("numerics_drift_score")
    assert lower_is_better("numerics_overhead_share")
    # serving latency (PR 15): landed BEFORE the first serving bench
    # round, the PR 9 _bytes lesson
    assert lower_is_better("serve_p50_ms")
    assert lower_is_better("serve_p99_ms")
    assert lower_is_better("serve_p99")
    assert lower_is_better("serving_request_latency")
    # throughput: _qps is higher-better and WINS over any lower-better
    # substring sharing the name
    assert not lower_is_better("serve_qps_per_chip")
    assert not lower_is_better("p99_bounded_qps")
    assert not lower_is_better("stall_free_qps")
    # the request-path plane (PR 16): phase shares of the request wall
    # and budget burn are costs; availability and batch fill are
    # utilization/goodness fractions whose markers WIN over any
    # lower-better substring in the same name
    assert lower_is_better("serve_queue_wait_share")
    assert lower_is_better("serve_dispatch_share")
    assert lower_is_better("serving_trace_overhead_share")
    assert lower_is_better("serve_error_budget_burn_rate")
    assert not lower_is_better("serve_availability")
    assert not lower_is_better("serve_batch_fill")
    # "availability" outranks a co-occurring lower-better marker
    assert not lower_is_better("availability_error_window")


def test_serving_latency_regression_fixture(tmp_path, capsys):
    """The serving direction markers as an end-to-end synthetic
    fixture: a p99 that RISES 30% exits 2 (regressed), a qps that
    DROPS 30% exits 2, and a qps that rises classifies improved —
    pinned before BENCH_r08 records the first serving baseline."""
    base = _artifact(tmp_path / "BENCH_r01.json", 1,
                     {"serve_qps_per_chip": 1000.0, "serve_p99_ms": 8.0})
    worse = _artifact(tmp_path / "BENCH_r02.json", 2,
                      {"serve_qps_per_chip": 1000.0,
                       "serve_p99_ms": 10.4})
    rc = benchdiff_main([str(base), str(worse)])
    out = capsys.readouterr().out
    assert rc == 2 and "regressed" in out

    slow = _artifact(tmp_path / "BENCH_r03.json", 3,
                     {"serve_qps_per_chip": 700.0, "serve_p99_ms": 8.0})
    rc = benchdiff_main([str(base), str(slow)])
    out = capsys.readouterr().out
    assert rc == 2
    assert any("serve_qps_per_chip" in line and "regressed" in line
               for line in out.splitlines())

    fast = _artifact(tmp_path / "BENCH_r04.json", 4,
                     {"serve_qps_per_chip": 1400.0, "serve_p99_ms": 8.0})
    rc = benchdiff_main([str(base), str(fast)])
    out = capsys.readouterr().out
    assert rc == 0
    assert any("serve_qps_per_chip" in line and "improved" in line
               for line in out.splitlines())


def test_overhead_share_bands_absolutely(tmp_path):
    """A signed share hovering at ~0 cannot use percent-of-base bands:
    a noise flip from -0.037 to +0.01 is a >100% relative move, and a
    base of exactly 0.0 is a meaningful value, not a new baseline."""
    from keystone_tpu.observability.benchdiff import (
        ABSOLUTE_BAND_FLOOR,
        classify,
    )

    m = "numerics_overhead_share"
    band, n = noise_band(m, [])
    assert band == ABSOLUTE_BAND_FLOOR and n == 0
    # zero base classifies normally (absolute delta), never new-baseline
    assert classify(m, 0.0, 0.01, band) == ("in-band", -0.01)
    # a genuine overhead jump past the 2-point bar regresses
    cls, delta = classify(m, 0.0, 0.1, band)
    assert cls == "regressed" and delta == pytest.approx(-0.1)
    # the band learns machine noise in ABSOLUTE units: swings of
    # 4/3 points -> median 3.5 x 1.5 = 5.25 points, so the -0.03 ->
    # +0.01 flip that a relative band called a 127% regression is noise
    arts = [load_artifact(str(p)) for p in
            _history(tmp_path, [-0.03, 0.01, -0.02], metric=m)]
    band, _ = noise_band(m, arts)
    assert band == pytest.approx(1.5 * 0.035)
    assert classify(m, -0.03, 0.01, band)[0] == "in-band"
    # the serving-trace share (PR 16) rides the same absolute banding
    # via the shared "overhead_share" marker
    band16, _ = noise_band("serving_trace_overhead_share", [])
    assert band16 == ABSOLUTE_BAND_FLOOR
    assert classify("serving_trace_overhead_share",
                    0.0, 0.01, band16) == ("in-band", -0.01)


def test_slo_plane_regression_fixtures(tmp_path, capsys):
    """The PR 16 direction markers end to end, pinned BEFORE BENCH_r09
    records the first request-path baseline (the PR 15 `_p99`/`_qps`
    discipline): availability that DROPS regresses, availability that
    rises improves, and a queue-wait share that GROWS (backpressure
    eating the wall) regresses."""
    base = _artifact(tmp_path / "BENCH_r01.json", 1,
                     {"serve_availability": 0.999,
                      "serve_queue_wait_share": 0.2})
    outage = _artifact(tmp_path / "BENCH_r02.json", 2,
                       {"serve_availability": 0.88,
                        "serve_queue_wait_share": 0.2})
    rc = benchdiff_main([str(base), str(outage)])
    out = capsys.readouterr().out
    assert rc == 2
    assert any("serve_availability" in line and "regressed" in line
               for line in out.splitlines())

    # a fresh dir: no learned history, so the default 8% band applies
    # and the +13.5% recovery classifies as a directional improvement
    rec = tmp_path / "rec"
    rec.mkdir()
    rec_base = _artifact(rec / "BENCH_r01.json", 1,
                         {"serve_availability": 0.88})
    recovered = _artifact(rec / "BENCH_r02.json", 2,
                          {"serve_availability": 0.999})
    rc = benchdiff_main([str(rec_base), str(recovered)])
    out = capsys.readouterr().out
    assert rc == 0
    assert any("serve_availability" in line and "improved" in line
               for line in out.splitlines())

    congested = _artifact(tmp_path / "BENCH_r04.json", 4,
                          {"serve_availability": 0.999,
                           "serve_queue_wait_share": 0.31})
    rc = benchdiff_main([str(base), str(congested)])
    out = capsys.readouterr().out
    assert rc == 2
    assert any("serve_queue_wait_share" in line and "regressed" in line
               for line in out.splitlines())


# -- classification + exit codes ---------------------------------------------

def test_in_band_noise_exits_zero(tmp_path, capsys):
    paths = _history(tmp_path, [100.0, 103.0, 98.0, 102.0])
    rc = benchdiff_main([str(paths[0]), str(paths[-1])])
    out = capsys.readouterr().out
    assert rc == 0
    assert "in-band" in out and "regressed" not in out.split("\n")[1]


def test_regression_beyond_band_exits_two(tmp_path, capsys):
    """The synthetic >band regression fixture: tight history, then a
    30% drop — exit 2 and the metric is named regressed."""
    paths = _history(tmp_path, [100.0, 101.0, 99.5, 70.0])
    rc = benchdiff_main([str(paths[-2]), str(paths[-1])])
    out = capsys.readouterr().out
    assert rc == 2
    assert "regressed" in out


def test_error_metric_direction_is_inverted(tmp_path, capsys):
    paths = _history(tmp_path, [0.10, 0.101, 0.099, 0.20],
                     metric="model_test_error")
    rc = benchdiff_main([str(paths[-2]), str(paths[-1])])
    assert rc == 2  # error DOUBLED: regression even though value rose
    paths2 = _history(tmp_path, [0.20, 0.201, 0.199, 0.10],
                      metric="model_test_error")
    assert benchdiff_main([str(paths2[-2]), str(paths2[-1])]) == 0
    assert "improved" in capsys.readouterr().out


def test_scaled_metrics_are_excluded(tmp_path, capsys):
    base = _artifact(tmp_path / "BENCH_r01.json", 1,
                     {"widgets_per_sec": 100.0})
    cur = _artifact(tmp_path / "BENCH_r02.json", 2,
                    {"widgets_per_sec": 50.0}, scaled=("widgets_per_sec",))
    rc = benchdiff_main([str(base), str(cur)])
    out = capsys.readouterr().out
    assert rc == 0  # a 50% drop measured SHRUNK is not a regression
    assert "scaled (excluded)" in out


def test_absent_and_new_metrics_are_visible_not_fatal(tmp_path, capsys):
    base = _artifact(tmp_path / "BENCH_r01.json", 1,
                     {"widgets_per_sec": 100.0, "old_per_sec": 5.0})
    cur = _artifact(tmp_path / "BENCH_r02.json", 2,
                    {"widgets_per_sec": 101.0, "fresh_per_sec": 9.0})
    rc = benchdiff_main([str(base), str(cur)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "absent" in out and "new" in out


def test_cross_host_refused_without_force(tmp_path, capsys):
    base = _artifact(tmp_path / "BENCH_r01.json", 1,
                     {"widgets_per_sec": 100.0},
                     meta={"hostname": "hostA"})
    cur = _artifact(tmp_path / "BENCH_r02.json", 2,
                    {"widgets_per_sec": 101.0},
                    meta={"hostname": "hostB"})
    rc = benchdiff_main([str(base), str(cur)])
    err = capsys.readouterr().err
    assert rc == 1
    assert "cross-host" in err and "--force" in err
    assert benchdiff_main([str(base), str(cur), "--force"]) == 0


def test_legacy_artifacts_without_meta_compare_with_note(tmp_path, capsys):
    paths = _history(tmp_path, [100.0, 101.0])
    rc = benchdiff_main([str(paths[0]), str(paths[1])])
    captured = capsys.readouterr()
    assert rc == 0
    assert "bench_meta" in captured.err  # the unverified-host note


def test_usage_errors_exit_one(tmp_path, capsys):
    assert benchdiff_main([]) == 1
    assert benchdiff_main(["--band"]) == 1
    assert benchdiff_main([str(tmp_path / "missing1.json"),
                           str(tmp_path / "missing2.json")]) == 1


def test_band_override(tmp_path):
    paths = _history(tmp_path, [100.0, 94.0])
    # 6% drop: in-band at the default 8% floor, regressed at --band 0.02
    assert benchdiff_main([str(paths[0]), str(paths[1])]) == 0
    assert benchdiff_main([str(paths[0]), str(paths[1]),
                           "--band", "0.02"]) == 2


# -- acceptance: the recorded r01-r05 history ---------------------------------

#: What the driver recorded for rounds 1-5 (the metrics this gate was
#: argued over), as fixtures: the r01/r02 files named a platform that is
#: gone and were deleted, and a test must not depend on which old
#: records the repo still carries.
_RECORDED = {
    1: {"cifar_randompatch_images_per_sec_per_chip": 61086.7},
    2: {"cifar_randompatch_images_per_sec_per_chip": 114318.9,
        "block_ls_solver_tflops": 25.81,
        "imagenet_rehearsal_images_per_sec_per_chip": 204.68,
        "cifar_e2e_images_per_sec_per_chip": 78604.1},
    3: {"cifar_randompatch_images_per_sec_per_chip": 113795.8,
        "block_ls_solver_tflops": 38.82,
        "cifar_randompatch_test_error": 0.2676,
        "cifar_e2e_images_per_sec_per_chip": 85360.2,
        "imagenet_rehearsal_images_per_sec_per_chip": 206.18},
    4: {"cifar_randompatch_images_per_sec_per_chip": 113979.9,
        "block_ls_solver_tflops": 38.17,
        "cifar_randompatch_test_error": 0.2676},
    5: {"cifar_e2e_images_per_sec_per_chip": 76198.1,
        "cifar_randompatch_images_per_sec_per_chip": 115022.8,
        "block_ls_solver_tflops": 40.05,
        "cifar_randompatch_test_error": 0.2676,
        "imagenet_rehearsal_images_per_sec_per_chip": 579.47},
}


def _recorded_history(tmp_path):
    return {n: _artifact(tmp_path / f"BENCH_r{n:02d}.json", n, metrics)
            for n, metrics in _RECORDED.items()}


def test_recorded_r03_vs_r05_e2e_delta_is_in_band(tmp_path, capsys):
    """The PERFORMANCE.md hand argument as an exit code: the 85.4k ->
    76.2k e2e delta (-10.7%) sits inside the band derived from the
    metric's own run-to-run history (r02 -> r03), so the gate exits 0
    and labels it in-band — and the genuinely improved imagenet number
    is not noise."""
    paths = _recorded_history(tmp_path)
    rc = benchdiff_main([str(paths[3]), str(paths[5])])
    out = capsys.readouterr().out
    assert rc == 0
    e2e_row = next(line for line in out.splitlines()
                   if line.startswith("cifar_e2e_images_per_sec_per_chip"))
    assert "in-band" in e2e_row
    imagenet_row = next(
        line for line in out.splitlines()
        if line.startswith("imagenet_rehearsal_images_per_sec_per_chip"))
    assert "improved" in imagenet_row


def test_recorded_artifacts_compare_api(tmp_path):
    paths = _recorded_history(tmp_path)
    base = load_artifact(str(paths[3]))
    cur = load_artifact(str(paths[5]))
    rows = compare(base, cur, discover_history(str(paths[5])))
    by_metric = {r["metric"]: r for r in rows}
    assert by_metric["cifar_e2e_images_per_sec_per_chip"][
        "classification"] == "in-band"
    assert not any(r["classification"] == "regressed" for r in rows)
    # without the r02 point the band falls back to its 8% floor and the
    # same delta reads as a regression: the history is what decides
    os.remove(paths[2])
    rows = compare(base, cur, discover_history(str(paths[5])))
    assert {r["metric"]: r for r in rows}[
        "cifar_e2e_images_per_sec_per_chip"]["classification"] == "regressed"
