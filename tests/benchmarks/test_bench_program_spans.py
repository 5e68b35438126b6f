"""The program's own spans as the benchmark reads them: on both clocks
(a CPU profiler capture of a tiny fit against the flight recorder's ring,
mapped through the harness's ``bench:fit`` anchors), PR 24's seven
readers on a hand-built run whose split is known exactly (four on the
trace's clock, ``benchmarks/layers/_program_spans.py``; three on the
host's, ``_ring_spans.py``, and so every cell's), and the cell's CPU
rehearsal with the spans of every fit checked and the blocking sync
forbidden.
"""
import json
import os
import statistics
import subprocess
import sys

import numpy as np
import pytest

import manifest_checks
from benchmarks import xplane
from benchmarks.harness import Run, load_module
from benchmarks.layers import _program_spans
from benchmarks.spans import Spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MANIFEST = manifest_checks.load_manifest()

NEW = ["optimize_host_s.refit", "dispatch_host_s.refit", "host_wait_s.refit",
       "idle_host_busy_s.refit", "idle_host_waiting_s.refit", "h2d_mb.refit",
       "span_coverage_pct.refit"]
#: host seconds and bytes, on the host's clock: no trace, no ten fits
HOST = ["optimize_host_s.refit", "host_wait_s.refit", "h2d_mb.refit"]
#: the split of the device's idle time: the trace's clock
TRACE_CLOCK = [name for name in NEW if name not in HOST]


def make_run(tmp_path, trace=True):
    said = []
    run = Run(cell={"name": "t"}, cfg={}, traffic={}, seed=0, seconds=1.0,
              trace=trace, rehearsal=True, control=False,
              workdir=str(tmp_path), say=said.append, spans=Spans())
    run.said = said
    return run


def manifest_holds(manifest):
    """PR 24's seven metrics are listed, in their order among themselves,
    each with a reader, on ``mnist_refit`` and moving its rate."""
    manifest_checks.per_layer_is_held(
        manifest, NEW, "mnist_refit", moves="refit_items_per_s")


def test_the_manifest_lists_the_seven_readers_in_their_order():
    manifest_holds(MANIFEST)
    # the idle split wants ten fits a window (``MIN_FITS``): the cell of
    # 180 first, and behind it the cells whose windows hold as many
    for name in TRACE_CLOCK:
        listed = manifest_checks.named(MANIFEST["per_layer"], name)["workloads"]
        assert listed[0] == "mnist_refit" and len(set(listed)) == len(listed)
        assert set(listed) <= {c["name"] for c in MANIFEST["workloads"]}


# -- both clocks ---------------------------------------------------------------

def tiny_fit(seed):
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.pipelines.images.mnist.random_fft import (
        MnistRandomFFTConfig, run)

    rng = np.random.RandomState(seed)
    parts = []
    for rows in (256, 64):
        parts.append(LabeledData(
            data=ArrayDataset.from_numpy(
                rng.rand(rows, 784).astype(np.float32)),
            labels=ArrayDataset.from_numpy(
                rng.randint(0, 10, rows).astype(np.int32))))
    return run(MnistRandomFFTConfig(num_ffts=2, block_size=512, lam=1e-2),
               train=parts[0], test=parts[1])


#: What the two clocks are held to, nanoseconds. A property of the
#: MAPPING is held tightly: one offset serves every span of a capture
#: and a duration is the same on both clocks, so the typical span agrees
#: to a tenth of a millisecond (it reads 3 to 5 us and 1 us here), and no
#: trace interval lies inside its ring span by that much, because the
#: program opens its annotation before it reads its clock and closes it
#: after (``timeline._OpenSpan``): only a wrong offset or unit could.
TIGHT_NS = 1e5
#: What SCHEDULING does is held by a limit: a thread taken off its core
#: between an annotation's edge and the clock read beside it widens that
#: one span's trace interval, or moves that one anchor, by the time it
#: was off, and nothing in the mapping can shorten that. Idle, the worst
#: span of 93 read 0.02 to 0.25 ms; with twelve busy processes on this
#: machine's eight cores one span in one capture of twelve read 64 ms;
#: tier-1's six loaded workers passed the old limit of 1 ms at PR 47. A
#: second still fails a wrong unit or an offset from another capture, and
#: no more than a tenth of the spans may be over a millisecond at all.
LOOSE_NS = 1e9
MANY_NS = 1e6


def test_a_capture_holds_the_program_spans_on_the_trace_clock(tmp_path):
    """Any profiler capture shows the program's spans as ``ks:<cat>:
    <name>`` on the host plane, and each agrees with its ring span mapped
    through the ``bench:fit`` anchors: the mapping tightly, what the
    scheduler can do to a single span or anchor under a limit."""
    import jax

    from keystone_tpu.observability.timeline import flight_recorder

    tiny_fit(0)  # compiles stay out of the capture
    flight_recorder().clear()
    run = make_run(tmp_path)
    run.start_trace()
    try:
        with run.spans.span("window"):
            for seed in (1, 2, 3):
                with run.spans.span("fit"):
                    tiny_fit(seed)
    finally:
        jax.profiler.stop_trace()
    run.trace_data = xplane.load(run._trace_dir)
    captured = xplane.load(run._trace_dir, span_prefix="ks:").spans
    offset, spread, fits = _program_spans.anchors(run)
    # of three anchors the spread is their whole range: one fit's anchor
    # moved by the scheduler is all of it (the readers, which refuse over
    # a millisecond, have the quartiles of ten fits or more)
    assert len(fits) == 3 and spread < LOOSE_NS
    # (after-the-fact records, the h2d pool's lanes here, write no
    # annotation: only what was open as a context is on both clocks)
    ring = [s for s in flight_recorder().spans()
            if s.cat in ("dag", "solve", "ingest", "wait", "eval")]
    labels = {f"{s.cat}:{s.name}" for s in ring}
    assert {"ingest:h2d", "dag:optimize", "wait:d2h", "eval:evaluate",
            "solve:fit:BlockLeastSquaresEstimator"} <= labels
    assert any(n.startswith("dag:node:") for n in labels)
    assert any(n.startswith("dag:rules:") for n in labels)
    assert sorted(n for n, _, _ in captured) == sorted(
        f"{s.cat}:{s.name}" for s in ring)
    # spans of one name pair off in the order they started, on both clocks
    by_name = {}
    for name, start, end in sorted(captured, key=lambda c: c[1]):
        by_name.setdefault(name, []).append((start, end))
    early, longer = [], []   # the trace's interval against the ring's
    for s in sorted(ring, key=lambda s: s.start_s):
        start, end = by_name[f"{s.cat}:{s.name}"].pop(0)
        early.append(s.start_s * 1e9 + offset - start)
        longer.append((end - start) - s.dur_s * 1e9)
    assert statistics.median(map(abs, early)) < TIGHT_NS
    assert statistics.median(map(abs, longer)) < TIGHT_NS
    assert min(early) > -TIGHT_NS and min(longer) > -TIGHT_NS
    assert max(early) < LOOSE_NS and max(longer) < LOOSE_NS, (
        max(early), max(longer))
    assert sum(e > MANY_NS or d > MANY_NS
               for e, d in zip(early, longer)) <= len(ring) // 10
    # and the readers find the fit path in it, though a CPU trace has no
    # device plane to split (no device, nothing to read)
    assert _program_spans.read(run) is None


# -- the readers on a run whose split is known -----------------------------------

OFFSET_NS = 5e9      # trace clock = host clock x 1e9 + this
T0 = 100.0           # host clock at the first fit, seconds
PERIOD = 1.1         # a fit of 1.0 s and a pause of 0.1 s

#: a fused node's label runs to kilobytes; the table cuts it, the split not
NODE = "node:Fused[" + ", ".join(["Fused[A >> B >> C]"] * 32) + "]#1"
#: One fit, seconds from its start: (cat, name, start, end, parent index).
FIT_SPANS = [
    ("ingest", "h2d", 0.00, 0.05, None),
    ("dag", "optimize", 0.05, 0.15, None),
    ("dag", "rules:b", 0.06, 0.10, 1),
    ("eval", "evaluate", 0.20, 1.00, None),
    ("dag", NODE, 0.20, 0.40, 3),
    ("solve", "fit:Est", 0.25, 0.35, 4),
    ("wait", "d2h", 0.40, 0.90, 3),
]
FIT_OPS = [(0.10, 0.12), (0.50, 0.80)]   # the device is busy
#: idle seconds of one fit by innermost span, and of the pause after it
FIT_IDLE = {"ingest:h2d": 0.05, "dag:optimize": 0.04, "dag:rules:b": 0.04,
            "dag:" + NODE: 0.10, "solve:fit:Est": 0.10, "wait:d2h": 0.20,
            "eval:evaluate": 0.10, "unspanned": 0.05}
PAUSE = 0.10


def fabricate(tmp_path, fits, linked=True, jitter_ns=0.0, counted=True):
    """A run of ``fits`` identical fits: the harness's spans on both
    clocks, device ops, the program's spans in the global ring and what
    its counter ``ingest.h2d_bytes`` holds beside them."""
    from keystone_tpu.observability.metrics import MetricsRegistry
    from keystone_tpu.observability.timeline import flight_recorder

    rec = flight_recorder()
    if counted:
        MetricsRegistry.get_or_create().counter(
            "ingest.h2d_bytes").inc(fits * 1000)
    run = make_run(tmp_path)
    ops, traced, seq = [], [], 0
    for i in range(fits):
        t = T0 + i * PERIOD
        wobble = jitter_ns * (1 if i % 2 else -1)
        run.spans.records.append(("fit", t, t + 1.0))
        traced.append(("fit", t * 1e9 + OFFSET_NS + wobble,
                       (t + 1.0) * 1e9 + OFFSET_NS + wobble))
        ops += [("fusion", (t + s) * 1e9 + OFFSET_NS, (t + e) * 1e9 + OFFSET_NS)
                for s, e in FIT_OPS]
        seqs = []
        for cat, name, s, e, parent in FIT_SPANS:
            seq += 1
            seqs.append(seq)
            link = (seq, None if parent is None else seqs[parent],
                    seqs[0] if parent is None else seqs[parent])
            rec.record(name, cat, t + s, e - s,
                       {"nbytes": 1000} if name == "h2d" else None,
                       link=link if linked else (0, None, None))
    end = T0 + (fits - 1) * PERIOD + 1.0
    traced.append(("window", T0 * 1e9 + OFFSET_NS, end * 1e9 + OFFSET_NS))
    run.trace_data = xplane.Trace([xplane.DeviceTrace(0, [], ops)], traced)
    return run


def read_all(run):
    return {name: load_module("layers", name).read(run) for name in NEW}


def expected(fits):
    idle = fits * sum(FIT_IDLE.values()) + (fits - 1) * PAUSE
    unspanned = fits * FIT_IDLE["unspanned"] + (fits - 1) * PAUSE
    return {"optimize_host_s.refit": 0.10,
            "dispatch_host_s.refit": 0.20,
            "host_wait_s.refit": 0.50,
            "idle_host_waiting_s.refit": 0.20,
            "idle_host_busy_s.refit": idle / fits - 0.20,
            "h2d_mb.refit": 1e-3,
            "span_coverage_pct.refit": 100 * (idle - unspanned) / idle}


def test_each_reader_returns_the_exact_split(tmp_path):
    run = fabricate(tmp_path, fits=12)
    got = read_all(run)
    assert got == pytest.approx(expected(12), rel=1e-6)
    split = _program_spans.read(run)
    assert split.fits == 12 and split.dropped == 0
    assert split.anchor_spread_ns == 0.0
    assert split.idle_by_span == pytest.approx(
        {**{k: 12 * v for k, v in FIT_IDLE.items()},
         "unspanned": 12 * FIT_IDLE["unspanned"] + 11 * PAUSE}, rel=1e-6)
    # busy + waiting is the device's idle time, as device_idle_pct has it
    window = run.trace_data.window()
    idle = (window[1] - window[0]) / 1e9 - run.trace_data.busy_seconds(window)
    assert 12 * (got["idle_host_busy_s.refit"]
                 + got["idle_host_waiting_s.refit"]) == pytest.approx(idle)
    # the table was said once, however many readers asked
    assert sum("idle seconds by innermost" in line for line in run.said) == 1
    assert any("wait:d2h" in line for line in run.said)
    assert max(map(len, run.said)) < 200


def test_h2d_reader_refuses_a_counter_that_disagrees_with_the_spans(tmp_path):
    run = fabricate(tmp_path, fits=12, counted=False)   # never raised
    assert load_module("layers", "h2d_mb.refit").read(run) is None
    assert any("not reported" in line for line in run.said)


@pytest.mark.parametrize("why", ["no_trace", "program_without_links",
                                 "anchors_spread", "too_few_fits"])
def test_readers_return_none_where_nothing_sound_is_there(tmp_path, why):
    fits = 12
    if why == "no_trace":
        run = fabricate(tmp_path, fits)
        run.trace_data = None
    elif why == "program_without_links":   # a parent commit's ring
        run = fabricate(tmp_path, fits, linked=False)
    elif why == "anchors_spread":          # offsets 4 ms apart
        run = fabricate(tmp_path, fits, jitter_ns=2e6)
    else:
        fits = 9
        run = fabricate(tmp_path, fits)
    got = read_all(run)
    assert {name: got[name] for name in TRACE_CLOCK} == dict.fromkeys(
        TRACE_CLOCK)
    # seconds and bytes on the host's clock need none of that
    want = expected(fits)
    assert {name: got[name] for name in HOST} == pytest.approx(
        {name: want[name] for name in HOST}, rel=1e-6)


def test_a_ring_that_dropped_spans_gives_the_suffix_of_whole_fits(
        tmp_path, monkeypatch):
    from keystone_tpu.observability.timeline import (flight_recorder,
                                                     reset_flight_recorder)

    per_fit = len(FIT_SPANS)
    monkeypatch.setenv("KEYSTONE_FLIGHT_SPANS", str(15 * per_fit + 3))
    reset_flight_recorder()
    run = fabricate(tmp_path, fits=30)
    assert flight_recorder().dropped() == 15 * per_fit - 3
    got = read_all(run)
    split = _program_spans.read(run)
    assert split.fits == 15 and split.dropped
    assert split.seconds == pytest.approx(14 * PERIOD + 1.0)
    assert got == pytest.approx(expected(15), rel=1e-6)
    # under ten whole fits the idle split is not read; the host's seconds
    # and bytes are, over the nine
    monkeypatch.setenv("KEYSTONE_FLIGHT_SPANS", str(9 * per_fit + 3))
    reset_flight_recorder()
    got = read_all(fabricate(tmp_path, fits=30))
    want = expected(9)
    assert got == pytest.approx(
        {name: want[name] if name in HOST else None for name in NEW},
        rel=1e-6)


# -- the cell's rehearsal, with the spans of every fit checked --------------------

CHECK_SPANS = """
import json, sys
import benchmarks.run as harness
from benchmarks.drivers import fit_loop
from keystone_tpu.observability.timeline import flight_recorder
from keystone_tpu.workflow import executor
def never(value):
    raise AssertionError('an untraced run reached _block_on_device')
executor._block_on_device = never
windows = []
real = fit_loop.one_fit
def one_fit(run, *a, **kw):
    n = len(flight_recorder().spans())
    out = real(run, *a, **kw)
    windows.append(flight_recorder().spans()[n:])
    return out
fit_loop.one_fit = one_fit
code = harness.main(sys.argv[1:])
need = ('dag:node:', 'dag:optimize', 'ingest:h2d', 'wait:d2h',
        'eval:evaluate', 'solve:fit:')
report = {'fits': len(windows), 'dropped': flight_recorder().dropped(),
          'missing': [], 'unlinked': 0}
for spans in windows:
    names = [f'{s.cat}:{s.name}' for s in spans]
    report['missing'] += [p for p in need
                          if not any(n.startswith(p) for n in names)]
    report['unlinked'] += sum(s.seq == 0 or s.root is None for s in spans)
print(json.dumps(report), file=sys.stderr)
sys.exit(code)
"""


def test_the_refit_cell_rehearses_with_the_spans_of_every_fit():
    args = ["--workload", "mnist_refit", "--seed", "2147483777", "--seconds",
            "2", "--trace", "0", "--rehearse"]
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run([sys.executable, "-c", CHECK_SPANS, *args], cwd=ROOT,
                          env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["metrics"] == {}
    report = json.loads(done.stderr.strip().splitlines()[-1])
    assert report["fits"] == result["attempted"] + 1   # and the warming fit
    assert report["missing"] == [] and report["unlinked"] == 0
    assert report["dropped"] == 0
