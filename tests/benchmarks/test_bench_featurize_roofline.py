"""``featurize_roofline.refit`` and its count ``counts/dense_dft.py``: on
the small trace recorded on a TPU v5e (three runs of ``jit_step``), on a
hand-built trace with a solve beside the featurizer, and where there is
nothing to read."""
import os

import pytest

from benchmarks import xplane
from benchmarks.harness import Run, load_json, load_module, load_peaks
from benchmarks.spans import Spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TRACE = os.path.join(HERE, "data", "tiny_trace.xplane.pb")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
#: small enough that three "fits" of it fit the trace's 11 us of device time
SHAPE = {"train_rows": 48, "test_rows": 16, "image_size": 784,
         "fft_size": 1024, "features_per_fft": 512, "num_ffts": 2}


@pytest.fixture(scope="module")
def counts():
    return load_module("counts", "dense_dft")


@pytest.fixture(scope="module")
def reader():
    return load_module("layers", "featurize_roofline.refit")


def make_run(tmp_path, trace_data, cfg=SHAPE, fits=3, peaks=PEAKS):
    run = Run(cell={"name": "t"}, cfg=dict(cfg), traffic={}, seed=0,
              seconds=1.0, trace=True, rehearsal=False, control=False,
              workdir=str(tmp_path), say=lambda text: None, spans=Spans(),
              peaks=peaks)
    run.trace_data = trace_data
    if fits is not None:
        run.facts["fits"] = fits
    return run


def test_counts_at_the_cell_size(counts):
    cfg = load_json(os.path.join(ROOT, "benchmarks", "configs",
                                 "mnist_random_fft_32.json"))
    rows = cfg["train_rows"] + cfg["test_rows"]
    shape = (rows, cfg["image_size"], cfg["features_per_fft"], cfg["num_ffts"])
    assert cfg["features_per_fft"] * 2 == cfg["fft_size"]
    assert counts.flops(*shape) == 2 * 70000 * 784 * 512 * 32
    assert counts.bytes_moved(*shape) == 4 * 70000 * (784 + 32 * 512)
    seconds, bound = counts.roofline_seconds(load_peaks("TPU v5 lite"), *shape)
    # 1.80 TFLOP of float32 in six bfloat16 passes at 197 TFLOP/s; the
    # 4.8 GB read and written once would take 5.9 ms
    assert bound == "compute" and seconds == pytest.approx(54.77e-3, rel=1e-3)
    high, _ = counts.roofline_seconds(PEAKS, *shape, precision="high")
    assert high == pytest.approx(seconds / 2)


def test_counts_turn_memory_bound_when_the_rows_are_short(counts):
    # 8 pixels a row: 16 flops a feature in six passes against 4 bytes
    seconds, bound = counts.roofline_seconds(PEAKS, 1000, 8, 512, 32)
    assert bound == "memory"
    assert seconds == pytest.approx(4 * 1000 * (8 + 32 * 512) / 819e9)


def test_reader_on_the_recorded_trace(tmp_path, reader, counts):
    trace = xplane.load(TRACE, span_prefix="harness:")
    run = make_run(tmp_path, trace)
    least, bound = counts.roofline_seconds(PEAKS, 64, 784, 512, 2)
    assert bound == "compute"
    device = trace.program_seconds()["jit_step"]   # no solve in this trace
    share = reader.read(run)
    assert share == pytest.approx(100.0 * 3 * least / device)
    assert 0.0 < share < 100.0


def test_reader_leaves_the_solve_out_and_everything_else_in(tmp_path, reader,
                                                            counts):
    second = 1e9
    modules = [("jit_raw", 0.0, 0.2 * second),
               ("jit__block_solve", 0.2 * second, 0.9 * second),
               ("jit_apply", 0.9 * second, 1.0 * second)]
    trace = xplane.Trace(
        devices=[xplane.DeviceTrace(0, modules, [])],
        spans=[("window", 0.0, 1.0 * second)])
    cfg = dict(SHAPE, train_rows=60000, test_rows=10000, num_ffts=32)
    run = make_run(tmp_path, trace, cfg=cfg, fits=2)
    least, _ = counts.roofline_seconds(PEAKS, 70000, 784, 512, 32)
    # two fits in 0.3 s of featurize, apply and evaluation
    assert reader.read(run) == pytest.approx(100.0 * 2 * least / 0.3)
    # the window clips what ran outside it
    trace.spans = [("window", 0.1 * second, 1.0 * second)]
    assert reader.read(run) == pytest.approx(100.0 * 2 * least / 0.2)


@pytest.mark.parametrize("why", ["no trace", "no fits", "no peaks",
                                 "no shape", "only the solve ran"])
def test_reader_returns_none_where_there_is_nothing_to_read(tmp_path, reader,
                                                            why):
    trace = xplane.load(TRACE, span_prefix="harness:")
    kwargs = {}
    if why == "no trace":
        trace = None
    elif why == "no fits":
        kwargs["fits"] = None
    elif why == "no peaks":
        kwargs["peaks"] = None
    elif why == "no shape":
        kwargs["cfg"] = {k: v for k, v in SHAPE.items() if k != "num_ffts"}
    else:
        trace = xplane.Trace(
            devices=[xplane.DeviceTrace(0, [("jit__block_solve", 0.0, 5e8)], [])],
            spans=[])
    assert reader.read(make_run(tmp_path, trace, **kwargs)) is None
