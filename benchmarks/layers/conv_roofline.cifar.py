"""The block maker's share of its roofline: the least time the chip
could take for the convolutions of one fit (``counts/
conv_rectify_pool.py``: ``epochs`` generations of the training rows and
one of the test rows, a 108-deep product at one bfloat16 pass, against
the bytes a maker that never stores its patches must move) over
``conv_dev_ms.cifar``. The program makes the training rows' blocks more
often than that (``blocks_generated.cifar``), so the share cannot pass
100% and reads lower for every extra generation."""
from benchmarks.layers import _common, _maker_loops


def read(run):
    fits = run.facts.get("fits")
    seconds = _maker_loops.maker_seconds(run)
    shape = run.cfg.get("solve_shape")
    if not fits or not seconds or not shape or run.peaks is None:
        return None
    least, _bound = _common.load_counts("conv_rectify_pool").roofline_seconds(
        run.peaks, shape["rows"], shape["test_rows"], shape["filters"],
        shape["positions"], shape["patch_dim"], shape["pools"],
        shape["epochs"], shape["conv_precision"])
    return 100.0 * least * fits / seconds
