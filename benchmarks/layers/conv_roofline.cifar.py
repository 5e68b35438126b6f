"""The block maker's share of its roofline: the least time the chip
could take for the convolutions of one fit (``counts/
conv_rectify_pool.py``: ``epochs`` generations of the training rows and
one of the test rows, a 108-deep product at one bfloat16 pass over the
patch positions that some pooling region covers, against the bytes a
maker that never stores its patches must move) over
``conv_dev_ms.cifar``. The positions are the configuration's:
``solve_shape.pooled_positions`` where its geometry pools fewer than it
convolves (196 of 361 on a 24 x 24 crop), ``positions`` where all are
pooled; a maker at the peak of what a feature needs reads 100% either
way. A program that makes a block more often than the least
(``blocks_generated.cifar``) reads lower for every extra generation, so
the share cannot pass 100%."""
from benchmarks.layers import _common, _maker_loops

#: what ``solve_shape`` may state of the maker's geometry beside the
#: counts' defaults (``cifar_refit``'s)
GEOMETRY = ("filters_a_block", "image_floats")


def read(run):
    fits = run.facts.get("fits")
    seconds = _maker_loops.maker_seconds(run)
    shape = run.cfg.get("solve_shape")
    if not fits or not seconds or not shape or run.peaks is None:
        return None
    least, _bound = _common.load_counts("conv_rectify_pool").roofline_seconds(
        run.peaks, shape["rows"], shape["test_rows"], shape["filters"],
        shape.get("pooled_positions", shape["positions"]),
        shape["patch_dim"], shape["pools"], shape["epochs"],
        shape["conv_precision"],
        **{k: shape[k] for k in GEOMETRY if k in shape})
    return 100.0 * least * fits / seconds
