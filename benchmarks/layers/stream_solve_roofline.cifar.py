"""The streamed block solve's share of its roofline, the maker apart:
the least time the chip could take for one fit's Grams, factors and
epoch products (``counts/streamed_bcd.py`` with no generation product:
the convolution is ``conv_roofline.cifar``'s; the last, narrower block
at its own width) over ``stream_solve_dev_ms.cifar``.

What the least leaves out, so that the share reads lower for each and
never over 100%: the program sweeps the last block at the full
``block_size`` (widened with zero filters: 4,096 columns for
``cifar_aug_refit``'s 3,616 and for ``cifar_refit``'s 2,176), and where
the rows are taken in chunks (``cifar_aug_refit``) it passes over the
held block four times more than a block that is summed as it is made
(mean, centred squares, Gram + cross, the update of ``P``: memory, about
0.2 s a fit at the chip's rate), none of which a solve must do."""
from benchmarks.layers import _common


def read(run):
    fits = run.facts.get("fits")
    seconds = _common.load_reader("stream_solve_dev_ms.cifar").solve_seconds(run)
    shape = run.cfg.get("solve_shape")
    if not fits or not seconds or not shape or run.peaks is None:
        return None
    counts = _common.load_counts("streamed_bcd")
    whole = shape["blocks"] - (1 if shape.get("last_block") else 0)
    parts = [(shape["block_size"], whole)] + (
        [(shape["last_block"], 1)] if shape.get("last_block") else [])
    passes = counts.MXU_PASSES[shape["precision"]]
    compute = memory = 0.0
    for width, blocks in parts:
        compute += sum(counts.fit_flops(
            shape["rows"], 0, width, blocks, shape["classes"],
            shape["epochs"]).values()) * passes / run.peaks["bf16_flops_per_s"]
        memory += counts.fit_bytes(
            shape["rows"], 0, width, blocks, shape["classes"],
            shape["epochs"]) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * max(compute, memory) * fits / seconds
