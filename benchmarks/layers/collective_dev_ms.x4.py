"""Device milliseconds a fit that the FIRST chip spends in collective
operations: the events of its ``XLA Ops`` line inside the window whose
HLO op is an all-reduce, all-gather, reduce-scatter, collective-permute
or all-to-all (``all-reduce.24``: the name up to its number), over the
window's fits. An asynchronous pair is counted by its ``-done``, the
wait for what ``-start`` launched; the ``-start`` itself returns at once
and is not counted. A collective inside a loop is one event an
iteration, so the block solve's one all-reduce a block step (the
upper-triangle tiles of the block's Gram and its cross product, summed
over the data shards) counts once a block. Time, not exposure: whether
compute ran on the chip beside a collective is not told apart, and the
other chips are seen only through ``chip_skew_pct.x4``. None where the
trace has no such op (one chip, or a program from before the cell)."""
from benchmarks import xplane

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def is_collective(op: str) -> bool:
    """``all-reduce.24`` and ``all-gather-done.3`` are; ``all-reduce-\
start.3`` and ``fusion.7`` are not."""
    kind = op.split(".", 1)[0]
    if kind.endswith("-start"):
        return False
    if kind.endswith("-done"):
        kind = kind[:-len("-done")]
    return kind in COLLECTIVES


def collective_seconds(trace):
    """``{op: (events, seconds)}`` of the first chip's collective ops
    inside the window."""
    window = trace.window()
    found = {}
    if not trace.devices or window is None:
        return found
    for name, s, e in trace.devices[0].ops:
        if is_collective(name):
            for cs, ce in xplane.clip([(s, e)], *window):
                events, seconds = found.get(name, (0, 0.0))
                found[name] = (events + 1, seconds + (ce - cs) / 1e9)
    return found


def read(run):
    fits = run.facts.get("fits")
    if not fits or run.trace_data is None:
        return None
    found = collective_seconds(run.trace_data)
    if not found:
        return None
    run.say("collective ops of the first chip in the window: " + ", ".join(
        f"{name} x{events} {seconds:.4f} s" for name, (events, seconds)
        in sorted(found.items(), key=lambda kv: -kv[1][1])[:8]))
    return 1e3 * sum(seconds for _, seconds in found.values()) / fits
