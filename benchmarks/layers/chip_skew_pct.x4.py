"""How unevenly the chips of a cell were busy: the busiest chip's busy
seconds in the window less the idlest's, over the mean of all of them,
as a percentage. Rows are laid evenly over the data shards and every
chip runs the same programs, so this reads near 0 unless one chip waits
on the others (a collective's slow member shows as the OTHERS' busy
time: an op that waits is an op that runs) or does work of its own (the
first chip holds what is put on "the" device). None with fewer than two
device planes in the trace."""
from benchmarks import xplane


def busy_by_chip(trace):
    window = trace.window()
    if window is None:
        return []
    return [xplane.union_seconds(trace.busy_intervals(d, window))
            for d in trace.devices]


def read(run):
    if run.trace_data is None:
        return None
    busy = busy_by_chip(run.trace_data)
    if len(busy) < 2 or not sum(busy):
        return None
    run.say("busy seconds a chip in the window: "
            + ", ".join(f"{b:.3f}" for b in busy))
    return 100.0 * (max(busy) - min(busy)) / (sum(busy) / len(busy))
