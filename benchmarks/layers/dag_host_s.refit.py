"""Host seconds per fit that are neither getting the rows nor device
work: the fit's wall, minus the call that makes its datasets (the loader,
or host to device of held rows), minus the time an op ran on the device
while the DAG executed."""


def read(run):
    fits = run.spans.count("fit")
    if not fits or run.trace_data is None:
        return None
    busy = run.trace_data.busy_seconds(run.trace_data.window())
    rows = run.spans.total("ingest") + run.spans.total("to_device")
    return (run.spans.total("fit") - rows - busy) / fits
