"""Host seconds per fit in making new datasets of the held rows (host to
device, padding and sharding included)."""


def read(run):
    fits = run.spans.count("to_device")
    return run.spans.total("to_device") / fits if fits else None
