"""Host seconds of set-up inside the loader call (files -> datasets):
the one read of the files that a cell fitting from held rows makes."""


def read(run):
    return run.facts.get("loader_s") or None
