"""Seconds from the process's start, as the harness has it
(``benchmarks.harness.T_START``), to the end of the package's import:
the interpreter's first imports, ``import jax``, the device client
(``jax.devices()``) and ``import keystone_tpu``. The end is the
program's pinned span ``startup:import``, which the ring never drops;
the span's own length (the package alone) is said beside it. ``None``
for a program that pins no such span (a parent commit)."""
from benchmarks.harness import T_START


def read(run):
    from keystone_tpu.observability.timeline import flight_recorder

    pinned = getattr(flight_recorder(), "pinned", None)
    span = pinned().get("startup:import") if pinned else None
    if span is None:
        return None
    run.say(f"start-up: the package's own import took {span.dur_s:.3f} s")
    return span.start_s + span.dur_s - T_START
