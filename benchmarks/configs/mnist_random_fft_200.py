"""MnistRandomFFT at its source's 200 branches, on every chip the process
sees: ``mnist_random_fft_32``'s job (CSVs from the seed, the app's
loader, the app's public ``run()``; ``hold`` / ``datasets`` for a traffic
mix that fits again and again from held rows) with nothing of its own
but what the four-chip deployment adds to the comparison: around every
fit it reads the program's own account of where the design matrix lay
(``solve.data_shards``, ``solve.shard_bytes_max``, ``solve.sharded.fits``,
``solve.allreduce_bytes``). The mesh is the package's default one; no
path here is chosen by the configuration's name."""
from __future__ import annotations

from benchmarks.configs import mnist_random_fft_32

#: the program's counters (rise a fit) and gauges (value after a fit)
COUNTERS = {"sharded_fits": "solve.sharded.fits",
            "allreduce_bytes": "solve.allreduce_bytes"}
GAUGES = {"data_shards": "solve.data_shards",
          "shard_bytes_max": "solve.shard_bytes_max"}
#: what they read around every fit of this process, oldest first
#: (``layers/allreduce_mb.x4.py`` reads the window's)
FIT_COUNTS = []


class Job(mnist_random_fft_32.Job):
    def fit(self, loaded):
        from keystone_tpu.observability.metrics import MetricsRegistry

        registry = MetricsRegistry.get_or_create()
        before = {k: registry.counter(name).value
                  for k, name in COUNTERS.items()}
        outcome = super().fit(loaded)
        counts = {k: registry.counter(name).value - before[k]
                  for k, name in COUNTERS.items()}
        counts.update((k, registry.gauge(name).value)
                      for k, name in GAUGES.items())
        FIT_COUNTS.append(counts)
        return outcome

    def answers(self, outcome):
        return dict(super().answers(outcome), fit_counts=list(FIT_COUNTS))


def prepare(cfg, seed, workdir):
    from keystone_tpu.observability.names import METRIC_NAMES

    missing = sorted({*COUNTERS.values(), *GAUGES.values()} - METRIC_NAMES)
    if missing:
        # a program from before PR 38 cannot say where its design matrix
        # lay, and the cell's ``correct`` rests on that: fail at once
        raise SystemExit(f"this program has no {', '.join(missing)}: it "
                         "cannot run mnist_random_fft_200")
    return Job(cfg, seed, workdir)
