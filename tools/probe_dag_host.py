"""Host probe for graph composition (ISSUE 39): what one ``MnistRandomFFT``
fit costs the HOST at 8 / 32 / 100 / 200 branches, and where in it.

    python3 tools/probe_dag_host.py [--branches 8,32,100,200] [--fits 7]

No chip and no data of size: ``run()`` on 64 + 32 random rows, so the
device's share is small and what differs with the branch count is host
work. A fit's host seconds are read twice: the CPU seconds of the thread
that runs the fit (``time.thread_time``: Python's own work, no wait
counted wherever it happens), and its wall less the seconds inside
``jax.Array._value`` (where the host waits for results: what ISSUE 39
read; on a CPU backend a dispatch can wait for the program before it
too, so it reads higher than the first). Inside them, on the
wall clock, the seconds in ``build_featurizer``, in its
``Pipeline.gather``, in the ``>>`` that make its branches, and in the
optimizer's runs (``dag:optimize``). Medians of ``--fits`` fits after one
that compiles.

Beside the seconds, counted in one more fit and not timed: the
program's own ``dag.compose.entries`` / ``dag.compose.calls`` where the
tree has them, and this probe's independent count of the same thing,
``written``: the entries of every dictionary and set that a ``Graph``
made outside the optimizer holds and that no earlier graph held (the
same object), so a tree from before the counters can be read too.
Nothing here is a device time. Writes ``chiprun_out/probe_dag_host.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

TRAIN_ROWS, TEST_ROWS = 64, 32
SECTIONS = ("build_featurizer", "gather", "branch_rshift", "optimize")
COUNTERS = ("dag.compose.entries", "dag.compose.calls")


class Clock:
    """Seconds spent inside the program's own functions, by section."""

    def __init__(self):
        self.seconds = dict.fromkeys((*SECTIONS, "value"), 0.0)
        self.in_featurizer = self.gathered = False

    def timed(self, section, fn, when=lambda: True):
        def wrapper(*args, **kwargs):
            if not when():
                return fn(*args, **kwargs)
            start = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                self.seconds[section] += time.perf_counter() - start
        return wrapper

    def install(self):
        from jax._src.array import ArrayImpl

        from keystone_tpu.pipelines.images.mnist import random_fft
        from keystone_tpu.workflow.optimizer.rule import Optimizer
        from keystone_tpu.workflow.pipeline import Chainable, Pipeline

        build, gather = random_fft.build_featurizer, Pipeline.gather

        def build_featurizer(config):
            self.in_featurizer, self.gathered = True, False
            try:
                return build(config)
            finally:
                self.in_featurizer = False

        def gather_once(branches):
            self.gathered = True
            return gather(branches)

        random_fft.build_featurizer = self.timed(
            "build_featurizer", build_featurizer)
        Pipeline.gather = staticmethod(self.timed("gather", gather_once))
        # the ``>>`` that make the branches: those before the gather
        Chainable.__rshift__ = self.timed(
            "branch_rshift", Chainable.__rshift__,
            when=lambda: self.in_featurizer and not self.gathered)
        Optimizer.execute = self.timed("optimize", Optimizer.execute)
        ArrayImpl._value = property(
            self.timed("value", ArrayImpl._value.fget))


class Written:
    """While open: the entries of the containers that graphs made
    outside the optimizer hold and no earlier graph held."""

    def __init__(self):
        self.entries = self.graphs = self._optimizing = 0
        self._seen = {}

    def __enter__(self):
        from keystone_tpu.workflow.graph import Graph
        from keystone_tpu.workflow.optimizer.rule import Optimizer

        self._init, self._execute = Graph.__init__, Optimizer.execute

        def init(graph, *args, **kwargs):
            self._init(graph, *args, **kwargs)
            if self._optimizing:
                return
            self.graphs += 1
            for part in (graph.sources, graph.sink_dependencies,
                         graph.operators, graph.dependencies):
                if id(part) not in self._seen:
                    self._seen[id(part)] = part  # held, so ids stay unique
                    self.entries += len(part)

        def execute(*args, **kwargs):
            self._optimizing += 1
            try:
                return self._execute(*args, **kwargs)
            finally:
                self._optimizing -= 1

        Graph.__init__, Optimizer.execute = init, execute
        return self

    def __exit__(self, *exc):
        from keystone_tpu.workflow.graph import Graph
        from keystone_tpu.workflow.optimizer.rule import Optimizer

        Graph.__init__, Optimizer.execute = self._init, self._execute


def one_fit(branches: int, rows):
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.pipelines.images.mnist import random_fft

    (train_x, train_y), (test_x, test_y) = rows
    # new datasets a fit, as the benchmark's ``fit_in_memory`` hands them:
    # nothing the prefix-state table has met
    train = LabeledData(ArrayDataset.from_numpy(train_x),
                        ArrayDataset.from_numpy(train_y))
    test = LabeledData(ArrayDataset.from_numpy(test_x),
                       ArrayDataset.from_numpy(test_y))
    # one sign seed for every fit, as a cell's configuration has: other
    # signs are other constants to the featurizer's programs, and compile
    random_fft.run(random_fft.MnistRandomFFTConfig(
        num_ffts=branches, block_size=2048, lam=0.0, seed=0),
        train=train, test=test)


def counters():
    from keystone_tpu.observability.metrics import MetricsRegistry
    from keystone_tpu.observability.names import METRIC_NAMES

    registry = MetricsRegistry.get_or_create()
    return {name: registry.counter(name).value if name in METRIC_NAMES
            else None for name in COUNTERS}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--branches", default="8,32,100,200")
    p.add_argument("--fits", type=int, default=7)
    args = p.parse_args(argv)

    rng = np.random.default_rng(39)
    rows = tuple(
        (rng.integers(0, 256, size=(n, 784)).astype(np.float32),
         rng.integers(0, 10, size=n).astype(np.int32))
        for n in (TRAIN_ROWS, TEST_ROWS))
    clock = Clock()
    clock.install()
    devnull = open(os.devnull, "w")
    out = []
    for branches in (int(b) for b in args.branches.split(",")):
        readings = []
        for _ in range(args.fits + 1):
            clock.seconds = dict.fromkeys(clock.seconds, 0.0)
            stdout, sys.stdout = sys.stdout, devnull
            start, cpu = time.perf_counter(), time.thread_time()
            try:
                one_fit(branches, rows)
            finally:
                cpu = time.thread_time() - cpu
                wall = time.perf_counter() - start
                sys.stdout = stdout
            readings.append(dict(
                clock.seconds, wall=wall, host_cpu=cpu,
                host_less_value=wall - clock.seconds["value"]))
        before = counters()
        stdout, sys.stdout = sys.stdout, devnull
        try:
            with Written() as written:
                one_fit(branches, rows)
        finally:
            sys.stdout = stdout
        after = counters()
        line = {"branches": branches, "fits": args.fits}
        for key in ("host_cpu", "host_less_value", *SECTIONS):
            # the first fit compiles: not among the medians
            line[f"{key}_s"] = statistics.median(
                r[key] for r in readings[1:])
        line["host_cpu_s_min_max"] = [
            min(r["host_cpu"] for r in readings[1:]),
            max(r["host_cpu"] for r in readings[1:])]
        for name in COUNTERS:
            line[name] = (None if after[name] is None
                          else after[name] - before[name])
        line["written"] = written.entries
        line["graphs_made"] = written.graphs
        out.append(line)
        print(json.dumps(line), flush=True)

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_dag_host.json", "w") as f:
        json.dump(out, f, indent=1)
    print("| branches | host CPU s a fit | build_featurizer | gather | branches' >> "
          "| dag:optimize | dag.compose.entries | written (probe's count) |")
    print("|---|---|---|---|---|---|---|---|")
    for line in out:
        entries = line["dag.compose.entries"]
        print(f"| {line['branches']} | {line['host_cpu_s']:.4f} "
              f"({line['host_cpu_s_min_max'][0]:.4f}-"
              f"{line['host_cpu_s_min_max'][1]:.4f}) "
              f"| {line['build_featurizer_s']:.4f} | {line['gather_s']:.4f} "
              f"| {line['branch_rshift_s']:.4f} | {line['optimize_s']:.4f} "
              f"| {'none' if entries is None else int(entries)} "
              f"| {line['written']} |")


if __name__ == "__main__":
    main()
