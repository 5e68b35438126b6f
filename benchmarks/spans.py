"""The harness's own spans: host-clock intervals around the calls into
each layer, also written into the profiler's trace (as ``bench:<name>``)
so that idle gaps of the device can be labelled by what the host did."""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, List, Tuple


class Spans:
    def __init__(self) -> None:
        self.records: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:" + name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def total(self, name: str) -> float:
        return sum(e - s for n, s, e in self.records if n == name)

    def count(self, name: str) -> int:
        return sum(1 for n, _, _ in self.records if n == name)

    def by_name(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for n, s, e in self.records:
            out[n] = out.get(n, 0.0) + (e - s)
        return out
