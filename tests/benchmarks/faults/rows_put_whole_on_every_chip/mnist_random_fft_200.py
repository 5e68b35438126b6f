"""``rows_put_whole_on_every_chip``, the fault of the mesh: the sharding
that lays a batch's rows over the data axis is swapped for one that puts
every row on every chip, where ``parallel/dataset.py`` asks for it. Every
fit then computes the right model (each chip fits all the rows, nothing
is reduced between them), at four times the memory and the work, and
every gap reads sound: only the program's own account of where its
design matrix lay says so (``shards_off``, ``replicated_off``). The
rehearsal's environment is set before the program is imported; the rest
of a run is driven as it is. The run has to come out not correct."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "mnist_random_fft_200.json")) as f:
    os.environ.update(json.load(f)["rehearsal"]["env"])

import benchmarks.run as harness  # noqa: E402
from keystone_tpu.parallel import dataset, mesh  # noqa: E402

dataset.batch_sharding = mesh.replicated_sharding
sys.exit(harness.main(sys.argv[1:]))
