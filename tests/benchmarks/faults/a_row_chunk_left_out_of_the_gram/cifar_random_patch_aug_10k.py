"""``a_row_chunk_left_out_of_the_gram`` for the configuration whose
streamed sweep takes its rows in chunks: the sums over a held block's
chunks (``ops.linalg._ChunkedBlock.sum``: means, deviations, Gram and
cross product) stop one chunk short, and the rest of a run is driven as
it is. The program counts the rows of a block's Gram where it sums them,
so the run has to come out not correct by ``rows_solved_off``, and by
the weights."""
import sys

import jax

import benchmarks.run as harness
from keystone_tpu.ops import linalg


def one_chunk_short(self, part, init):
    return jax.lax.fori_loop(
        0, self.count - 1, lambda j, acc: jax.tree_util.tree_map(
            jax.numpy.add, acc, part(j)), init)


linalg._ChunkedBlock.sum = one_chunk_short
sys.exit(harness.main(sys.argv[1:]))
