"""What the TPU's compiler makes of a program of the main path, with no
chip: compiled for a described v5e (the `on-chip-measurement` guide,
section 2). Only one process may load the TPU's library, so the topology
is described inside a fixture, and every such compile lives in THIS file
(a second file can land on another worker, whose fixture then skips).
"""
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from keystone_tpu.ops import sift

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def probe_voc():
    spec = importlib.util.spec_from_file_location(
        "probe_voc", os.path.join(ROOT, "tools", "probe_voc.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_a_sift_chunk_is_written_once_in_place_in_the_layout_it_leaves_in(
        one_chip, no_compile_cache):
    """``voc_refit``'s largest bucket at the cell's chunk of 16: every
    scale's segment starts and ends on a lane tile, so no update of the
    output is shifted across lanes and the program does not end in a
    copy of its 411 MB into another layout (``PERF.md`` section 6,
    PR 47: five such updates and the copy were a quarter of dense
    SIFT's device time)."""
    height, width = 384, 512
    args, static = sift.chunk_call(      # the call the stage makes
        jnp.zeros((16, height, width)), np.full((16, 2), (height, width)))
    compiled = sift._dsift_chunk.lower(
        *jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), args),
        **static).compile()
    text = compiled.as_text()
    assert "dynamic-update-slice" in text and "is_index_aligned" in text
    structure = probe_voc().chunk_program_structure(text)
    assert structure["unaligned_updates"] == 0, structure
    assert not structure["root_is_copy"], structure
    assert compiled.memory_analysis().output_size_in_bytes == (
        16 * 128 * sift.chunk_width(height, width) * 4)
