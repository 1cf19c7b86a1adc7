"""Compile the chip's rank and fused descent for a described TPU v5e, no chip attached.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker running
this file loads the TPU compiler.
"""
import os

import pytest

from repro.core.succinct import device_rank
from repro.kernels.bitvec_rank import bitvec_rank

# The largest k²-tree of the chip smoke (jamendo stand-in at scale 1.0, four
# predicate-hash shards) holds 574,891 words over its levels: width bucket
# 2**20. 2**24 words (64 MiB of words plus 64 MiB of ranks) is a tree ~30x
# larger.
WIDTHS = [device_rank.width_bucket(574_891), 2**24]
BUCKETS = [device_rank.MIN_POSITIONS, 65_536]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("width", WIDTHS)
def test_rank_compiles_for_v5e(one_chip, width):
    """The TPU compiler accepts the rank program at this width for both
    position buckets; its arguments are the whole word and rank arrays."""
    assert width == 2**20 or width == 2**24
    for bucket in BUCKETS:
        compiled = bitvec_rank.lower(
            *device_rank.rank_args(width, bucket, one_chip)).compile()
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes >= 8 * width + 4 * bucket
        assert mem.output_size_in_bytes == 4 * bucket
        assert "gather" in compiled.as_text()


def test_descent_compiles_for_v5e(one_chip):
    """The TPU compiler accepts the fused descent over a 2**20-word tree of
    19 levels (jamendo's deepest) with a 64k-entry frontier; its arguments
    cover the whole word and rank arrays, and it gathers from them."""
    from repro.kernels.k2_descent import k2_descent

    width, h = 2**20, 19
    compiled = k2_descent.lower(*device_rank.descent_args(width, h, one_chip),
                                k=2, axis=0, cap=65_536, out_cap=4096).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 8 * width + 4 * device_rank.DESCENT_MAX_BATCH
    assert mem.output_size_in_bytes >= 4 * (h + 2 * 4096)  # sizes, then pairs
    assert "gather" in compiled.as_text()
