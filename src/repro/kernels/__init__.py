"""Device kernels for the framework's compute hot spots.

Each Pallas kernel lives in its own module (pl.pallas_call + BlockSpec),
has a pure-jnp oracle in `ref.py`, and a jitted wrapper in `ops.py` that
picks interpret mode off-TPU. `bitvec_rank`, the query path's one device
op, is plain XLA instead (see its module). See tests/test_kernels.py for
the sweep tests.
"""
from repro.kernels import ops, ref
from repro.kernels.ops import (
    bitvec_rank,
    build_csr_blocks,
    csr_spmm,
    digram_pair_counts,
    dot_interaction,
    embedding_bag,
    flash_attention,
)

__all__ = [
    "ops",
    "ref",
    "bitvec_rank",
    "build_csr_blocks",
    "csr_spmm",
    "digram_pair_counts",
    "dot_interaction",
    "embedding_bag",
    "flash_attention",
]
