"""Jitted public wrappers for the Pallas kernels.

On non-TPU backends the kernels run in `interpret=True` mode (the kernel
body executes as traced JAX ops — bit-exact correctness, no Mosaic); on TPU
they compile to Mosaic. Models call these wrappers through the
`use_pallas` config switch so CPU dry-runs lower the pure-jnp reference
path while TPU runs get the kernels. `bitvec_rank` is the exception: it
is plain XLA on every backend (see `repro.kernels.bitvec_rank`).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import ref
from repro.kernels.bitvec_rank import bitvec_rank as _bitvec_rank
from repro.kernels.digram_count import digram_pair_counts as _digram_pair_counts
from repro.kernels.dot_interaction import dot_interaction as _dot_interaction
from repro.kernels.embedding_bag import embedding_bag as _embedding_bag
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.segment_matmul import build_csr_blocks, csr_spmm as _csr_spmm


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "sm_scale", "block_q", "block_k")
)
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    sm_scale=None, block_q=128, block_k=128):
    return _flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, interpret=_interpret(),
    )


@functools.partial(jax.jit, static_argnames=("n_nodes", "block_n", "block_d"))
def csr_spmm(x, src_idx, local_dst, n_nodes, *, block_n=128, block_d=None):
    return _csr_spmm(
        x, src_idx, local_dst, n_nodes, block_n=block_n, block_d=block_d,
        interpret=_interpret(),
    )


@functools.partial(jax.jit, static_argnames=("combiner", "block_b", "block_d"))
def embedding_bag(table, indices, *, combiner="sum", block_b=128, block_d=None):
    return _embedding_bag(
        table, indices, combiner=combiner, block_b=block_b, block_d=block_d,
        interpret=_interpret(),
    )


@functools.partial(jax.jit, static_argnames=("block_b",))
def dot_interaction(x, *, block_b=128):
    return _dot_interaction(x, block_b=block_b, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_n",))
def digram_pair_counts(its, cnts, *, block_n=256):
    return _digram_pair_counts(its, cnts, block_n=block_n, interpret=_interpret())


bitvec_rank = _bitvec_rank  # plain XLA, already jitted: no interpret mode


__all__ = [
    "flash_attention",
    "csr_spmm",
    "build_csr_blocks",
    "embedding_bag",
    "dot_interaction",
    "digram_pair_counts",
    "bitvec_rank",
    "ref",
]
