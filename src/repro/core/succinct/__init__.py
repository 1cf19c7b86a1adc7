"""Succinct data structures used by the ITR encoder/decoder and query engine.

All structures report `size_in_bytes()` so compression benchmarks account the
true serialized footprint, and expose numpy-side query paths. On a TPU the
k²-tree's levels also live on the device, where its batched rank runs
(`repro.core.succinct.device_rank`).
"""
from repro.core.succinct.bitvector import (
    BitVector,
    pack_bits,
    unpack_bits,
)
from repro.core.succinct.elias_fano import EliasFano
from repro.core.succinct.delta_code import (
    delta_decode,
    delta_encode,
    gamma_decode,
    gamma_encode,
)
from repro.core.succinct.k2tree import K2Tree

__all__ = [
    "BitVector",
    "pack_bits",
    "unpack_bits",
    "EliasFano",
    "delta_encode",
    "delta_decode",
    "gamma_encode",
    "gamma_decode",
    "K2Tree",
]
