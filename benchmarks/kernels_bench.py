"""Kernel micro-bench: µs/call of each kernel against its jnp reference, on
whatever backend JAX runs. On a CPU the Pallas kernels run in interpret mode
and `bitvec_rank` on XLA:CPU, so these numbers rank CPU hypotheses only;
they are never device numbers (device timings come from runs on the chip).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.kernels.segment_matmul import build_csr_blocks


def _k2_batched_row_bench(rng, n_rows=256, iters=3):
    """Time one batched multi-row k²-tree expansion with its levels placed
    for the device rank (XLA on this backend) vs the host numpy rank."""
    from repro.core.succinct import K2Tree
    from repro.core.succinct.device_rank import DeviceLevels

    n = m = 2048
    r = rng.integers(0, n, 20000)
    c = rng.integers(0, m, 20000)
    host = K2Tree(r, c, n, m)
    placed = K2Tree(r, c, n, m)
    if placed.device is None:
        placed.device = DeviceLevels(placed.levels)
    qs = rng.integers(0, n, n_rows).astype(np.int64)

    timings = {}
    for name, tree in (("device", placed), ("numpy", host)):
        tree.rows_many(qs)  # warmup (compilation / caches)
        t0 = time.perf_counter()
        for _ in range(iters):
            tree.rows_many(qs)
        timings[name] = (time.perf_counter() - t0) / iters * 1e6
    return (f"k2_rows_batched_{n_rows}r", timings["device"], timings["numpy"])


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else None
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run(quiet=False):
    rng = np.random.default_rng(0)
    rows = []

    q = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    flash = lambda: ops.flash_attention(q, k, k, block_q=128, block_k=128)
    attn_ref = jax.jit(lambda q, k, v: ref.attention_ref(q, k, v))
    rows.append(("flash_attention_256", _time(lambda *a: flash()),
                 _time(attn_ref, q, k, k)))

    x = jnp.asarray(rng.normal(size=(512, 128)), jnp.float32)
    s = rng.integers(0, 512, 2048)
    r = rng.integers(0, 512, 2048)
    src, dst = build_csr_blocks(s, r, 512)
    rows.append(("csr_spmm_2048e", _time(ops.csr_spmm, x, jnp.asarray(src), jnp.asarray(dst), 512),
                 _time(jax.jit(lambda x: ref.spmm_ref(x, jnp.asarray(s), jnp.asarray(r), 512)), x)))

    tbl = jnp.asarray(rng.normal(size=(5000, 128)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 5000, (256, 4)), jnp.int32)
    rows.append(("embedding_bag_256x4", _time(ops.embedding_bag, tbl, idx),
                 _time(jax.jit(lambda t, i: ref.embedding_bag_ref(t, i)), tbl, idx)))

    xf = jnp.asarray(rng.normal(size=(128, 27, 128)), jnp.float32)
    rows.append(("dot_interaction_27f", _time(ops.dot_interaction, xf),
                 _time(jax.jit(ref.dot_interaction_ref), xf)))

    its = jnp.asarray(rng.integers(0, 50, (512, 8)), jnp.int32)
    cnts = jnp.asarray(rng.integers(1, 9, (512, 8)), jnp.int32)
    rows.append(("digram_count_512x8", _time(ops.digram_pair_counts, its, cnts),
                 _time(jax.jit(ref.digram_pair_counts_ref), its, cnts)))

    words = jnp.asarray(rng.integers(0, 2**32, 4096, dtype=np.uint64).astype(np.uint32))
    ranks = jnp.asarray(rng.integers(0, 100, 4096), jnp.int32)
    pos = jnp.asarray(rng.integers(0, 4096 * 32, 1024), jnp.int32)
    rows.append(("bitvec_rank_1024q", _time(ops.bitvec_rank, words, ranks, pos),
                 _time(jax.jit(ref.bitvec_rank_ref), words, ranks, pos)))

    # non-multiple-of-block batch: exercises the pad-to-boundary path
    pos_odd = jnp.asarray(rng.integers(0, 4096 * 32, 1000), jnp.int32)
    rows.append(("bitvec_rank_1000q_pad", _time(ops.bitvec_rank, words, ranks, pos_odd),
                 _time(jax.jit(ref.bitvec_rank_ref), words, ranks, pos_odd)))

    # batched k²-tree multi-row traversal (the query-engine hot loop): one
    # level-synchronous sweep for 256 rows, device-placed rank vs numpy
    rows.append(_k2_batched_row_bench(rng, n_rows=256))

    out = []
    for name, k_us, r_us in rows:
        out.append({"kernel": name, "kernel_us": k_us, "jnp_ref_us": r_us})
        if not quiet:
            print(f"kern {name:<22} kernel={k_us:9.1f}us  ref={r_us:9.1f}us")
    return out


if __name__ == "__main__":
    run()
