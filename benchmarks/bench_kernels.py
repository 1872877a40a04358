"""Kernel micro-benches: wall time of each Pallas kernel's jnp reference
and the kernel/reference agreement.  On the TPU the kernel is the compiled
one; on the CPU backend it runs in the Pallas interpreter (a correctness
harness only), and the row names say ``interpret``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timed
from repro.kernels import ops, ref


def bench_kernels(fast=True):
    mode = "interpret" if ops.interpret_mode() else "compiled"
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, S, H, K, hd = 2, 512, 8, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
    f = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v))
    f(q, k, v).block_until_ready()
    _, us = timed(lambda: f(q, k, v).block_until_ready(), repeat=5)
    flops = 4 * B * H * S * S * hd / 2
    o1 = ops.flash_attention(q, k, v, block_q=128, block_k=128)
    err = float(jnp.max(jnp.abs(o1 - f(q, k, v))))
    emit(f"kernel_flash_attention_ref_vs_{mode}", us,
         f"{flops/us/1e3:.1f}GFLOPs_kernel_maxerr_{err:.1e}")

    n, L = 8192, 512
    lid = jax.random.randint(ks[0], (n, 6), -1, L).astype(jnp.int32)
    rates = jax.random.uniform(ks[1], (n,)) * 1e9
    queue = jnp.zeros((L,))
    cap = jnp.full((L,), 1e11)
    g = jax.jit(lambda: ref.linkload_ref(lid, rates, L, 400e3, 1600e3, 0.2, queue, cap, 1e-5))
    g()[0].block_until_ready()
    _, us = timed(lambda: g()[0].block_until_ready(), repeat=10)
    l1, _, _ = ops.linkload(lid, rates, queue, cap, n_links=L)
    err = float(jnp.max(jnp.abs(l1 - g()[0])))
    emit(f"kernel_linkload_ref_vs_{mode}", us,
         f"{n*6/us:.0f}Mupdates/s_kernel_maxerr_{err:.1e}")
