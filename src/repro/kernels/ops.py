"""Jit'd kernel entry points: the compiled Pallas kernel on TPU, the Pallas
interpreter on the CPU backend (tests), and an error on any other backend."""
from __future__ import annotations

import jax

from repro.kernels import flash_attention as _fa, linkload as _ll
from repro.kernels import ref


def interpret_mode() -> bool:
    """False on TPU (compiled kernel), True on the CPU backend (interpreter)."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"no Pallas kernel path for backend {backend!r}")
    return backend == "cpu"


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=128, block_k=128):
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret_mode(),
    )


def linkload(link_ids, rates, queue, capacity, **kw):
    return _ll.linkload(link_ids, rates, queue, capacity,
                        interpret=interpret_mode(), **kw)


flash_attention_ref = ref.flash_attention_ref
linkload_ref = ref.linkload_ref
