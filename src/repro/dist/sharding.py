"""FSDP + TP sharding rules for the production meshes.

One shape-driven rule serves all ten architectures: for every parameter
matrix the largest dim divisible by the ``model`` axis takes tensor
parallelism and the largest *remaining* dim divisible by the ``data`` axis
takes FSDP — so no big matrix is ever fully replicated, and every
assignment divides evenly (validated against abstract 16x16 meshes in
tests/test_system.py without touching devices).  Vectors (norms, biases)
stay replicated; the ``pod`` axis is deliberately never used for params —
across pods the model is pure data-parallel and grad sync goes through
``dist.collectives`` (or one fat XLA all-reduce in the baseline mode).

Optimizer moments mirror param specs by construction (the dryrun builds
them with the same function), giving ZeRO-style sharded optimizer state.
"""
from __future__ import annotations

import numpy as np
from jax import tree as jtree
from jax.sharding import PartitionSpec as P


def _axis_sizes(mesh) -> dict:
    return dict(mesh.shape)


def _trim(assign: list) -> P:
    while assign and assign[-1] is None:
        assign.pop()
    return P(*assign)


def _matrix_spec(shape, data: int | None, model: int | None) -> P:
    if len(shape) < 2:
        return P()  # norms / biases / scalars: replicate
    order = sorted(range(len(shape)), key=lambda i: (-shape[i], i))
    assign: list = [None] * len(shape)
    mi = next((i for i in order if model and shape[i] % model == 0), None)
    if mi is not None:
        assign[mi] = "model"
    di = next((i for i in order if i != mi and data and shape[i] % data == 0), None)
    if di is not None:
        assign[di] = "data"
    return _trim(assign)


def param_specs(params, mesh):
    """PartitionSpec pytree for a parameter tree (arrays or ShapeDtypeStructs),
    same structure as ``params``."""
    sizes = _axis_sizes(mesh)
    data, model = sizes.get("data"), sizes.get("model")
    return jtree.map(lambda leaf: _matrix_spec(np.shape(leaf), data, model), params)


def batch_specs(batch, mesh):
    """Inputs shard their leading (global batch) dim over pod x data."""
    sizes = _axis_sizes(mesh)
    dp = tuple(a for a in ("pod", "data") if a in sizes)
    n = int(np.prod([sizes[a] for a in dp], dtype=np.int64)) if dp else 1

    def spec(leaf):
        shape = np.shape(leaf)
        if not shape or n <= 1 or shape[0] % n:
            return P()
        return P(dp if len(dp) > 1 else dp[0])

    return jtree.map(spec, batch)


def cache_specs(cache, mesh):
    """KV / recurrent caches: batch dim over pod x data, plus TP on the
    first non-batch dim the model axis divides (heads, typically)."""
    sizes = _axis_sizes(mesh)
    model = sizes.get("model")
    dp = tuple(a for a in ("pod", "data") if a in sizes)
    n = int(np.prod([sizes[a] for a in dp], dtype=np.int64)) if dp else 1

    def spec(leaf):
        shape = np.shape(leaf)
        assign: list = [None] * len(shape)
        if shape and n > 1 and shape[0] % n == 0:
            assign[0] = dp if len(dp) > 1 else dp[0]
        for i in range(1, len(shape)):
            if model and shape[i] % model == 0:
                assign[i] = "model"
                break
        return _trim(assign)

    return jtree.map(spec, cache)
