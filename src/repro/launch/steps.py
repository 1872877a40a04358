"""Step builders: train_step / prefill_step / serve_step for any arch.

Two grad-sync modes:
  * "xla"        — paper-baseline: params replicated over the pod axis,
    XLA inserts one fat all-reduce per gradient (the single-path elephant
    flow SeqBalance's motivation describes).
  * "seqbalance" — the pod-axis gradient sync runs through
    dist.collectives.seqbalance_all_reduce inside a partial-manual
    shard_map (manual over "pod", auto over data/model): N chunk rings on
    distinct directions, congestion-table-aware.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import collectives
from repro.models import model
from repro.train import optimizer as opt_mod


def make_train_step(cfg, opt_cfg: opt_mod.AdamWConfig, mesh=None, grad_sync: str = "xla",
                    plan: collectives.PathPlan | None = None):
    has_pod = mesh is not None and "pod" in mesh.axis_names and mesh.shape["pod"] > 1

    if grad_sync == "seqbalance" and has_pod:
        def train_step(state, batch):
            def per_pod(params, batch_shard):
                def lf(p):
                    return model.loss_fn(p, cfg, batch_shard)

                (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params)
                grads = collectives.tree_all_reduce_mean(grads, "pod", plan)
                loss = collectives.baseline_all_reduce(loss, "pod") / jax.lax.axis_size("pod")
                return loss, grads

            # manual over pod only; data/model stay auto (pjit semantics)
            pp = jax.shard_map(
                per_pod,
                mesh=mesh,
                in_specs=(P(), P("pod")),
                out_specs=(P(), P()),
                axis_names={"pod"},
                check_vma=False,
            )
            loss, grads = pp(state["params"], batch)
            new_p, new_opt, om = opt_mod.update(grads, state["opt"], state["params"], opt_cfg)
            return {"params": new_p, "opt": new_opt}, {"loss": loss, **om}

        return train_step

    def train_step(state, batch):
        def lf(p):
            return model.loss_fn(p, cfg, batch)

        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(state["params"])
        new_p, new_opt, om = opt_mod.update(grads, state["opt"], state["params"], opt_cfg)
        return {"params": new_p, "opt": new_opt}, {"loss": loss, **om}

    return train_step


def make_prefill_step(cfg, max_len: int):
    def prefill_step(params, batch):
        return model.prefill(params, cfg, batch, max_len)

    return prefill_step


def make_serve_step(cfg):
    """One decode step: greedy next token against the KV cache."""

    def serve_step(params, tokens, cache):
        logits, cache = model.decode_step(params, cfg, tokens, cache)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_tok, logits, cache

    return serve_step


def init_state(key, cfg):
    params = model.init_params(key, cfg)
    return {"params": params, "opt": opt_mod.init(params)}
