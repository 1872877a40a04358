import os

if __name__ == "__main__":
    # the CLI compiles on 512 virtual CPU devices; importing this module
    # (collective_bytes) leaves the caller's backend and flags alone
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512")))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each live cell this lowers the real step function (train_step for
train_4k, prefill_step for prefill_32k, serve_step for decode shapes) with
production shardings on the 16x16 single-pod mesh AND the 2x16x16
multi-pod mesh, compiles it, and records:

  * memory_analysis  (bytes per device — proves the cell fits)
  * cost_analysis    (HLO flops / bytes accessed — roofline numerator)
  * collective bytes (parsed from the partitioned HLO: all-gather,
    all-reduce, reduce-scatter, all-to-all, collective-permute)

Results append to benchmarks/artifacts/dryrun_<mesh>.json, which
benchmarks/roofline.py turns into EXPERIMENTS.md §Roofline.

Usage:
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both [--grad-sync seqbalance]
"""
import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import registry  # noqa: E402
from repro.dist import collectives, sharding  # noqa: E402
from repro.launch import mesh as mesh_mod, steps  # noqa: E402
from repro.models import model  # noqa: E402
from repro.train import optimizer as opt_mod  # noqa: E402

COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(f64|f32|f16|bf16|f8\w*|s64|s32|s16|s8|u64|u32|u16|u8|pred|c64|c128)\[([0-9,]*)\]")


def _first_shape_bytes(line: str) -> int:
    """Bytes of the result shape on an HLO op line (covers tuple results)."""
    total = 0
    head = line.split("=", 1)[0] if "=" in line else line
    # result type annotation sits right after '=' in HLO text: take the lhs
    # of the op call on the rhs instead (robust across printers):
    rhs = line.split("=", 1)[1] if "=" in line else line
    m = _SHAPE_RE.findall(rhs.split("(", 1)[0])
    for dt, dims in m:
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES.get(dt[:4].rstrip("["), _DTYPE_BYTES.get(dt, 4))
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in partitioned HLO."""
    out = {k: 0 for k in COLLECTIVE_OPS}
    out["count"] = 0
    for line in hlo_text.splitlines():
        ls = line.strip()
        if "=" not in ls:
            continue
        rhs = ls.split("=", 1)[1].strip()
        body = rhs.split("(", 1)[0]
        for op in COLLECTIVE_OPS:
            # match op name at the start of the call (after shape annotation)
            if re.search(rf"\b{op}(-start|-done)?\(", rhs) or body.endswith(op):
                if f"{op}-done" in rhs:
                    continue  # avoid double counting async pairs
                out[op] += _first_shape_bytes(ls)
                out["count"] += 1
                break
    out["total"] = sum(out[k] for k in COLLECTIVE_OPS)
    return out


def _spec_tree_for_state(state_shapes, mesh):
    pspecs = sharding.param_specs(state_shapes["params"], mesh)
    opt = state_shapes["opt"]
    opt_specs = opt_mod.AdamWState(
        step=P(),
        mu=sharding.param_specs(opt.mu, mesh),
        nu=sharding.param_specs(opt.nu, mesh),
    )
    return {"params": pspecs, "opt": opt_specs}


def _named(tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def _lower_cell(cfg, shape, mesh, grad_sync):
    """Lower + compile one (cfg, shape) on ``mesh``; returns compiled."""
    batch_sds = registry.input_specs(cfg, shape)
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
    if shape.kind == "train":
        state_sds = jax.eval_shape(lambda k: steps.init_state(k, cfg), key_sds)
        specs = _spec_tree_for_state(state_sds, mesh)
        b_specs = sharding.batch_specs(batch_sds, mesh)
        plan = collectives.PathPlan(n_chunks=4) if grad_sync == "seqbalance" else None
        step_fn = steps.make_train_step(cfg, opt_mod.AdamWConfig(), mesh, grad_sync, plan)
        jf = jax.jit(
            step_fn,
            in_shardings=(_named(specs, mesh), _named(b_specs, mesh)),
            out_shardings=(_named(specs, mesh), None),
            donate_argnums=(0,),
        )
        with mesh:
            return jf.lower(state_sds, batch_sds).compile()
    if shape.kind == "prefill":
        params_sds = jax.eval_shape(lambda k: model.init_params(k, cfg), key_sds)
        pspecs = sharding.param_specs(params_sds, mesh)
        b_specs = sharding.batch_specs(batch_sds, mesh)
        step_fn = steps.make_prefill_step(cfg, shape.seq_len)
        out_sds = jax.eval_shape(step_fn, params_sds, batch_sds)
        c_specs = sharding.cache_specs(out_sds[1], mesh)  # shard the cache!
        jf = jax.jit(
            step_fn,
            in_shardings=(_named(pspecs, mesh), _named(b_specs, mesh)),
            out_shardings=(None, _named(c_specs, mesh)),
        )
        with mesh:
            return jf.lower(params_sds, batch_sds).compile()
    # decode
    params_sds = jax.eval_shape(lambda k: model.init_params(k, cfg), key_sds)
    pspecs = sharding.param_specs(params_sds, mesh)
    cache_sds = jax.eval_shape(
        lambda: model.init_cache(None, cfg, shape.global_batch, shape.seq_len)
    )
    c_specs = sharding.cache_specs(cache_sds, mesh)
    tok_sds = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    t_specs = sharding.batch_specs({"tokens": tok_sds}, mesh)["tokens"]
    step_fn = steps.make_serve_step(cfg)
    jf = jax.jit(
        step_fn,
        in_shardings=(_named(pspecs, mesh), _named(t_specs, mesh), _named(c_specs, mesh)),
        out_shardings=(None, None, _named(c_specs, mesh)),
        donate_argnums=(2,),
    )
    with mesh:
        return jf.lower(params_sds, tok_sds, cache_sds).compile()


def _cell_costs(compiled) -> dict:
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    get = (lambda k: float(cost.get(k, 0.0))) if isinstance(cost, dict) else (
        lambda k: float(getattr(cost, k.replace(" ", "_"), 0.0) or 0.0))
    return {
        "flops": get("flops"),
        "bytes": get("bytes accessed"),
        "coll": collective_bytes(compiled.as_text())["total"],
    }


def _depth_cfg(cfg, d: int):
    """Config with ``d`` superblocks (plus whisper's encoder scaled along)."""
    from repro.models.transformer import block_program

    _, _, n_super, _ = block_program(cfg)
    lps = cfg.n_layers // max(n_super, 1)
    kw = {"n_layers": d * lps}
    if cfg.is_encoder_decoder:
        kw["n_encoder_layers"] = d
    return cfg.replace(**kw), n_super, (cfg.n_layers % max(lps, 1)) / max(lps, 1)


def extrapolated_costs(cfg, shape, mesh, grad_sync) -> dict:
    """XLA's cost analysis counts a while-loop (scan) body ONCE; the true
    per-step cost is cost(outside) + n_super * cost(body).  Lower the model
    at depths 1 and 2 and extrapolate: cost(n) = c1 + (n-1+trail)*(c2-c1).
    (Methodology recorded in EXPERIMENTS.md §Dry-run.)"""
    cfg1, n_super, trail = _depth_cfg(cfg, 1)
    cfg2, _, _ = _depth_cfg(cfg, 2)
    c1 = _cell_costs(_lower_cell(cfg1, shape, mesh, grad_sync))
    c2 = _cell_costs(_lower_cell(cfg2, shape, mesh, grad_sync))
    scale = (n_super - 1) + trail
    return {
        k + "_x": c1[k] + scale * (c2[k] - c1[k]) for k in ("flops", "bytes", "coll")
    }


def run_cell(arch: str, shape_name: str, mesh, mesh_label: str, grad_sync: str = "xla",
             remat: str = "dots") -> dict:
    cfg = registry.get_config(arch).replace(remat=remat)
    shape = registry.get_shape(shape_name)
    ok, why = registry.cell_is_supported(arch, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_label, "status": "SKIP",
                "reason": why}
    t0 = time.time()
    batch_sds = registry.input_specs(cfg, shape)
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)

    if shape.kind == "train":
        state_sds = jax.eval_shape(lambda k: steps.init_state(k, cfg), key_sds)
        specs = _spec_tree_for_state(state_sds, mesh)
        b_specs = sharding.batch_specs(batch_sds, mesh)
        plan = collectives.PathPlan(n_chunks=4) if grad_sync == "seqbalance" else None
        step_fn = steps.make_train_step(cfg, opt_mod.AdamWConfig(), mesh, grad_sync, plan)
        jf = jax.jit(
            step_fn,
            in_shardings=(_named(specs, mesh), _named(b_specs, mesh)),
            out_shardings=(_named(specs, mesh), None),
            donate_argnums=(0,),
        )
        with mesh:
            lowered = jf.lower(state_sds, batch_sds)
    elif shape.kind == "prefill":
        params_sds = jax.eval_shape(lambda k: model.init_params(k, cfg), key_sds)
        pspecs = sharding.param_specs(params_sds, mesh)
        b_specs = sharding.batch_specs(batch_sds, mesh)
        step_fn = steps.make_prefill_step(cfg, shape.seq_len)
        out_sds = jax.eval_shape(step_fn, params_sds, batch_sds)
        c_specs = sharding.cache_specs(out_sds[1], mesh)  # shard the cache!
        jf = jax.jit(
            step_fn,
            in_shardings=(_named(pspecs, mesh), _named(b_specs, mesh)),
            out_shardings=(None, _named(c_specs, mesh)),
        )
        with mesh:
            lowered = jf.lower(params_sds, batch_sds)
    else:  # decode
        params_sds = jax.eval_shape(lambda k: model.init_params(k, cfg), key_sds)
        pspecs = sharding.param_specs(params_sds, mesh)
        cache_sds = jax.eval_shape(
            lambda: model.init_cache(None, cfg, shape.global_batch, shape.seq_len)
        )
        c_specs = sharding.cache_specs(cache_sds, mesh)
        tok_sds = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        t_specs = sharding.batch_specs({"tokens": tok_sds}, mesh)["tokens"]
        step_fn = steps.make_serve_step(cfg)
        jf = jax.jit(
            step_fn,
            in_shardings=(_named(pspecs, mesh), _named(t_specs, mesh), _named(c_specs, mesh)),
            out_shardings=(None, None, _named(c_specs, mesh)),
            donate_argnums=(2,),
        )
        with mesh:
            lowered = jf.lower(params_sds, tok_sds, cache_sds)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    try:  # depth-extrapolated costs (scan bodies count once in XLA's CA)
        xcosts = extrapolated_costs(cfg, shape, mesh, grad_sync)
    except Exception as e:
        xcosts = {"flops_x": -1.0, "bytes_x": -1.0, "coll_x": -1.0,
                  "x_error": f"{type(e).__name__}: {e}"}

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    def g(obj, name, default=0.0):
        try:
            v = getattr(obj, name, None)
            if v is None and hasattr(obj, "get"):
                v = obj.get(name, default)
            return float(v) if v is not None else default
        except Exception:
            return default

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_label, "status": "OK",
        "grad_sync": grad_sync, "remat": remat,
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "flops": g(cost, "flops") if not isinstance(cost, dict) else float(cost.get("flops", 0.0)),
        "bytes_accessed": g(cost, "bytes accessed")
        if not isinstance(cost, dict) else float(cost.get("bytes accessed", 0.0)),
        "argument_size_bytes": g(mem, "argument_size_in_bytes"),
        "output_size_bytes": g(mem, "output_size_in_bytes"),
        "temp_size_bytes": g(mem, "temp_size_in_bytes"),
        "peak_bytes": g(mem, "peak_memory_in_bytes"),
        "collectives": coll,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        **xcosts,
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--grad-sync", default="xla", choices=["xla", "seqbalance"])
    ap.add_argument("--remat", default="dots", choices=["none", "dots", "full"])
    ap.add_argument("--out", default="benchmarks/artifacts")
    args = ap.parse_args()

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", mesh_mod.make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16", mesh_mod.make_production_mesh(multi_pod=True)))

    cells = []
    if args.all:
        for arch, shape, ok, why in registry.list_cells(include_skipped=True):
            cells.append((arch, shape.name))
    else:
        cells.append((args.arch, args.shape))

    os.makedirs(args.out, exist_ok=True)
    for mesh_label, mesh in meshes:
        path = os.path.join(args.out, f"dryrun_{mesh_label}_{args.grad_sync}.json")
        existing = {}
        if os.path.exists(path):
            for r in json.load(open(path)):
                existing[(r["arch"], r["shape"])] = r
        for arch, shape_name in cells:
            if (arch, shape_name) in existing and existing[(arch, shape_name)]["status"] in ("OK", "SKIP"):
                print(f"[cached] {mesh_label} {arch} {shape_name}")
                continue
            print(f"[dryrun] {mesh_label} {arch} {shape_name} ...", flush=True)
            try:
                rec = run_cell(arch, shape_name, mesh, mesh_label, args.grad_sync, args.remat)
            except Exception as e:
                rec = {"arch": arch, "shape": shape_name, "mesh": mesh_label,
                       "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
            existing[(arch, shape_name)] = rec
            json.dump(list(existing.values()), open(path, "w"), indent=1)
            status = rec["status"]
            extra = ""
            if status == "OK":
                extra = (f" flops={rec['flops']:.3e} coll={rec['collectives']['total']:.3e}B"
                         f" peak={rec['peak_bytes']:.3e}B compile={rec['compile_s']}s")
            print(f"[{status}] {mesh_label} {arch} {shape_name}{extra}", flush=True)


if __name__ == "__main__":
    main()
