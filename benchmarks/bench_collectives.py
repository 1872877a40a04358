"""§Perf bench: SeqBalance multi-path grad sync vs stock XLA all-reduce —
collective op counts/bytes from lowered HLO on an 8-device subprocess.

The child only counts HLO bytes on 8 virtual CPU devices, so it runs with
``JAX_PLATFORMS=cpu``: it must never try to open a TPU that the parent
process already holds."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import PERF, emit

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_CODE = textwrap.dedent("""
    import json, re
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.dist.collectives import PathPlan, seqbalance_all_reduce
    from repro.launch.dryrun import collective_bytes

    mesh = jax.make_mesh((8,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
    x = jnp.zeros((8, 1 << 20), jnp.float32)  # 4 MB bucket per device

    def seq(x):
        return seqbalance_all_reduce(x, "pod", PathPlan(n_chunks=4, wire_dtype="%s"))

    def base(x):
        return jax.lax.psum(x, "pod")

    out = {}
    for name, fn in (("seqbalance", seq), ("baseline", base)):
        g = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("pod"), out_specs=P("pod")))
        hlo = g.lower(x).compile().as_text()
        out[name] = collective_bytes(hlo)
    print(json.dumps(out))
""")


def bench_collectives(fast=True):
    for wire in ("float32", "bfloat16"):
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = SRC
        r = subprocess.run([sys.executable, "-c", _CODE % wire], capture_output=True,
                           text=True, env=env, timeout=600)
        if r.returncode != 0:
            raise RuntimeError(f"collectives_{wire} child exited {r.returncode}:"
                               f"\n{r.stderr[-2000:]}")
        res = json.loads(r.stdout.strip().splitlines()[-1])
        sb, bl = res["seqbalance"], res["baseline"]
        emit(f"collectives_seqbalance_{wire}", 0.0,
             f"permute_ops_{sb['count']}_bytes_{sb['total']:.3e}")
        emit(f"collectives_baseline_{wire}", 0.0,
             f"allreduce_ops_{bl['count']}_bytes_{bl['total']:.3e}")
        if bl["total"]:
            emit(f"collectives_byte_ratio_{wire}", 0.0,
                 f"seq/base_{sb['total']/bl['total']:.2f}")
        # machine-readable record for BENCH_netsim.json (counts/bytes only —
        # the CI gate stays timing-free for this bench)
        PERF.setdefault("collectives", {})[wire] = {
            "seqbalance_ops": sb["count"], "seqbalance_bytes": sb["total"],
            "baseline_ops": bl["count"], "baseline_bytes": bl["total"],
            "byte_ratio": (sb["total"] / bl["total"]) if bl["total"] else None,
        }
