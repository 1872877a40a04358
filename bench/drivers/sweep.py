"""Closed-loop study driver for ``sweep`` traffic: vmapped batches of traces
through ``repro.netsim.sweep.run_batch``, the simulator's entry point for
paper-style (scheme x workload x load x seed) studies.

Set-up generates the trace pool (the same traces in every run, their lanes
in each batch ordered by the run's seed), warms one batch of every shape
bucket the pool will use, and runs one batch more.  The window
sends a batch, waits for its results on the host, and sends the next: the
sort, window planning, padding, stacking, transfer, dispatch, result fetch
and spill retries of ``run_batch`` all run inside it; trace generation does
not.  After the window, a sample of the sims it finished is compared with
the plain reference (``bench/reference/fluid.py``), and every sim it
finished with the configuration's guarantees over the whole horizon.
"""
from __future__ import annotations

import time

import numpy as np

from bench.harness import cost, traffic as gen_traffic


def _topology(topology, fabric: dict):
    kind = fabric["kind"]
    if kind == "leaf_spine":
        return topology.leaf_spine(
            fabric["n_leaf"], fabric["n_spine"], fabric["hosts_per_leaf"],
            fabric["link_bw"], host_bw=fabric.get("host_bw"),
            base_rtt_s=fabric["base_rtt_s"])
    if kind == "three_tier":
        return topology.three_tier(
            n_tor=fabric["n_tor"], n_agg=fabric["n_agg"], n_core=fabric["n_core"],
            hosts_per_tor=fabric["hosts_per_tor"], bw_tor_agg=fabric["bw_tor_agg"],
            bw_agg_core=fabric["bw_agg_core"], host_bw=fabric["host_bw"],
            base_rtt_s=fabric["base_rtt_s"])
    raise ValueError(f"unknown fabric kind {kind!r}")


def _shape_key(sweep, compact, topo, cfg, traces) -> tuple:
    """The (F_pad, W, A, B) buckets ``run_batch`` will dispatch for a batch,
    in its dispatch order, worked out with the program's own planning
    functions."""
    prepped = [compact.sort_trace(t) for t in traces]
    groups: dict[int, list] = {}
    for arrays, _, n in prepped:
        groups.setdefault(sweep._f_bucket(n), []).append(arrays)
    key = []
    for f_pad, arrays in groups.items():
        w = min(sweep.plan_window(topo, [], scheme=cfg.scheme, sorted_arrays=arrays), f_pad)
        a = max(compact.max_admits_per_step(x[1], x[5], cfg.dt) for x in arrays)
        key.append((f_pad, w, min(sweep._round_up(a, 32), f_pad), len(arrays)))
    return tuple(key)


class Study:
    """One cell's closed-loop study: ``setup``, ``window``, ``check``."""

    def __init__(self, cell, seed: int, log):
        self.cell, self.seed, self.log = cell, int(seed), log
        self.t = cell.traffic
        self.sim = self.t["sim"]

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        self.build_pool()
        self.warm()

    def build_pool(self, n: int | None = None) -> None:
        """The run's traces in batches: pool slot i always holds the trace
        of work seed i, and the run's seed orders the lanes."""
        from repro.netsim import compact, engine, sweep, topology, workloads
        from repro.netsim.dcqcn import DCQCNParams

        self.sweep, self.compact = sweep, compact
        fabric = self.cell.config["fabric"]
        self.topo = _topology(topology, fabric)
        self.cfg = engine.SimConfig(
            scheme=self.sim["scheme"], n_sub=self.sim.get("n_sub", 4), dt=self.sim["dt"],
            duration_s=self.sim["horizon_s"], dataplane=self.sim["dataplane"],
            uplink_sample_every=self.sim["uplink_sample_every"],
            dcqcn=DCQCNParams(**self.cell.config["dcqcn"]))
        self.n_steps = int(round(self.cfg.duration_s / self.cfg.dt))
        self.chunk = cost.chunk_steps(self.cfg.chunk_steps, self.cfg.uplink_sample_every,
                                      self.n_steps)
        B = self.t["batch"]
        order = gen_traffic.run_order(self.seed, self.t["pool_batches"], B)[: n or None]
        # pool slot i holds the same trace (work seed i) in every run; the
        # run's seed orders the lanes within each batch
        self.batches = []
        for slots in order:
            batch = []
            for i in slots:
                f = gen_traffic.poisson_flows(self.t["generator"], self.topo.n_hosts,
                                              self.topo.hosts_per_leaf, i)
                batch.append(workloads.Trace(valid=np.ones(f["sizes"].size, bool), **f))
            self.batches.append(batch)
        self.pool = [t for b in self.batches for t in b]
        self.keys = [_shape_key(sweep, compact, self.topo, self.cfg, b) for b in self.batches]

    def warm(self) -> None:
        """Run one batch of every shape bucket of the pool, then one more."""
        warm = {}
        for i, k in enumerate(self.keys):
            warm.setdefault(k, i)
        self.log(f"pool: {len(self.pool)} traces in {len(self.batches)} batches of "
                 f"{self.t['batch']}, "
                 f"{len(warm)} shape buckets (F_pad, W, A, B): {sorted(warm)}")
        for i in sorted(warm.values()):
            self.sweep.run_batch(self.topo, self.cfg, self.batches[i])
        # one batch more once every program is built
        self.sweep.run_batch(self.topo, self.cfg, self.batches[0])

    # ------------------------------------------------------------ window
    def window(self, seconds: float, span) -> dict:
        """Closed loop for ``seconds``; returns the cell's record."""
        batches, done = [], []
        i = 0
        t0 = time.perf_counter()
        while True:
            b = i % len(self.batches)
            traces = self.batches[b]
            with span("bench.batch"):
                res, outs = self.sweep.run_batch(self.topo, self.cfg, traces)
            exits = [cost.exit_steps(r.finish, self.cfg.dt, self.chunk, self.n_steps)
                     for r in res]
            batches.append(dict(exits=exits, groups=self.keys[b]))
            done += list(zip(traces, res, outs))
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        import jax

        jax.block_until_ready([d[2] for d in done[-len(traces):]])
        t1 = time.perf_counter()
        wall = t1 - t0
        steps = len(done) * self.n_steps
        self.done = done
        self.log(f"window exits: slowest sim's exit step per batch "
                 f"{[max(b['exits']) for b in batches]} of {self.n_steps}")
        return dict(
            window_s=wall, batches=batches, sims=len(done),
            units=[dict(steps=max(b["exits"]), dispatches=[(g[1], g[3]) for g in b["groups"]])
                   for b in batches],
            pool_reuses=max(0, i - len(self.batches)),
            n_steps=self.n_steps, chunk=self.chunk,
            n_sub=self.cfg.n_sub, n_fabric_hops=self.topo.n_fabric_hops,
            n_links=self.topo.n_links,
            metrics=dict(sim_steps_per_s=steps / wall))

    # ------------------------------------------------------------ check
    def sample(self) -> list:
        """A seeded sample of the window's finished sims, with the one of
        most flows in it; drops the rest of the window's outputs."""
        chk = self.t["check"]
        rng = np.random.default_rng([self.seed, 7])
        n = len(self.done)
        largest = max(range(n), key=lambda j: int(self.done[j][0].valid.sum()))
        others = [j for j in rng.permutation(n).tolist() if j != largest]
        picked = [self.done[j] for j in [largest] + others[: chk["sims"] - 1]]
        self.done = None
        return [dict(trace=t, finish=np.asarray(r.finish),
                     goodput=np.asarray(o.goodput_total[: chk["steps"]]),
                     max_queue=np.asarray(o.max_queue[: chk["steps"]]))
                for t, r, o in picked]

    def reference(self, sample: list, precision: str = "f32") -> list:
        """The plain reference over the sample's traces, first ``check.steps``
        steps, in the same form as ``sample``'s entries; ``check.ref_batch``
        sims at a time, so that it fits beside what the process holds."""
        import jax
        import jax.numpy as jnp

        from bench.reference import fluid

        T = self.t["check"]["steps"]
        fab = fluid.build_fabric(self.cell.config["fabric"])
        params = fluid.params_from(self.cell.config, self.t)
        run = fluid.batched(fab, params, T, self.cfg.uplink_sample_every, precision)
        k = self.t["check"].get("ref_batch", len(sample))
        out = []
        for i in range(0, len(sample), k):
            part = sample[i:i + k]
            F = fluid.pad_to(max(int(d["trace"].valid.sum()) for d in part))

            def pad(a, fill):
                return np.concatenate([a, np.full(F - a.size, fill, a.dtype)])

            cols = [jnp.asarray(np.stack([pad(np.asarray(getattr(d["trace"], name)), fill)
                                          for d in part]))
                    for name, fill in (("sizes", 1.0), ("arrivals", np.inf), ("src", 0),
                                       ("dst", 0), ("flow_id", 0), ("valid", False))]
            r = jax.device_get(run(*cols))
            out += [dict(trace=d["trace"], goodput=np.asarray(r.goodput[j]),
                         max_queue=np.asarray(r.max_queue[j]),
                         finish=np.asarray(r.finish[j][: int(d["trace"].valid.sum())]))
                    for j, d in enumerate(part)]
        return out

    def check(self, control: str | None = None) -> list[tuple[str, float, float]]:
        """Compare a seeded sample of the window's sims with the reference
        over the first ``check.steps`` steps, and every sim of the window
        with the configuration's guarantees over the whole horizon; returns
        (name, value, limit) and sets ``failed``, the sims that spilled or
        broke a guarantee.  ``control`` puts the reference at that lower
        precision in the program's place for the first comparison (the
        control of it); the whole-horizon numbers are the program's."""
        chk = self.t["check"]
        whole = []
        if control is None:
            whole, self.failed = horizon_check(self.done, self.cfg.dt, self.n_steps, chk)
        sample = self.sample()
        ref = self.reference(sample)
        got = sample if control is None else self.reference(sample, control)
        for k in sorted({min(50, chk["steps"]), chk["steps"]}):
            gaps = [traj_gap(g, r, k, self.kmin) for g, r in zip(got, ref)]
            self.log(f"check detail: traj_gap over {k} steps per sim {gaps}")
        return compare(got, ref, chk["steps"], self.cfg.dt, self.kmin, chk) + whole

    @property
    def kmin(self) -> float:
        return float(self.cell.config["dcqcn"]["kmin_bytes"])


def traj_gap(got: dict, ref: dict, T: int, kmin: float) -> float:
    """Widest gap over the first T steps between two runs' total goodput
    (as a share of the reference's peak) and deepest queue (as a share of
    the ECN threshold kmin)."""
    gp, gp_r = (np.asarray(x["goodput"][:T], np.float64) for x in (got, ref))
    mq, mq_r = (np.asarray(x["max_queue"][:T], np.float64) for x in (got, ref))
    return max(float(np.max(np.abs(gp - gp_r))) / max(float(np.max(np.abs(gp_r))), 1.0),
               float(np.max(np.abs(mq - mq_r))) / kmin)


def compare(got: list, ref: list, T: int, dt: float, kmin: float, chk: dict):
    """The numbers that decide ``correct``, each with its limit:

    * ``traj_gap``: the widest ``traj_gap`` over the sampled sims;
    * ``finish_mismatch``: flows that the reference completes within the
      first T steps and the run under test completes at another time."""
    gap, mism = 0.0, 0
    for g, r in zip(got, ref):
        gap = max(gap, traj_gap(g, r, T, kmin))
        f_r = np.asarray(r["finish"], np.float64)
        f_g = np.asarray(g["finish"], np.float64)
        if f_g.size != f_r.size:  # an answer for another trace: nothing matches
            f_g = np.full(f_r.size, np.nan)
        due = np.isfinite(f_r) & (f_r <= (T + 0.5) * dt)
        mism += int(np.sum(f_g[due] != f_r[due]))
    return [("traj_gap", gap, chk["traj_gap_limit"]),
            ("finish_mismatch", float(mism), chk["finish_mismatch_limit"])]


def sim_horizon(trace, res, outs, dt: float, n_steps: int) -> tuple[int, float]:
    """One sim against the guarantee that every admitted byte is delivered
    within the horizon of ``n_steps``: the flows with no finish time inside
    it, and the gap between the bytes its goodput delivered (``goodput_total
    * dt / 8`` summed over the steps it reports) and the flows' sizes, as a
    share of the sizes."""
    valid = np.asarray(trace.valid)
    gp = np.asarray(outs.goodput_total, np.float64)
    f = np.asarray(res.finish, np.float64)
    if f.size != valid.size:  # an answer for another trace: no flow is done
        f = np.full(valid.size, np.inf)
    unfinished = int(np.sum(~(f[valid] <= (n_steps + 0.5) * dt)))
    sizes = float(np.asarray(trace.sizes, np.float64)[valid].sum())
    gap = abs(float(gp.sum()) * dt / 8.0 - sizes) / max(sizes, 1.0)
    return unfinished, gap


def horizon_check(done: list, dt: float, n_steps: int, chk: dict):
    """Every (trace, result, outputs) of the window against ``sim_horizon``:
    the numbers ``unfinished`` (flows, summed) and ``bytes_gap`` (the
    widest), each with its limit, and the count of sims that spilled or
    broke either limit."""
    total, widest, failed = 0, 0.0, 0
    for trace, res, outs in done:
        n, gap = sim_horizon(trace, res, outs, dt, n_steps)
        total, widest = total + n, max(widest, gap)
        failed += int(res.spill_steps > 0 or n > chk["unfinished_limit"]
                      or gap > chk["bytes_gap_limit"])
    return [("unfinished", float(total), chk["unfinished_limit"]),
            ("bytes_gap", widest, chk["bytes_gap_limit"])], failed
