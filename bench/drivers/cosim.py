"""Closed-loop study driver for ``cosim`` traffic: campaigns of a training
job's ring all-reduce over a fabric that loses a switch, through
``repro.dist.cosim.run_cosim``, the simulator's entry point for fault
studies.

Each epoch of a campaign renders the ring schedule under the plan in force
(ECMP, flow ids steered onto the planned paths), runs one B = 1 sim with
the epoch's link capacities as a traced operand, reports congestion to the
planner and applies the next plan.  Set-up builds the fabric and runs one
campaign, then one more.  The window runs whole campaigns back to back,
each inside the timed span ``bench.batch``.  Every campaign does the same
work: pool slot i runs with work seed i, which moves flow ids and not
paths, and the run's seed orders the pool.  After the window, a seeded
sample of its epochs, always holding one in which the switch dies, is
compared with the plain reference given the epoch's capacities, and every
epoch with the configuration's guarantees.  The capacities, the flows'
paths and the quarantine a plan should hold are worked out here from the
reference's own link tables and the configuration's fault, not read from
the program.
"""
from __future__ import annotations

import time

import numpy as np

from bench.drivers.sweep import _topology, traj_gap
from bench.harness import cost

ROCE_DPORT = 4791


def switch_links(fab, switch: int) -> np.ndarray:
    """Link ids of the reference's three-tier fabric that die with
    aggregation switch ``switch``: its ToR uplinks, both directions of its
    core links, and its ToR downlinks."""
    assert fab.kind == "three_tier", fab.kind
    T, A, C = fab.layout
    t, c = np.arange(T), np.arange(C)
    return np.concatenate([t * A + switch, T * A + switch * C + c,
                           T * A + A * C + c * A + switch,
                           T * A + 2 * A * C + switch * T + t])


def ecmp_paths(fab, src, dst, fid) -> np.ndarray:
    """Path of each flow under plain ECMP, by the reference's own hash of
    the flow's five-tuple (source port from the flow id)."""
    from bench.reference import fluid

    u32 = fluid._u32
    sport = u32(0xB000) + fluid.fmix32(u32(fid)) % u32(0x3FFF)
    h = fluid.hash_tuple(u32(src), u32(dst), sport, u32(ROCE_DPORT))
    return np.asarray(h % u32(fab.n_paths)).astype(np.int32)


def flow_links(fab, src, dst, path) -> np.ndarray:
    """i32[F, n_fabric_hops + 2] links of each flow (-1: none), host NICs
    included."""
    from bench.reference import fluid

    src, dst = np.asarray(src, np.int64), np.asarray(dst, np.int64)
    hpl = fab.hosts_per_leaf
    hops = np.asarray(fluid.fabric_hops(fab, src // hpl, dst // hpl, np.asarray(path)))
    return np.concatenate([(fab.tx0 + src)[:, None], hops, (fab.rx0 + dst)[:, None]], 1)


def dead_paths(fab, cap: np.ndarray) -> frozenset:
    """Paths that cross a zero-capacity fabric link between some pair of
    distinct leaves."""
    from bench.reference import fluid

    n, P = fab.n_leaf, fab.n_paths
    s, d, p = np.meshgrid(np.arange(n), np.arange(n), np.arange(P), indexing="ij")
    keep = s != d
    hops = np.asarray(fluid.fabric_hops(fab, s[keep], d[keep], p[keep]))
    dead = (cap[hops] <= 0.0).any(-1)
    return frozenset(int(x) for x in np.unique(p[keep][dead]))


class Study:
    """One cell's closed-loop study: ``setup``, ``window``, ``check``."""

    def __init__(self, cell, seed: int, log):
        self.cell, self.seed, self.log = cell, int(seed), log
        self.t = cell.traffic
        self.ring, self.camp, self.sim = self.t["ring"], self.t["campaign"], self.t["sim"]
        self.fault = cell.config["fault"]

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        self.build_pool()
        self.warm()

    def build_pool(self, n: int | None = None) -> None:
        """The fabric, the fault schedule and the run's order of work seeds
        (``n`` of them, or the whole pool)."""
        from bench.reference import fluid
        from repro.dist import cosim
        from repro.netsim import topology
        from repro.netsim.dcqcn import DCQCNParams

        self.cosim = cosim
        self.topo = _topology(topology, self.cell.config["fabric"])
        self.hosts = cosim.ring_hosts(self.topo, self.ring["members"])
        f = self.fault
        self.faults = (cosim.kill_spine(self.topo, f["switch"], epoch=f["down_epoch"],
                                        recover_epoch=f["up_epoch"]),)
        self.dcqcn = DCQCNParams(**self.cell.config["dcqcn"])
        self.order = np.random.default_rng(self.seed).permutation(
            self.t["pool"]).tolist()[: n or None]
        # the schedule, from the reference's link tables
        self.fab = fluid.build_fabric(self.cell.config["fabric"])
        self.dead = switch_links(self.fab, f["switch"])
        assert self.dead.size == f["links"], (self.dead.size, f["links"])
        self.dead_set = dead_paths(self.fab, self.capacity(f["down_epoch"]))

    def capacity(self, epoch: int) -> np.ndarray:
        """f32[n_links + 1] link capacities of ``epoch`` under the fault."""
        cap = np.asarray(self.fab.capacity, np.float32).copy()
        if self.fault["down_epoch"] <= epoch < self.fault["up_epoch"]:
            cap[self.dead] *= np.float32(self.fault["scale"])
        return cap

    def expected_quarantine(self, epoch: int) -> frozenset:
        """Paths the plan in force at ``epoch`` quarantines: those dead in
        an epoch fewer than ``phi_steps`` epochs before it."""
        down = range(self.fault["down_epoch"], self.fault["up_epoch"])
        recent = range(epoch - self.camp["phi_steps"] + 1, epoch)
        return self.dead_set if any(e in down for e in recent) else frozenset()

    def campaign(self, work_seed: int):
        """One campaign through the program's entry point."""
        return self.cosim.run_cosim(
            self.topo, self.hosts, self.ring["size_bytes"], scheme=self.sim["scheme"],
            epochs=self.camp["epochs"], faults=self.faults,
            phi_steps=self.camp["phi_steps"], n_chunks=self.ring["n_chunks"],
            dt=self.sim["dt"], steer=self.camp["steer"], seed=int(work_seed),
            dataplane=self.sim["dataplane"], dcqcn=self.dcqcn)

    def warm(self) -> None:
        """One campaign builds every executable; one more runs warm."""
        t0 = time.perf_counter()
        hist = self.campaign(self.order[0])
        t1 = time.perf_counter()
        self.warm_hists = [hist, self.campaign(self.order[-1])]
        self.log(f"pool: {len(self.order)} campaigns of {hist.epochs} epochs, "
                 f"{hist.duration_s!r} s horizon; warm-up campaign {t1 - t0!r} s, "
                 f"then {time.perf_counter() - t1!r} s; builds per epoch "
                 f"{[r.new_builds for r in hist.records]}")

    # ------------------------------------------------------------ window
    def window(self, seconds: float, span) -> dict:
        """Whole campaigns back to back for ``seconds``; returns the cell's
        record.  Each epoch's sim is kept for the check as it leaves
        ``sweep.run_batch``."""
        from repro.netsim import sweep

        kept: list = []
        run_batch = sweep.run_batch

        def keep(topo, cfg, traces, **kw):
            res, outs = run_batch(topo, cfg, traces, **kw)
            kept.append(dict(cfg=cfg, trace=traces[0], finish=res[0].finish,
                             spill=int(res[0].spill_steps),
                             goodput=outs[0].goodput_total, max_queue=outs[0].max_queue))
            return res, outs

        done, walls = [], []
        sweep.run_batch = keep
        try:
            i = 0
            t0 = time.perf_counter()
            while True:
                s0, k0 = time.perf_counter(), len(kept)
                with span("bench.batch"):
                    hist = self.campaign(self.order[i % len(self.order)])
                walls.append(time.perf_counter() - s0)
                done.append(dict(hist=hist, sims=kept[k0:]))
                i += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            wall = time.perf_counter() - t0
        finally:
            sweep.run_batch = run_batch
        self.done = done
        cfg = kept[-1]["cfg"]
        self.n_steps = int(round(cfg.duration_s / cfg.dt))
        self.dt = cfg.dt
        chunk = cost.chunk_steps(cfg.chunk_steps, cfg.uplink_sample_every, self.n_steps)
        epochs = [dict(steps=cost.exit_steps(s["finish"], cfg.dt, chunk, self.n_steps))
                  for c in done for s in c["sims"]]
        self.log(f"window: {len(done)} campaigns, seconds each {walls}; exit step per "
                 f"epoch of the first {[e['steps'] for e in epochs[:len(done[0]['sims'])]]}"
                 f" of {self.n_steps}")
        return dict(window_s=wall, sims=len(epochs), epochs=epochs,
                    pool_reuses=max(0, i - len(self.order)), n_steps=self.n_steps,
                    metrics=dict(sim_steps_per_s=len(epochs) * self.n_steps / wall))

    # ------------------------------------------------------------ check
    def guarantees(self) -> tuple[list, int]:
        """Every epoch of the window against the configuration's
        guarantees: the numbers ``unfinished_live`` (flows off the dead
        links with no finish by the horizon, summed),
        ``rebuilds_after_epoch0`` (set-up's campaigns included),
        ``quarantine_mismatch`` (epochs whose plan's quarantine is not the
        one the schedule and the reference's link tables give),
        ``convergence_epochs`` (the slowest campaign's epochs from the kill
        to an epoch back within 10 % of the pre-fault p99 with every flow
        done; a campaign that never gets there reads its epoch count) and
        ``plan_refused``, each with its limit; and the count of epochs that
        spilled or broke a per-epoch guarantee."""
        chk = self.t["check"]
        kill = self.fault["down_epoch"]
        pairs = [(rec, s) for c in self.done for rec, s in zip(c["hist"].records, c["sims"])]
        # every epoch's flows on their paths at once: one hash call
        cols = {k: np.concatenate([np.asarray(getattr(s["trace"], k)) for _, s in pairs])
                for k in ("src", "dst", "flow_id")}
        links = np.split(flow_links(self.fab, cols["src"], cols["dst"],
                                    ecmp_paths(self.fab, cols["src"], cols["dst"],
                                               cols["flow_id"])),
                         np.cumsum([s["trace"].valid.size for _, s in pairs])[:-1])
        links = [np.where(x >= 0, x, self.fab.n_links) for x in links]
        horizon = (self.n_steps + 0.5) * self.dt
        unfinished = mismatch = refused = conv = failed = 0
        # a campaign builds its executables in epoch 0, so the first one
        # run, in set-up where there is one, is where a rebuild shows
        rebuilds = sum(r.new_builds for h in getattr(self, "warm_hists", ())
                       for r in h.records[1:])
        for c in self.done:
            hist = c["hist"]
            failed += abs(hist.epochs - len(c["sims"]))
            refused += hist.plan_refused
            e_conv = hist.convergence_epoch(kill)
            conv = max(conv, hist.epochs if e_conv is None else e_conv - kill)
        for (rec, s), lk in zip(pairs, links):
            valid = np.asarray(s["trace"].valid)
            live = valid & ~(self.capacity(rec.epoch)[lk] <= 0.0).any(1)
            f = np.asarray(s["finish"], np.float64)
            if f.size != valid.size:  # an answer for another trace
                f = np.full(valid.size, np.inf)
            n_late = int(np.sum(live & ~(f <= horizon)))
            builds = rec.new_builds if rec.epoch > 0 else 0
            wrong = frozenset(rec.quarantined) != self.expected_quarantine(rec.epoch)
            unfinished += n_late
            rebuilds += builds
            mismatch += int(wrong)
            failed += int(s["spill"] > 0 or n_late > 0 or builds > 0 or wrong)
        return [("unfinished_live", float(unfinished), chk["unfinished_live_limit"]),
                ("rebuilds_after_epoch0", float(rebuilds), chk["rebuilds_after_epoch0_limit"]),
                ("quarantine_mismatch", float(mismatch), chk["quarantine_mismatch_limit"]),
                ("convergence_epochs", float(conv), chk["convergence_epochs_limit"]),
                ("plan_refused", float(refused), chk["plan_refused_limit"])], failed

    def sample(self) -> list:
        """A seeded sample of the window's epochs: one in which the switch
        dies, and ``check.epochs - 1`` others, their per-step outputs over
        ``steps`` steps (zero past the end of a sim that stopped short);
        drops the rest of the window's outputs."""
        chk = self.t["check"]
        T = self.steps
        pad = lambda a: np.concatenate([a, np.zeros(max(0, T - a.size), a.dtype)])[:T]
        rng = np.random.default_rng([self.seed, 7])
        flat = [(rec.epoch, s) for c in self.done for rec, s in zip(c["hist"].records, c["sims"])]
        onset = [j for j, (e, _) in enumerate(flat) if e == self.fault["down_epoch"]]
        first = onset[int(rng.integers(len(onset)))]
        others = [j for j in rng.permutation(len(flat)).tolist() if j != first]
        out = []
        for j in [first] + others[: chk["epochs"] - 1]:
            e, s = flat[j]
            out.append(dict(epoch=e, trace=s["trace"], finish=np.asarray(s["finish"]),
                            goodput=pad(np.asarray(s["goodput"], np.float32)),
                            max_queue=pad(np.asarray(s["max_queue"], np.float32))))
        self.log(f"check sample: epochs {[d['epoch'] for d in out]}")
        return out

    @property
    def steps(self) -> int:
        return min(self.t["check"]["steps"], self.n_steps)

    def reference(self, sample: list, precision: str = "f32") -> list:
        """The plain reference over the sample's traces for ``steps`` steps,
        each given its epoch's capacities, in the form of ``sample``'s
        entries; epochs of one capacity state share a batch."""
        import jax
        import jax.numpy as jnp

        from bench.reference import fluid

        params = fluid.params_from(self.cell.config, self.t)
        groups: dict = {}
        for i, d in enumerate(sample):
            groups.setdefault(tuple(float(x) for x in self.capacity(d["epoch"])), []).append(i)
        out: list = [None] * len(sample)
        for cap, idx in groups.items():
            run = fluid.batched(self.fab._replace(capacity=cap), params, self.steps, 1,
                                precision)
            part = [sample[i] for i in idx]
            F = fluid.pad_to(max(int(d["trace"].valid.sum()) for d in part))

            def pad(a, fill):
                return np.concatenate([a, np.full(F - a.size, fill, a.dtype)])

            cols = [jnp.asarray(np.stack([pad(np.asarray(getattr(d["trace"], name)), fill)
                                          for d in part]))
                    for name, fill in (("sizes", 1.0), ("arrivals", np.inf), ("src", 0),
                                       ("dst", 0), ("flow_id", 0), ("valid", False))]
            r = jax.device_get(run(*cols))
            for j, (i, d) in enumerate(zip(idx, part)):
                out[i] = dict(epoch=d["epoch"], trace=d["trace"],
                              goodput=np.asarray(r.goodput[j]),
                              max_queue=np.asarray(r.max_queue[j]),
                              finish=np.asarray(r.finish[j][: int(d["trace"].valid.sum())]))
        return out

    def check(self, control: str | None = None) -> list[tuple[str, float, float]]:
        """Compare the sample with the reference given the same capacities,
        over ``steps`` steps, and every epoch of the window with the
        guarantees; returns (name, value, limit) and sets ``failed``.
        ``control`` puts the reference at that lower precision in the
        program's place for the comparison (the control of it); the
        guarantees are the program's alone and are left out then."""
        chk = self.t["check"]
        whole = []
        if control is None:
            whole, self.failed = self.guarantees()
        sample = self.sample()
        self.done = None
        ref = self.reference(sample)
        got = sample if control is None else self.reference(sample, control)
        return compare(got, ref, self.steps, self.dt, self.kmin, chk) + whole

    @property
    def kmin(self) -> float:
        return float(self.cell.config["dcqcn"]["kmin_bytes"])


def compare(got: list, ref: list, T: int, dt: float, kmin: float, chk: dict):
    """The numbers of the comparison, each with its limit:

    * ``traj_gap``: the widest ``traj_gap`` over the sampled epochs;
    * ``finish_mismatch``: flows that the reference completes within the
      first T steps and the run under test completes at another time;
    * ``bytes_gap``: the widest gap over the sampled epochs between the
      bytes delivered (goodput summed over the T steps) and the
      reference's, as a share of the reference's."""
    gap, mism, bgap = 0.0, 0, 0.0
    for g, r in zip(got, ref):
        gap = max(gap, traj_gap(g, r, T, kmin))
        f_r = np.asarray(r["finish"], np.float64)
        f_g = np.asarray(g["finish"], np.float64)
        if f_g.size != f_r.size:  # an answer for another trace: nothing matches
            f_g = np.full(f_r.size, np.nan)
        due = np.isfinite(f_r) & (f_r <= (T + 0.5) * dt)
        mism += int(np.sum(f_g[due] != f_r[due]))
        b_g, b_r = (float(np.sum(np.asarray(x["goodput"][:T], np.float64))) for x in (g, r))
        bgap = max(bgap, abs(b_g - b_r) / max(b_r, 1.0))
    return [("traj_gap", gap, chk["traj_gap_limit"]),
            ("finish_mismatch", float(mism), chk["finish_mismatch_limit"]),
            ("bytes_gap", bgap, chk["bytes_gap_limit"])]
