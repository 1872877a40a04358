"""Multi-epoch co-simulation driver: the paper's Fig. 11 convergence story.

``dist.netfeed.co_simulate`` closes ONE plan -> trace -> fluid-sim ->
health -> plan cycle.  This module iterates it over a mutable FAULT
SCHEDULE — spines killed at epoch k, recovering at epoch k + m, capacity
brown-outs — and records per-epoch FCT / imbalance / plan-churn into a
``CosimHistory`` the benches plot as convergence curves and CDFs:

  epoch t:  capacity_t = capacity_at(topo, faults, t)      (fault state)
            trace_t    = collective_trace(plan_t, ...)     (ring schedule;
                         ECMP-steered so plan_t's chunk->path map BINDS)
            sim        = sweep.run_one(..., capacity=capacity_t)
            reports    = report_congestion(health, ..., step=t)
            plan_{t+1} = health.plan(t + 1)                (phi-expiry:
                         a path re-enters exactly phi_steps after its
                         last report — recovered spines are released)

Two contracts make the loop cheap and honest:

  * capacity is a TRACED sweep operand (netsim/sweep.py), so every epoch
    after the first reuses the one compiled program no matter how the
    fault schedule mutates link capacities — ``EpochRecord.new_builds``
    proves it per epoch from ``sweep.cache_stats()``;
  * the ring cadence and the per-flow slot window are fixed from the
    HEALTHY topology at epoch 0 (the collective's schedule does not know
    about faults, and one slot per flow makes spill — and therefore
    shape-changing retries — impossible), so trace shapes never drift.

Each epoch runs inside the host span ``repro.cosim.epoch`` and its stages
inside ``repro.cosim.trace`` / ``.sim`` / ``.feedback`` / ``.record``
(``obs.scopes.span``), so a profiler trace splits an epoch's wall time
into host planning and device work; the spans change no result.

``run_cosim_grid`` fans a (scheme x ring size x fault schedule x seed)
grid through ``netsim.sweep.run_jobs`` — including paper-scale
``three_tier`` (320 hosts) — one callable job per grid point.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time

import numpy as np

from repro.dist import netfeed
from repro.dist.elastic import LinkHealth
from repro.obs import scopes


# ---------------------------------------------------------- fault schedule
@dataclasses.dataclass(frozen=True)
class FaultEvent:
    """Capacity of ``links`` is multiplied by ``scale`` for epochs in
    [start_epoch, end_epoch) — end_epoch None means the fault never
    recovers.  scale 0.0 is a hard failure; 0 < scale < 1 a brown-out."""

    start_epoch: int
    links: tuple[int, ...]
    scale: float = 0.0
    end_epoch: int | None = None

    def __post_init__(self):
        # an empty-links or end<=start event is always a typo'd schedule:
        # it silently applies to nothing / never, and the bench reads the
        # run as a (vacuously) healthy fault epoch
        assert len(self.links) > 0, "FaultEvent with no links is a no-op"
        assert self.start_epoch >= 0, self.start_epoch
        assert self.scale >= 0.0, self.scale
        if self.end_epoch is not None:
            assert self.end_epoch > self.start_epoch, \
                (self.start_epoch, self.end_epoch)

    def active(self, epoch: int) -> bool:
        return self.start_epoch <= epoch and (
            self.end_epoch is None or epoch < self.end_epoch)


def kill_spine(topo, spine: int, *, epoch: int,
               recover_epoch: int | None = None) -> FaultEvent:
    """Hard-fail one fabric switch (leaf_spine: a spine; three_tier: an
    aggregation switch — see ``topology.spine_links``)."""
    from repro.netsim.topology import spine_links

    return FaultEvent(epoch, spine_links(topo, spine), 0.0, recover_epoch)


def brownout_spine(topo, spine: int, scale: float, *, epoch: int,
                   recover_epoch: int | None = None) -> FaultEvent:
    """Degrade one fabric switch's links to ``scale`` x capacity."""
    from repro.netsim.topology import spine_links

    assert 0.0 < scale < 1.0, scale
    return FaultEvent(epoch, spine_links(topo, spine), scale, recover_epoch)


def capacity_at(topo, faults, epoch: int) -> np.ndarray:
    """The epoch's link-capacity vector (f32[n_links + 1], sentinel slot
    preserved): base topology capacity with every active fault applied."""
    cap = np.asarray(topo.capacity, np.float32).copy()
    for ev in faults:
        if ev.active(epoch):
            cap[list(ev.links)] *= np.float32(ev.scale)
    return cap


def ring_hosts(topo, n: int) -> list[int]:
    """``n`` ring members striped across leaves (host i lives on leaf
    i % n_leaf) — the pod-gateway pattern: consecutive ring neighbors are
    always on different racks while n <= n_leaf, so every ring segment
    crosses the fabric."""
    L, hpl = topo.n_leaf, topo.hosts_per_leaf
    assert 2 <= n <= topo.n_hosts, (n, topo.n_hosts)
    return [(i % L) * hpl + (i // L) for i in range(n)]


# ------------------------------------------------------------- epoch record
@dataclasses.dataclass
class EpochRecord:
    """One planning epoch's observables.  FCTs are CENSORED at the horizon
    (metrics.fct_samples): a killed spine starves flows outright and a
    survivors-only p99 would read the disaster epoch as healthy."""

    epoch: int
    fct_p50_s: float
    fct_p99_s: float
    fct_mean_s: float
    completion: float
    imbalance_mean: float
    plan_churn: int  # inactive-flag flips between this plan and the next
    quarantined: tuple[int, ...]  # paths inactive in THIS epoch's plan
    reported_slow: tuple[int, ...]  # paths report_congestion flagged
    spill_steps: int
    new_builds: int  # sweep executables built this epoch (0 after epoch 0)
    fct: np.ndarray  # censored per-flow samples (CDFs)
    imbalance: np.ndarray  # per-(ToR, window) imbalance samples
    # --- chaos-campaign observables (defaults keep legacy callers intact)
    replan_round: int = -1  # in-epoch replanning cut round (-1 = none)
    straggler_scale: float = 1.0  # cadence stretch the ring actually paid
    straggler_quarantined: tuple[int, ...] = ()  # ranks the policy benched
    # --- degraded-telemetry observables (-1 / False = no channel in play)
    safe_mode: bool = False  # epoch RAN on the blind ECMP fallback
    plan_version: int = -1  # version of the plan in force this epoch
    reports_sent: int = -1  # telemetry payloads emitted this epoch
    reports_delivered: int = -1  # payloads the channel delivered this epoch
    reports_admitted: int = -1  # deliveries admitted by the staleness gate
    reports_stale: int = -1  # deliveries older than the staleness bound
    reports_duplicate: int = -1  # duplicated deliveries (idempotently dropped)
    # --- adaptive-dt observables (0 = fixed dt or nothing fast-forwarded)
    ff_steps: int = 0  # dt steps the quiescence fast-forward covered
    # --- in-sim recorder drain (None = no RecordSpec passed to run_cosim)
    insim: dict | None = None  # obs.epoch_summary of the epoch's ring buffer


@dataclasses.dataclass
class CosimHistory:
    """The driver's full output: per-epoch records, the plan sequence, and
    the LinkHealth whose phi windows produced it."""

    scheme: str
    phi_steps: int
    duration_s: float
    records: list[EpochRecord]
    plans: list  # PathPlan used in epoch t (len == epochs)
    final_plan: object  # plan for epoch `epochs` (what a deployment ships)
    health: LinkHealth
    plan_refused: int = 0  # newer-plan applications refused (gate: zero)

    @property
    def epochs(self) -> int:
        return len(self.records)

    def baseline_p99(self, fault_epoch: int) -> float:
        """Pre-failure reference: median censored p99 over the epochs
        before the first fault (epoch 0 if the fault hits immediately)."""
        pre = [r.fct_p99_s for r in self.records[:max(fault_epoch, 1)]]
        return float(np.median(pre))

    def convergence_epoch(self, fault_epoch: int,
                          tol: float = 0.10) -> int | None:
        """First epoch >= ``fault_epoch`` whose censored p99 FCT is back
        within ``tol`` of the pre-failure baseline with every flow
        completing — the paper's "FCT recovers within a few epochs"
        claim, as a number.  None = never converged."""
        base = self.baseline_p99(fault_epoch)
        for r in self.records[fault_epoch:]:
            if r.completion >= 1.0 and r.fct_p99_s <= (1.0 + tol) * base:
                return r.epoch
        return None

    def fct_cdf(self, epochs: list[int] | None = None, points: int = 50):
        from repro.netsim import metrics

        rs = self.records if epochs is None else \
            [r for r in self.records if r.epoch in epochs]
        return metrics.cdf(np.concatenate([r.fct for r in rs]), points)

    def imbalance_cdf(self, epochs: list[int] | None = None,
                      points: int = 50):
        from repro.netsim import metrics

        rs = self.records if epochs is None else \
            [r for r in self.records if r.epoch in epochs]
        samples = np.concatenate([r.imbalance for r in rs]) if any(
            r.imbalance.size for r in rs) else np.zeros(1)
        return metrics.cdf(samples, points)

    def as_record(self) -> dict:
        """JSON-able per-epoch curves for BENCH_netsim.json."""
        rs = self.records
        return dict(
            scheme=self.scheme,
            phi_steps=self.phi_steps,
            epochs=self.epochs,
            duration_ms=round(self.duration_s * 1e3, 3),
            p50_us=[round(r.fct_p50_s * 1e6, 2) for r in rs],
            p99_us=[round(r.fct_p99_s * 1e6, 2) for r in rs],
            completion=[round(r.completion, 4) for r in rs],
            imbalance_mean=[round(r.imbalance_mean, 4) for r in rs],
            plan_churn=[r.plan_churn for r in rs],
            n_quarantined=[len(r.quarantined) for r in rs],
            spill_steps=[r.spill_steps for r in rs],
            new_builds=[r.new_builds for r in rs],
            replan_round=[r.replan_round for r in rs],
            straggler_scale=[round(r.straggler_scale, 3) for r in rs],
            n_straggler_quarantined=[len(r.straggler_quarantined) for r in rs],
            safe_mode=[bool(r.safe_mode) for r in rs],
            plan_version=[r.plan_version for r in rs],
            reports_sent=[r.reports_sent for r in rs],
            reports_delivered=[r.reports_delivered for r in rs],
            reports_admitted=[r.reports_admitted for r in rs],
            reports_stale=[r.reports_stale for r in rs],
            reports_duplicate=[r.reports_duplicate for r in rs],
        )

    def summary_lines(self) -> list[str]:
        return [
            f"epoch {r.epoch:2d} p99 {r.fct_p99_s * 1e6:8.1f}us "
            f"done {r.completion:5.3f} quar {len(r.quarantined):3d} "
            f"churn {r.plan_churn:3d} builds {r.new_builds}"
            for r in self.records
        ]


# ----------------------------------------------------------- epoch journal
JOURNAL_SCHEMA_VERSION = 2


class JournalSchemaError(RuntimeError):
    """A cosim journal written by an incompatible driver version.  Raised
    (never silently restarted over) because a schema mismatch means the
    journal may hold epochs this driver would MISPARSE — the user must
    delete or migrate the file explicitly.  A *spec* mismatch (same schema,
    different campaign) still restarts silently: that is a different run,
    not a different format."""


def _rec_to_json(r: EpochRecord) -> dict:
    d = dataclasses.asdict(r)
    d["fct"] = np.asarray(r.fct, np.float32).tolist()
    d["imbalance"] = np.asarray(r.imbalance, np.float32).tolist()
    for k in ("quarantined", "reported_slow", "straggler_quarantined"):
        d[k] = list(d[k])
    return d


def _rec_from_json(d: dict) -> EpochRecord:
    d = dict(d)
    d["fct"] = np.asarray(d["fct"], np.float32)
    d["imbalance"] = np.asarray(d["imbalance"], np.float32)
    for k in ("quarantined", "reported_slow", "straggler_quarantined"):
        d[k] = tuple(d.get(k, ()))
    return EpochRecord(**d)


def _load_journal(journal: str, spec_key: dict):
    """Parse a campaign journal.  Returns (records, epoch_states) for a
    journal whose header matches ``spec_key``; None for a missing,
    spec-mismatched (different campaign — restart, don't splice), or
    corrupt file; raises ``JournalSchemaError`` for a cosim journal whose
    ``schema_version`` this driver does not speak (resuming over it could
    misparse epochs).  ``epoch_states`` are the per-epoch (plan_inactive,
    health, straggler, telemetry, watchdog) snapshots; the LAST one is the
    exact driver state to resume from."""
    import json
    import os

    if not os.path.exists(journal):
        return None
    try:
        with open(journal) as fh:
            raw = [ln for ln in fh if ln.strip()]
    except OSError:
        return None
    if not raw:
        return None
    try:
        head = json.loads(raw[0])
    except ValueError:
        return None
    if not isinstance(head, dict) or head.get("journal") != "cosim":
        return None
    schema = head.get("schema_version", head.get("version"))
    if schema != JOURNAL_SCHEMA_VERSION:
        raise JournalSchemaError(
            f"cosim journal {journal!r} has schema_version={schema!r} but "
            f"this driver writes schema_version={JOURNAL_SCHEMA_VERSION}; "
            "refusing to resume over an incompatible format — delete the "
            "journal (restarts the campaign) or replay it with the driver "
            "version that wrote it")
    if head.get("spec") != spec_key:
        return None
    records, states = [], []
    for ln in raw[1:]:
        # a torn tail line IS the interruption artifact: keep the prefix
        try:
            d = json.loads(ln)
            records.append(_rec_from_json(d["record"]))
            states.append(d)
        except (ValueError, KeyError, TypeError):
            break
    return records, states


# ------------------------------------------------------------------ driver
def run_cosim(
    topo,
    hosts,
    size_bytes: float,
    *,
    scheme: str = "ecmp",
    epochs: int = 8,
    faults: tuple = (),
    campaign=None,
    phi_steps: int = 2,
    cooldown_steps: int = 0,
    n_chunks: int = 8,
    wire_dtype: str = "float32",
    dt: float = 10e-6,
    duration_s: float | None = None,
    overload: float = 1.5,
    steer: bool = True,
    replan: bool = True,
    detect_delay_s: float | None = None,
    health: LinkHealth | None = None,
    straggler_policy=None,
    straggler_deadline_frac: float = 1.5,
    seed: int = 0,
    window_slots: int | None = None,
    imbalance_sample_every: int = 10,
    journal: str | None = None,
    telemetry=None,
    staleness_bound: int | None = None,
    blackout_epochs: int = 3,
    record=None,
    flight=None,
    flowcells: int = 1,
    reorder_budget: float | None = None,
    **cfg_kw,
) -> CosimHistory:
    """Run ``epochs`` plan -> sim -> health cycles over a fault schedule.

    ``hosts`` are the ring members (``ring_hosts`` for the gateway
    pattern); ``size_bytes`` the per-member all-reduce payload.  The ring
    cadence is fixed from the healthy topology: one round every
    max(segment serialization on the fabric, n_chunks segments on the host
    NIC) — so every epoch's trace has identical shapes and the traced
    capacity operand is the ONLY thing that changes with the fault state.
    ``window_slots`` defaults to one slot per flow, which makes spill
    impossible (a fault epoch can hold every flow in flight at once) and
    therefore keeps the compiled program's shapes pinned.

    Chaos-campaign extensions (all no-ops when unused):

      * ``campaign`` (``netsim.faults.FaultCampaign``) compiles per epoch
        into a WALL-CLOCK capacity schedule f32[K, n_links + 1] + a loss
        vector, threaded through the sweep as traced operands — flaps and
        PFC pauses land mid-horizon, lossy links drive go-back-N goodput
        amplification inside the dataplane, and every epoch still reuses
        the one compiled program (K is campaign-constant).  Epoch-level
        ``faults`` compose on top.
      * in-epoch replanning (``replan=True``, needs ``steer``): a campaign
        flap with an intra-epoch onset is DETECTED ``detect_delay_s``
        (default: two ring rounds) after it lands; rounds before the cut
        run the original plan, rounds after run a
        ``collectives.replan_chunk_paths`` pinned plan — in-flight rounds
        keep their QP flow ids, surviving steered QPs keep theirs, only
        QPs whose fabric path died re-steer (the no-reordering rule).
        When every active path died, chunks/QPs fall back to the primary
        path rather than stalling.
      * stragglers: campaign ``Straggler`` events stretch their rank's
        step duration; ``straggler_policy`` (auto-created when the
        campaign has stragglers) observes every rank per epoch, and ranks
        it quarantines stop gating the bulk-synchronous cadence — the
        ring's effective round gap is the slowest NON-quarantined rank.
      * ``cooldown_steps`` enables LinkHealth's flap hysteresis (re-report
        within the cooldown doubles the path's phi window).
      * ``journal`` (a file path) appends one JSON line per completed
        epoch; re-running with the same spec resumes after the last
        journaled epoch instead of restarting the campaign (exact driver
        state — records, health phi windows, straggler misses, telemetry
        queue, watchdog — restores from the journal tail; a spec mismatch
        restarts from scratch, a ``schema_version`` mismatch raises
        ``JournalSchemaError``).

    Degraded-telemetry extensions (``telemetry`` is a
    ``netsim.faults.TelemetryChannel``; ``telemetry=None`` is bit-identical
    to the legacy perfect-feedback driver):

      * every slow path ``netfeed.observe_congestion`` sees is SENT through
        the channel as an epoch-stamped ``("slow", path)`` report — plus
        one ``("hb", leaf)`` liveness heartbeat per leaf — and only what
        the channel delivers reaches the planner, admitted through
        ``LinkHealth.admit_report`` against ``staleness_bound`` (stale
        reports discarded, duplicated deliveries idempotent);
      * plans apply through ``collectives.apply_plan``: versions are
        strictly monotone across epochs, a replayed older plan is refused
        (asserted every epoch), and unexpected refusals of genuinely newer
        plans are counted (the bench gates on zero);
      * a ``dist.elastic.TelemetryWatchdog`` watches admissible deliveries:
        ``blackout_epochs`` silent epochs flip the driver into SAFE MODE —
        the epoch runs an all-paths-active plan with steering OFF (plain
        ECMP five-tuple hashing; same trace shapes, so the compiled
        program is reused) instead of steering on stale quarantines — and
        one admissible delivery after the channel heals flips it back.

    Observability extensions (DESIGN.md §16; both default off and change
    nothing when unused):

      * ``record`` (an ``obs.RecordSpec``) threads the traced in-sim ring
        buffer through every epoch's sim: the recorder costs exactly ONE
        extra executable per shape bucket (built at epoch 0, zero rebuilds
        after), the drained per-chunk summaries land on
        ``EpochRecord.insim`` via ``obs.epoch_summary``, and the spec
        joins the journal's ``spec_key`` so a resumed campaign can't mix
        recorded and unrecorded epochs.
      * ``flight`` (a path, or an open ``obs.FlightLog``) appends one
        schema-v2 JSONL event per epoch — wall-clock span, FCT stats,
        plan/quarantine/watchdog/telemetry state, sweep build + resilience
        counters, hot uplinks, fault activations, and the in-sim drain —
        plus a leading ``campaign`` event and a trailing ``run_end`` with
        the convergence verdict.  ``obs.trace_export`` renders the file as
        a perfetto timeline; ``obs.features.epoch_matrix`` lifts it into
        [epoch, uplink, feature] arrays.  A path is opened/closed by this
        call; an instance is shared (caller closes).

    Flowcell extensions (DESIGN.md §17; defaults are bit-identical to the
    pre-flowcell driver):

      * ``flowcells`` > 1 splits every chunk-QP into that many flowcells
        sprayed round-robin over the plan's active paths (each cell keeps
        its own five-tuple, so the split reuses the steering machinery —
        the trace just carries more, smaller flows plus a ``spray``
        column).
      * ``reorder_budget`` (packets, or None) turns on the explicit
        reordering-cost model: sprayed flows pay the go-back-N
        amplification ``dataplane.reorder_gbn_factor`` charges for
        inter-path skew beyond the budget.  It rides the sweep as a traced
        scalar operand, so every epoch and every budget reuses ONE
        compiled program; ``None`` traces the identical pre-flowcell
        program (the "reordering is free" bench arm).
    """
    from repro.dist import collectives
    from repro.netsim import compact, metrics, sweep, workloads
    from repro.netsim.engine import SimConfig

    hosts = list(hosts)
    n = len(hosts)
    if health is None:
        health = LinkHealth(n_paths=topo.n_paths, phi_steps=phi_steps,
                            cooldown_steps=cooldown_steps,
                            max_staleness_epochs=staleness_bound)
    else:
        phi_steps = health.phi_steps

    watchdog = None
    if telemetry is not None:
        from repro.dist.elastic import TelemetryWatchdog

        watchdog = TelemetryWatchdog(blackout_epochs=blackout_epochs)

    cap0 = np.asarray(topo.capacity)
    fabric_bw = float(np.median(cap0[np.asarray(topo.uplink_ids)]))
    host_bw = float(cap0[topo.n_links - 2 * topo.n_hosts])
    seg_bytes = size_bytes / (n * n_chunks)
    # a member serializes all n_chunks segments of a round through one NIC
    gap = max(seg_bytes * 8.0 / fabric_bw, n_chunks * seg_bytes * 8.0 / host_bw)
    rounds = 2 * (n - 1)
    if duration_s is None:
        duration_s = rounds * gap * 2.5 + 50 * dt
    n_steps = max(int(math.ceil(duration_s / dt)), 1)
    duration_s = n_steps * dt
    cfg = SimConfig(scheme=scheme, duration_s=duration_s, dt=dt, **cfg_kw)

    policy = straggler_policy
    if policy is None and campaign is not None and campaign.has_stragglers():
        from repro.dist.elastic import StragglerPolicy

        policy = StragglerPolicy(deadline_s=gap * straggler_deadline_frac,
                                 max_misses=2)

    # ---------------- journal: resume a previously interrupted campaign
    start_epoch = 0
    records: list[EpochRecord] = []
    plans: list = []
    spec_key = dict(
        scheme=scheme, epochs=epochs, hosts=[int(h) for h in hosts],
        size_bytes=float(size_bytes), phi_steps=phi_steps,
        cooldown_steps=cooldown_steps, n_chunks=n_chunks, seed=seed,
        steer=bool(steer), replan=bool(replan),
        topo=dict(kind=topo.kind, n_links=topo.n_links, n_paths=topo.n_paths),
        telemetry=None if telemetry is None else telemetry.config(),
        staleness_bound=staleness_bound,
        blackout_epochs=blackout_epochs if telemetry is not None else None,
    )
    if record is not None:
        # JSON-normalized (lists, not tuples) so a resumed journal's loaded
        # spec compares equal; absent entirely when unused so legacy
        # journals written before the recorder existed still match
        spec_key["record"] = dict(
            ring_chunks=int(record.ring_chunks),
            quantiles=[float(q) for q in record.quantiles])
    if flowcells != 1 or reorder_budget is not None:
        # same legacy-journal convention as ``record``: the key exists only
        # when the feature is used, so pre-flowcell journals still match
        spec_key["flowcell"] = dict(
            flowcells=int(flowcells),
            reorder_budget=None if reorder_budget is None
            else float(reorder_budget))

    def _fc(p):
        # stamp the split factor onto every plan the driver runs; plans are
        # frozen dataclasses, so this is a copy — health/journal state keeps
        # the unstamped originals
        return dataclasses.replace(p, flowcells=int(flowcells)) \
            if flowcells != 1 else p
    journal_fh = None
    if journal is not None:
        import json

        loaded = _load_journal(journal, spec_key)
        if loaded is not None:
            records, states = loaded
            start_epoch = len(records)
            if states:
                health.restore(states[-1]["health"])
                if policy is not None and states[-1].get("straggler"):
                    policy.restore(states[-1]["straggler"])
                if telemetry is not None and states[-1].get("telemetry"):
                    telemetry.restore(states[-1]["telemetry"])
                if watchdog is not None and states[-1].get("watchdog"):
                    watchdog.restore(states[-1]["watchdog"])
            for st in states:
                plans.append(collectives.PathPlan(
                    n_chunks=n_chunks, directions=tuple(health.directions),
                    inactive=tuple(bool(b) for b in st["plan_inactive"]),
                    wire_dtype=wire_dtype,
                    version=int(st["record"].get("plan_version", 0))))
        # (re)write header + the valid prefix: drops any torn tail line
        # left by the interruption so the resumed journal stays parseable
        journal_fh = open(journal, "w")
        journal_fh.write(json.dumps(dict(
            journal="cosim", schema_version=JOURNAL_SCHEMA_VERSION,
            spec=spec_key)) + "\n")
        for st in (loaded[1] if loaded is not None else ()):
            journal_fh.write(json.dumps(st) + "\n")
        journal_fh.flush()

    # ---------------- flight log: control-plane event stream (obs plane)
    fl = None
    fl_owned = False
    if flight is not None:
        from repro.obs import FlightLog

        if isinstance(flight, FlightLog):
            fl = flight
        else:
            fl = FlightLog(flight, meta=dict(spec=spec_key))
            fl_owned = True
        fl.event(
            "campaign", scheme=scheme, epochs=epochs, start_epoch=start_epoch,
            n_hosts=n, size_bytes=float(size_bytes), n_steps=n_steps,
            duration_s=duration_s, dt=dt, n_chunks=n_chunks,
            n_faults=len(faults) + (len(campaign.events)
                                    if campaign is not None else 0),
            telemetry=spec_key["telemetry"],
            record=spec_key.get("record"))

    plan = health.plan(start_epoch, n_chunks=n_chunks, wire_dtype=wire_dtype)
    plan_refused = 0
    W = window_slots
    try:
        for epoch in range(start_epoch, epochs):
            with scopes.span("repro.cosim.epoch"):
                t_ep = time.time()  # epoch wall-clock span for the flight log
                with scopes.span("repro.cosim.trace"):
                    # ------------------------------------- safe-mode plan selection
                    # entering state of the watchdog decides THIS epoch's conduct:
                    # blind planners don't steer — run everything-active, unsteered
                    in_safe = watchdog is not None and watchdog.safe_mode
                    if in_safe:
                        run_plan = collectives.PathPlan(
                            n_chunks=n_chunks, directions=tuple(health.directions),
                            inactive=None, wire_dtype=wire_dtype,
                            version=plan.version)
                    else:
                        run_plan = plan

                    # -------------------------------------------- fault state
                    if campaign is not None:
                        cap = campaign.capacity_schedule(topo, epoch)  # [K, nl+1]
                        for ev in faults:  # epoch-level faults compose on top
                            if ev.active(epoch):
                                cap[:, list(ev.links)] *= np.float32(ev.scale)
                        # adaptive dt: align the segment stride to the scan-chunk
                        # grid so no chunk straddles a capacity edge (the quiescence
                        # predicate would refuse to fast-forward it); fixed dt keeps
                        # the uniform stride bit-identical
                        K_chunk, _, _ = compact.plan_chunks(cfg, n_steps)
                        cap_seg = campaign.seg_steps(
                            n_steps, align=K_chunk if cfg.adaptive else 1)
                        loss = campaign.loss_at(topo, epoch)
                        # congestion reporting sees the epoch's WORST capacity: a
                        # link that flapped at all this epoch reads as degraded
                        cap_report = cap.min(axis=0)
                        slowdowns = campaign.straggler_slowdowns(epoch)
                    else:
                        cap = capacity_at(topo, faults, epoch)
                        cap_seg, loss, cap_report = 0, None, cap
                        slowdowns = {}

                    # -------------------------------------------- stragglers
                    strag_quar: tuple[int, ...] = ()
                    if policy is not None:
                        for i in range(n):
                            policy.observe(i, gap * slowdowns.get(i, 1.0))
                        strag_quar = policy.quarantined()
                    eff = max([slowdowns.get(i, 1.0) for i in range(n)
                               if i not in strag_quar] or [1.0])
                    gap_e = gap * eff  # slowest non-quarantined rank gates the ring

                    # ------------------------------- trace (+ in-epoch replanning)
                    steer_p = topo.n_paths if steer and not in_safe else None
                    onset = campaign.midepoch_onset(topo, epoch) if campaign else None
                    replan_round = -1
                    if onset is not None and replan and steer and not in_safe \
                            and onset.paths:
                        t_detect = onset.frac * duration_s + (
                            detect_delay_s if detect_delay_s is not None else 2 * gap_e)
                        r_cut = int(math.ceil(t_detect / gap_e))
                        if 0 < r_cut < rounds:
                            replan_round = r_cut
                    if replan_round > 0:
                        # the fault is observed mid-collective: report it NOW so
                        # both this epoch's tail and the next plan route around it
                        for p in onset.paths:
                            health.report_slow(p, epoch)
                        inact2 = tuple(d or (p in set(onset.paths))
                                       for p, d in enumerate(plan.inactive))
                        pinned = collectives.PinnedPlan(
                            n_chunks=n_chunks, directions=tuple(plan.directions),
                            inactive=inact2,
                            paths=collectives.replan_chunk_paths(
                                plan.chunk_paths(), tuple(plan.directions), inact2),
                            wire_dtype=wire_dtype)
                        # steering targets: surviving QPs keep their fid (their
                        # stream stays on its path — no reorder); only QPs whose
                        # fabric path died re-steer, round-robin over survivors,
                        # falling back to the primary path when none survive
                        active0 = [p for p, d in enumerate(plan.inactive)
                                   if not d] or [0]
                        tgt = np.array(
                            [[active0[(i * n_chunks + c) % len(active0)]
                              for i in range(n)] for c in range(n_chunks)], np.int32)
                        dead = set(onset.paths)
                        surv = [p for p in active0 if p not in dead] or [0]
                        tgt_b, k = tgt.copy(), 0
                        for c in range(n_chunks):
                            for i in range(n):
                                if int(tgt[c, i]) in dead:
                                    tgt_b[c, i] = surv[k % len(surv)]
                                    k += 1
                        tr_a = workloads.collective_trace(
                            _fc(plan), hosts, size_bytes, link_bw=fabric_bw,
                            round_gap_s=gap_e, rounds=replan_round, seed=seed,
                            steer_paths=steer_p, steer_targets=tgt)
                        tr_b = workloads.collective_trace(
                            _fc(pinned), hosts, size_bytes, link_bw=fabric_bw,
                            round_gap_s=gap_e, rounds=rounds - replan_round,
                            start_s=replan_round * gap_e, seed=seed,
                            steer_paths=steer_p, steer_targets=tgt_b)
                        trace = workloads.merge_traces(tr_a, tr_b)
                    else:
                        trace = workloads.collective_trace(
                            _fc(run_plan), hosts, size_bytes, link_bw=fabric_bw,
                            round_gap_s=gap_e, seed=seed, steer_paths=steer_p)
                    if W is None:
                        W = int(trace.valid.sum())  # spill-proof: one slot per flow

                with scopes.span("repro.cosim.sim"):
                    # -------------------------------------------------- simulate
                    b0 = sweep.cache_stats()["builds"]
                    result, outs = sweep.run_one(topo, cfg, trace, capacity=cap,
                                                 loss=loss, cap_seg_steps=cap_seg,
                                                 window_slots=W, record=record,
                                                 reorder=reorder_budget)
                    new_builds = sweep.cache_stats()["builds"] - b0
                    insim = None
                    if record is not None and getattr(result, "ring", None) is not None:
                        from repro import obs

                        insim = obs.epoch_summary(record, obs.drain(record, result.ring))

                with scopes.span("repro.cosim.feedback"):
                    # ------------------------------------ congestion feedback path
                    n_sent = n_delivered = n_admitted = n_stale = n_dup = -1
                    if telemetry is None:
                        # perfect channel: the legacy direct path, bit-identical
                        slow = netfeed.report_congestion(
                            health, topo, outs, step=epoch, overload=overload,
                            capacity=cap_report, loss=loss)
                    else:
                        observed = netfeed.observe_congestion(
                            topo, outs, overload=overload, capacity=cap_report,
                            loss=loss)
                        for p in observed:
                            telemetry.send(("slow", int(p)), epoch)
                        for leaf in range(topo.n_leaf):  # liveness heartbeats
                            telemetry.send(("hb", int(leaf)), epoch)
                        n_sent = len(observed) + topo.n_leaf
                        batch = telemetry.deliver(epoch)
                        n_delivered = len(batch)
                        n_admitted = n_stale = n_dup = 0
                        admitted_slow: list[int] = []
                        for payload, origin in batch:
                            if payload[0] == "slow":
                                verdict = health.admit_report(
                                    int(payload[1]), origin, epoch)
                                if verdict == "admitted":
                                    n_admitted += 1
                                    admitted_slow.append(int(payload[1]))
                                elif verdict == "stale":
                                    n_stale += 1
                                else:
                                    n_dup += 1
                            else:  # heartbeat: same staleness gate, no health state
                                if staleness_bound is not None \
                                        and epoch - origin > staleness_bound:
                                    n_stale += 1
                                else:
                                    n_admitted += 1
                        watchdog.observe(n_admitted)
                        slow = tuple(dict.fromkeys(admitted_slow))

                    # ------------------------------------ versioned plan application
                    next_plan = health.plan(epoch + 1, n_chunks=n_chunks,
                                            wire_dtype=wire_dtype)
                    applied, took = collectives.apply_plan(plan, next_plan)
                    if not took:
                        plan_refused += 1  # a genuinely newer plan was refused: bug
                    # the cross-version no-reordering invariant, asserted live: a
                    # reordered (older) or duplicated delivery must be refused and
                    # leave the applied table untouched
                    stale_applied, took_stale = collectives.apply_plan(applied, plan)
                    assert stale_applied is applied and not took_stale, \
                        (applied.version, plan.version)
                    dup_applied, took_dup = collectives.apply_plan(applied, applied)
                    assert dup_applied is applied and not took_dup

                with scopes.span("repro.cosim.record"):
                    churn = sum(int(a != b)
                                for a, b in zip(plan.inactive, applied.inactive))
                    fct, completion = metrics.fct_samples(result, trace,
                                                          horizon_s=duration_s)
                    imb = metrics.throughput_imbalance(
                        outs, sample_every=imbalance_sample_every,
                        trace_stride=cfg.uplink_sample_every)
                    rec = EpochRecord(
                        epoch=epoch,
                        fct_p50_s=float(np.percentile(fct, 50)),
                        fct_p99_s=float(np.percentile(fct, 99)),
                        fct_mean_s=float(fct.mean()),
                        completion=completion,
                        imbalance_mean=float(imb.mean()) if imb.size else 0.0,
                        plan_churn=churn,
                        quarantined=tuple(
                            p for p, d in enumerate(run_plan.inactive) if d),
                        reported_slow=tuple(slow),
                        spill_steps=int(result.spill_steps),
                        new_builds=new_builds,
                        fct=fct,
                        imbalance=imb,
                        replan_round=replan_round,
                        straggler_scale=float(eff),
                        straggler_quarantined=strag_quar,
                        safe_mode=in_safe,
                        plan_version=int(plan.version),
                        reports_sent=n_sent,
                        reports_delivered=n_delivered,
                        reports_admitted=n_admitted,
                        reports_stale=n_stale,
                        reports_duplicate=n_dup,
                        ff_steps=int(getattr(result, "ff_steps", 0)),
                        insim=insim,
                    )
                    records.append(rec)
                    plans.append(run_plan)
                    if journal_fh is not None:
                        import json

                        journal_fh.write(json.dumps(dict(
                            epoch=epoch,
                            record=_rec_to_json(rec),
                            plan_inactive=[bool(b) for b in run_plan.inactive],
                            health=health.state(),
                            straggler=policy.state() if policy is not None else None,
                            telemetry=telemetry.state()
                            if telemetry is not None else None,
                            watchdog=watchdog.state()
                            if watchdog is not None else None,
                        )) + "\n")
                        journal_fh.flush()
                    if fl is not None:
                        fa = list(campaign.activations(epoch)) if campaign else []
                        fa += [dict(kind="FaultEvent", links=list(ev.links),
                                    scale=ev.scale, start_epoch=ev.start_epoch,
                                    end_epoch=ev.end_epoch)
                               for ev in faults if ev.active(epoch)]
                        fl.event(
                            "epoch", epoch=epoch, t0_s=t_ep,
                            dur_s=time.time() - t_ep, n_steps=n_steps,
                            fct_p50_us=round(rec.fct_p50_s * 1e6, 3),
                            fct_p99_us=round(rec.fct_p99_s * 1e6, 3),
                            completion=round(completion, 5),
                            plan_version=int(run_plan.version), plan_churn=churn,
                            safe_mode=in_safe, replan_round=replan_round,
                            quarantined=[int(p) for p in rec.quarantined],
                            reported_slow=[int(p) for p in rec.reported_slow],
                            straggler_quarantined=[int(i) for i in strag_quar],
                            straggler_scale=float(eff),
                            new_builds=new_builds,
                            spill_steps=int(result.spill_steps),
                            ff_steps=rec.ff_steps,
                            reports=None if telemetry is None else dict(
                                sent=n_sent, delivered=n_delivered,
                                admitted=n_admitted, stale=n_stale, duplicate=n_dup),
                            watchdog=watchdog.state() if watchdog is not None
                            else None,
                            sweep=sweep.obs_stats(),
                            hot_uplinks=netfeed.hot_uplinks(
                                topo, outs, capacity=cap_report),
                            faults=fa,
                            insim=insim,
                        )
                plan = applied
        hist = CosimHistory(scheme=scheme, phi_steps=phi_steps,
                            duration_s=duration_s, records=records,
                            plans=plans, final_plan=plan, health=health,
                            plan_refused=plan_refused)
        if fl is not None and records:
            evs = list(faults) + (list(campaign.events) if campaign else [])
            fe = min((f.start_epoch for f in evs), default=1)
            fl.event(
                "run_end", epochs_run=len(records),
                convergence_epoch=hist.convergence_epoch(fe),
                plan_refused=plan_refused,
                total_new_builds=sum(r.new_builds for r in records),
                sweep=sweep.obs_stats())
    finally:
        if journal_fh is not None:
            journal_fh.close()
        if fl is not None and fl_owned:
            fl.close()
    return hist


def run_cosim_grid(specs: list[dict], *, workers: int | None = None,
                   salvage: bool = False, timeout_s: float | None = None,
                   retries: int = 0) -> list:
    """Fan a (scheme x ring size x fault schedule x seed) grid through the
    sweep runner's job pool: one ``run_cosim`` epoch loop per spec dict,
    dispatched by ``netsim.sweep.run_jobs`` (callable-job spelling), so
    grid points share the executable cache and the sharded dispatch path.
    Histories return in spec order.

    ``salvage`` / ``timeout_s`` / ``retries`` pass straight to
    ``sweep.run_jobs``: with ``salvage=True`` a chaos campaign that crashes
    or times out one grid cell yields a ``sweep.JobFailure`` poisoned
    record AT that cell's index (check ``getattr(h, "failed", False)``)
    instead of burning every completed sibling — exactly the crash-proof
    contract a 320-host fault sweep needs.

    Note: ``EpochRecord.new_builds`` attribution is per-process, so the
    no-recompile acceptance check should read a grid of ONE spec (or
    ``workers=1`` with distinct shapes) — concurrent grid points may
    interleave their builds."""
    from repro.netsim import sweep

    return sweep.run_jobs([functools.partial(run_cosim, **spec)
                           for spec in specs], workers=workers,
                          salvage=salvage, timeout_s=timeout_s,
                          retries=retries)
