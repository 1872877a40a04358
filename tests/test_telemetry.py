"""Seeded twins for the degraded-telemetry control plane (ISSUE 7):
TelemetryChannel mechanics, staleness-bounded admission, the blackout
watchdog state machine, versioned plan application, and the co-sim
driver's safe-mode fallback + journal schema v2.  The hypothesis
generalizations of the admission invariants live in
tests/test_telemetry_properties.py (optional dep); everything here runs
unconditionally.
"""
import json
import os

import numpy as np
import pytest

from repro.dist import collectives
from repro.dist.elastic import LinkHealth, TelemetryWatchdog
from repro.netsim.faults import FaultCampaign, LinkFlap, TelemetryChannel


# ------------------------------------------------------- channel mechanics
def test_perfect_channel_delivers_everything_in_order():
    ch = TelemetryChannel()
    for e in range(4):
        ch.send(("slow", e), e)
        ch.send(("hb", 0), e)
        assert ch.deliver(e) == [(("slow", e), e), (("hb", 0), e)]
    assert ch.sent == 8 and ch.delivered == 8 and ch.dropped == 0


def test_channel_delay_shifts_delivery_epochs():
    ch = TelemetryChannel(delay_epochs=2)
    ch.send(("slow", 1), 0)
    assert ch.deliver(0) == [] and ch.deliver(1) == []
    assert ch.deliver(2) == [(("slow", 1), 0)]  # origin stamp preserved
    assert ch.deliver(3) == []


def test_channel_loss_is_seeded_and_deterministic():
    def run(seed):
        ch = TelemetryChannel(loss=0.5, seed=seed)
        for e in range(40):
            ch.send(("slow", e), e)
        return tuple(p for p, _ in ch.deliver(100))

    assert run(3) == run(3)  # same seed, same fate
    assert run(3) != run(4)  # loss actually depends on the seed
    ch = TelemetryChannel(loss=0.5, seed=3)
    for e in range(40):
        ch.send(("slow", e), e)
    assert 0 < ch.dropped < 40  # neither lossless nor total blackout


def test_channel_duplication_and_reorder_are_seeded():
    ch = TelemetryChannel(dup=1.0, delay_epochs=0, seed=0)
    ch.send(("slow", 7), 0)
    got = ch.deliver(5)
    assert got.count((("slow", 7), 0)) == 2  # dup=1: exactly two copies
    a = TelemetryChannel(reorder=True, seed=9)
    b = TelemetryChannel(reorder=True, seed=9)
    for ch2 in (a, b):
        for i in range(6):
            ch2.send(("slow", i), 0)
    assert a.deliver(0) == b.deliver(0)  # reorder shuffle replays per seed


def test_channel_blackout_drops_sends_and_deliveries():
    # delay 2 straddles the [1, 4) window from both sides
    ch = TelemetryChannel(delay_epochs=2, blackout=(1, 4))
    ch.send(("slow", 0), 0)  # sent ok, arrives 2 = inside -> dropped
    ch.send(("slow", 1), 1)  # sent inside -> dropped
    ch.send(("slow", 2), 4)  # sent at 4 (window is half-open), arrives 6
    out = []
    for e in range(7):
        out.extend(ch.deliver(e))
    assert out == [(("slow", 2), 4)]
    assert ch.dropped == 2


def test_channel_state_restore_replays_bit_identically(tmp_path):
    def mk():
        return TelemetryChannel(loss=0.3, delay_epochs=1, jitter_epochs=1,
                                dup=0.3, reorder=True, seed=11)

    a = mk()
    for e in range(3):
        a.send(("slow", e), e)
        a.deliver(e)
    # snapshot through an actual JSON round-trip (the journal's spelling)
    snap = json.loads(json.dumps(a.state()))
    b = mk()
    b.restore(snap)
    for e in range(3, 8):
        a.send(("slow", e), e)
        b.send(("slow", e), e)
        assert a.deliver(e) == b.deliver(e)
    assert (a.sent, a.dropped, a.delivered) == (b.sent, b.dropped, b.delivered)


# --------------------------------------------- staleness-bounded admission
def test_admit_report_verdicts():
    h = LinkHealth(n_paths=4, phi_steps=3, max_staleness_epochs=2)
    assert h.admit_report(1, origin_epoch=5, now_epoch=5) == "admitted"
    assert h.admit_report(1, origin_epoch=5, now_epoch=6) == "duplicate"
    assert h.admit_report(1, origin_epoch=4, now_epoch=6) == "admitted"
    assert h.admit_report(2, origin_epoch=1, now_epoch=6) == "stale"
    # stale and duplicate admissions leave the quarantine state untouched
    assert h.inactive(6) == (False, True, False, False)
    # quarantine keys on the DELIVERY epoch (admitted at 6 -> held to 8)
    assert h.expiry(1) == 6 + 3


def test_admit_report_unbounded_by_default():
    h = LinkHealth(n_paths=2, phi_steps=2)
    assert h.admit_report(0, origin_epoch=0, now_epoch=50) == "admitted"


def test_duplicate_admission_does_not_trip_flap_hysteresis():
    # same (path, origin) delivered twice across the cooldown boundary: the
    # duplicate must not double the phi window
    h = LinkHealth(n_paths=2, phi_steps=2, cooldown_steps=4,
                   max_staleness_epochs=None)
    assert h.admit_report(0, origin_epoch=0, now_epoch=0) == "admitted"
    assert h.admit_report(0, origin_epoch=0, now_epoch=3) == "duplicate"
    assert h.phi_of(0) == 2  # unchanged: duplicates are state-free


def test_seen_set_survives_state_round_trip():
    h = LinkHealth(n_paths=2, phi_steps=2, max_staleness_epochs=3)
    h.admit_report(0, origin_epoch=1, now_epoch=1)
    h2 = LinkHealth(n_paths=2, phi_steps=2, max_staleness_epochs=3)
    h2.restore(json.loads(json.dumps(h.state())))
    assert h2.admit_report(0, origin_epoch=1, now_epoch=2) == "duplicate"


# --------------------------------------------------------------- watchdog
def test_watchdog_state_machine():
    wd = TelemetryWatchdog(blackout_epochs=3)
    assert wd.observe(2) == "ok" and not wd.safe_mode
    assert wd.observe(0) == "silent"
    assert wd.observe(0) == "silent"
    assert wd.observe(0) == "safe" and wd.safe_mode
    assert wd.observe(0) == "safe"  # stays safe while silent
    assert wd.observe(1) == "recovered" and not wd.safe_mode
    assert wd.observe(0) == "silent"  # counter restarted after recovery


def test_watchdog_state_round_trip():
    wd = TelemetryWatchdog(blackout_epochs=2)
    wd.observe(0)
    wd2 = TelemetryWatchdog(blackout_epochs=2)
    wd2.restore(json.loads(json.dumps(wd.state())))
    assert wd2.observe(0) == "safe"  # one more silent epoch tips it


# --------------------------------------------- versioned plan application
def test_apply_plan_refuses_stale_and_duplicate_deliveries():
    p1 = collectives.PathPlan(directions=(1, -1), version=1)
    p2 = collectives.PathPlan(directions=(1, -1), inactive=(True, False),
                              version=2)
    cur, took = collectives.apply_plan(p1, p2)
    assert took and cur is p2
    # duplicated delivery of the applied plan: refused, state untouched
    cur2, took2 = collectives.apply_plan(cur, p2)
    assert not took2 and cur2 is p2
    # reordered delivery of the superseded plan: refused
    cur3, took3 = collectives.apply_plan(cur, p1)
    assert not took3 and cur3 is p2


def test_apply_plan_adversarial_delivery_order():
    # any interleaving of versions 1..5 with repeats must land on 5 and
    # never step backwards
    plans = {v: collectives.PathPlan(version=v) for v in range(1, 6)}
    deliveries = [3, 1, 4, 4, 2, 5, 3, 5, 1]
    cur = plans[1]
    seen_version = cur.version
    for v in deliveries:
        cur, took = collectives.apply_plan(cur, plans[v])
        assert cur.version >= seen_version
        assert took == (v > seen_version)
        seen_version = cur.version
    assert cur is plans[5]


def test_health_plan_stamps_version_from_step():
    h = LinkHealth(n_paths=2, phi_steps=2)
    assert h.plan(7).version == 7
    assert h.plan(7, version=3).version == 3


# --------------------------------------- campaign duplicate-event rejection
def test_campaign_rejects_duplicate_events():
    ev = LinkFlap(links=(1, 2), start_epoch=1, end_epoch=3)
    dup = LinkFlap(links=(1, 2), start_epoch=1, end_epoch=3, duty=0.9)
    with pytest.raises(AssertionError, match="duplicate campaign event"):
        FaultCampaign(events=(ev, dup))


def test_campaign_accepts_distinct_windows_on_same_links():
    ev1 = LinkFlap(links=(1,), start_epoch=1, end_epoch=3)
    ev2 = LinkFlap(links=(1,), start_epoch=3, end_epoch=5)
    FaultCampaign(events=(ev1, ev2))  # must not raise


def test_random_campaign_never_draws_duplicates():
    from repro.netsim import topology
    from repro.netsim.faults import _event_key, random_campaign

    topo = topology.leaf_spine(2, 4, 2, 40e9)
    for seed in range(12):
        c = random_campaign(topo, epochs=6, n_faults=5, seed=seed, n_ranks=8)
        keys = [_event_key(e) for e in c.events]
        assert len(keys) == len(set(keys)) == 5


# -------------------------------------------------- backoff jitter (sweep)
def test_retry_sleep_is_deterministic_and_decorrelated():
    from repro.netsim.sweep import retry_sleep_s

    a = retry_sleep_s(0, 1, backoff_s=1.0, jitter_frac=0.5)
    assert a == retry_sleep_s(0, 1, backoff_s=1.0, jitter_frac=0.5)
    assert 1.0 <= a <= 1.5
    # different jobs failing on the same attempt sleep different amounts —
    # the anti-synchronized-retry-storm property
    sleeps = {retry_sleep_s(i, 1, 1.0, 0.5) for i in range(8)}
    assert len(sleeps) == 8
    # exponential base still doubles under the jitter envelope
    assert retry_sleep_s(0, 3, 1.0, 0.0) == 4.0
    # the test fast path: zero backoff never sleeps
    assert retry_sleep_s(5, 4, 0.0, 0.5) == 0.0


# ---------------------------------------------------- co-sim driver twins
def _cosim_kw(topo):
    from repro.dist import cosim

    return dict(scheme="ecmp", epochs=6, phi_steps=2, n_chunks=8, seed=0,
                faults=(cosim.kill_spine(topo, 1, epoch=1, recover_epoch=3),))


@pytest.fixture(scope="module")
def small_topo():
    from repro.netsim import topology

    return topology.leaf_spine(2, 4, 2, 40e9)


def test_cosim_perfect_channel_matches_no_channel(small_topo):
    from repro.dist import cosim

    topo = small_topo
    hosts = cosim.ring_hosts(topo, 4)
    h0 = cosim.run_cosim(topo, hosts, 2e6, **_cosim_kw(topo))
    h1 = cosim.run_cosim(topo, hosts, 2e6, telemetry=TelemetryChannel(),
                         **_cosim_kw(topo))
    for a, b in zip(h0.records, h1.records):
        assert a.quarantined == b.quarantined
        assert a.reported_slow == b.reported_slow
        assert a.plan_churn == b.plan_churn
        assert a.completion == b.completion
        np.testing.assert_array_equal(a.fct, b.fct)
        assert not b.safe_mode
    assert h0.final_plan.inactive == h1.final_plan.inactive
    assert h1.plan_refused == 0
    # plan versions are strictly monotone across the whole run
    vs = [r.plan_version for r in h1.records]
    assert vs == sorted(vs) and len(set(vs)) == len(vs)


def test_cosim_blackout_trips_safe_mode_and_recovers(small_topo):
    from repro.dist import cosim

    topo = small_topo
    hosts = cosim.ring_hosts(topo, 4)
    h = cosim.run_cosim(
        topo, hosts, 2e6, scheme="ecmp", epochs=8, phi_steps=2, n_chunks=8,
        seed=0, telemetry=TelemetryChannel(blackout=(0, 4), seed=1),
        blackout_epochs=2,
        faults=(cosim.kill_spine(topo, 1, epoch=1, recover_epoch=6),))
    safe = [r.epoch for r in h.records if r.safe_mode]
    assert safe and min(safe) == 2  # k=2 silent epochs (0, 1) -> safe at 2
    # while safe the planner does not steer on stale state: no quarantines
    for r in h.records:
        if r.safe_mode:
            assert r.quarantined == ()
    # channel heals at 4 -> recovery; steering resumes and the run converges
    assert not h.records[-1].safe_mode
    assert any(r.quarantined for r in h.records[5:])
    assert h.records[-1].completion >= 1.0


@pytest.mark.parametrize("delay", [0, 1, 2])
def test_cosim_lossy_telemetry_reconverges_near_lossless(delay, small_topo):
    """Congestion reports over a lossy channel (10 % and 30 % loss) at a
    given delay: the planner reconverges within one epoch of the lossless
    channel at the same delay, and the delay alone costs at most its own
    epochs against a perfect channel.  Plan versions stay strictly
    monotone and no newer plan is refused, whatever the channel drops."""
    from repro.dist import cosim

    topo = small_topo
    hosts = cosim.ring_hosts(topo, 4)
    kw = dict(scheme="ecmp", epochs=8, phi_steps=2, n_chunks=8, seed=0,
              staleness_bound=2,
              faults=(cosim.kill_spine(topo, 1, epoch=1, recover_epoch=5),))
    conv = {}
    for loss in (0.0, 0.1, 0.3):
        h = cosim.run_cosim(topo, hosts, 2e6, telemetry=TelemetryChannel(
            loss=loss, delay_epochs=delay, seed=7), **kw)
        vs = [r.plan_version for r in h.records]
        assert all(b > a for a, b in zip(vs, vs[1:])), (loss, vs)
        assert h.plan_refused == 0
        conv[loss] = h.convergence_epoch(1)
        assert conv[loss] is not None, (loss, delay)
    perfect = cosim.run_cosim(topo, hosts, 2e6, telemetry=TelemetryChannel(),
                              **kw).convergence_epoch(1)
    assert conv[0.0] <= perfect + delay, (conv, perfect)
    assert conv[0.1] <= conv[0.0] + 1 and conv[0.3] <= conv[0.0] + 1, conv


def test_cosim_journal_schema_v2_and_refusal(tmp_path, small_topo):
    from repro.dist import cosim

    topo = small_topo
    hosts = cosim.ring_hosts(topo, 4)
    jp = os.path.join(tmp_path, "tele.jsonl")
    kw = dict(_cosim_kw(topo), epochs=3)
    cosim.run_cosim(topo, hosts, 2e6, journal=jp, **kw)
    lines = open(jp).read().splitlines()
    head = json.loads(lines[0])
    assert head["schema_version"] == cosim.JOURNAL_SCHEMA_VERSION == 2
    # an old-format journal (v1 header) refuses loudly instead of resuming
    head["schema_version"] = 1
    with open(jp, "w") as fh:
        fh.write(json.dumps(head) + "\n" + "\n".join(lines[1:]) + "\n")
    with pytest.raises(cosim.JournalSchemaError, match="schema_version=1"):
        cosim.run_cosim(topo, hosts, 2e6, journal=jp, **kw)


def test_cosim_telemetry_journal_resume_bit_identical(tmp_path, small_topo):
    from repro.dist import cosim

    topo = small_topo
    hosts = cosim.ring_hosts(topo, 4)
    jp = os.path.join(tmp_path, "tele_resume.jsonl")

    def mk_kw():
        return dict(_cosim_kw(topo),
                    telemetry=TelemetryChannel(loss=0.3, delay_epochs=1,
                                               dup=0.2, seed=5),
                    staleness_bound=2)

    h_full = cosim.run_cosim(topo, hosts, 2e6, journal=jp, **mk_kw())
    # tear the journal after epoch 2 and resume with a FRESH channel: the
    # journaled channel/watchdog state must carry the in-flight reports
    lines = open(jp).read().splitlines()
    with open(jp, "w") as fh:
        fh.write("\n".join(lines[:4]) + "\n" + lines[4][:40] + "\n")
    h_res = cosim.run_cosim(topo, hosts, 2e6, journal=jp, **mk_kw())
    for a, b in zip(h_res.records, h_full.records):
        assert a.epoch == b.epoch
        assert a.quarantined == b.quarantined
        assert a.reported_slow == b.reported_slow
        assert (a.reports_delivered, a.reports_admitted,
                a.reports_stale, a.reports_duplicate) == \
               (b.reports_delivered, b.reports_admitted,
                b.reports_stale, b.reports_duplicate)
        np.testing.assert_allclose(a.fct, b.fct, rtol=1e-6)
    assert h_res.final_plan.inactive == h_full.final_plan.inactive
