"""PR 58: did the device wait?  Whole scripts of turns on a ``StepTimeline``
with an injected clock and scripted probes (``test_device_wait.py`` holds the
helpers and what a single launch books)."""

import random

import pytest

from dynamo_tpu.obs.timeline import CLASSES, StepTimeline
from test_device_wait import WAIT_KEYS, Clock, Probe, delta, turn


def test_the_launch_counters_are_totals_and_ready_readbacks_tell_the_class():
    """The four launch counters are kept once, whatever kind was launched;
    which class of turn found the chip dry is what the ready readbacks say:
    where every turn issues ahead they are the starved launches, by class."""
    clock = Clock()
    tl = StepTimeline(clock=clock)
    kinds = ["step", "decode_multi", "prefill_ragged", "unified",
             "spec_verify", "sp_prefill", "decode_multi"]
    for kind in kinds:      # serial: each reads back what it issued
        turn(tl, clock, kind, Probe(clock, clock.t), read=[(kind, 0.001)])
    snap = tl.snapshot()
    assert snap["launches_total"] == snap["starved_launches_total"] == 7
    assert 0 < snap["device_wait_lo_seconds_total"] \
        < snap["device_wait_hi_seconds_total"]
    assert not [k for k in snap if k.endswith(WAIT_KEYS) and k not in WAIT_KEYS]
    # every readback found its dispatch done (``done_at`` = its issue)
    assert [snap[f"{c}_ready_readbacks_total"] for c in CLASSES] == [3, 3, 1]
    # dispatch-ahead: turn k issues p_k behind p_(k-1) and then reads that
    rng = random.Random(7)
    tl = StepTimeline(clock=clock)
    before, starved_by_class = None, dict.fromkeys(CLASSES, 0)
    for k in range(200):
        kind = rng.choice(["step", "decode_multi", "unified"])
        snap = tl.snapshot()
        turn(tl, clock, kind, Probe(clock, clock.t + rng.random() * 0.03),
             read=[(before, rng.random() * 0.01)] if before else (),
             pre=rng.random() * 0.003)
        cls = {"step": "prefill", "decode_multi": "decode"}.get(kind, "mixed")
        starved_by_class[cls] += delta(tl, snap)["starved_launches_total"] \
            if before else 0    # the first has nothing before it to read
        before = kind
    snap = tl.snapshot()
    assert 20 < snap["starved_launches_total"] < 180
    assert {c: snap[f"{c}_ready_readbacks_total"] for c in CLASSES} \
        == starved_by_class


@pytest.mark.parametrize("seed", range(4))
def test_lo_below_hi_and_launches_add_up_over_a_random_script(seed):
    rng = random.Random(seed)
    clock = Clock()
    tl = StepTimeline(clock=clock)
    t_first = clock.t
    unread = []                                 # kinds issued, not read
    for _ in range(300):
        snap = tl.snapshot()
        roll = rng.random()
        if roll < 0.1 and not unread:
            turn(tl, clock)                     # no work
            clock.t += rng.random() * 0.05
            continue
        kind = None
        if roll < 0.9 or not unread:
            kind = rng.choice(["step", "decode_multi", "prefill_ragged",
                               "unified"])
        probe = rng.choice([None, Probe(clock, clock.t + rng.random() * 0.02)])
        read = [(k, rng.random() * 0.01) for k in unread]
        unread = []
        if kind is not None:
            if rng.random() < 0.7:
                unread = [kind]                 # stays in flight
            else:
                read.append((kind, rng.random() * 0.01))
        turn(tl, clock, kind, probe, read=read, pre=rng.random() * 0.002)
        d = delta(tl, snap)
        assert 0.0 <= d["device_wait_lo_seconds_total"] \
            <= d["device_wait_hi_seconds_total"] + 1e-12
        if not d["starved_launches_total"]:
            assert d["device_wait_hi_seconds_total"] == 0.0
    snap = tl.snapshot()
    assert 0 < snap["starved_launches_total"] < snap["launches_total"]
    assert snap["launches_total"] == sum(
        k["count"] for k in snap["dispatch_kinds"].values())
    assert snap["device_wait_lo_seconds_total"] <= clock.t - t_first
    assert snap["device_wait_hi_seconds_total"] <= clock.t - t_first
    assert sum(snap[f"{c}_ready_readbacks_total"] for c in CLASSES) \
        <= snap["launches_total"]


def test_a_probe_that_raises_never_reaches_the_step_loop():
    clock = Clock()
    tl = StepTimeline(clock=clock)
    calls = []

    def deleted():
        calls.append(clock.t)
        raise RuntimeError("Array has been deleted.")

    turn(tl, clock, "decode_multi", deleted)
    snap = tl.snapshot()
    turn(tl, clock, "decode_multi", Probe(clock, clock.t + 1),
         read=[("decode_multi", 0.01)])
    assert len(calls) == 1              # dropped at its first raise
    d = delta(tl, snap)
    # what cannot be watched counts as running: nothing is booked
    assert d["launches_total"] == 1 and d["starved_launches_total"] == 0
    assert d["decode_ready_readbacks_total"] == 0
    # a dispatch the engine dropped unread (fail_all) is forgotten
    tl.in_flight(None)
    assert tl._flight == ()
