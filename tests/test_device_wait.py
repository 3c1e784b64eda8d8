"""PR 58: did the device wait?  ``StepTimeline`` polls the dispatch in
flight where it reads the clock, and books at every launch whether the chip
had run dry and for how long at least (``lo``) and at most (``hi``); it
counts the readbacks that found their dispatch done, and books a step's
upload to its class (docs/observability.md, "Did the device wait?").  A
timeline with an injected clock and scripted probes: here what one launch
books; ``test_device_wait_scripts.py`` whole scripts, and
``test_device_wait_engine.py`` real engines.  (Three files of at most six
tests: xdist's ``loadfile`` hands files out largest first, and a large one
lands beside a timed rehearsal: PERF.md section 7, Owed (11).)"""

import pytest

from dynamo_tpu.obs.timeline import CLASSES, StepTimeline

WAIT_KEYS = ("launches_total", "starved_launches_total",
             "device_wait_lo_seconds_total", "device_wait_hi_seconds_total")


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class Probe:
    """A dispatch that finishes at ``done_at`` on the clock."""

    def __init__(self, clock, done_at):
        self.clock, self.done_at, self.polls = clock, done_at, []

    def __call__(self):
        self.polls.append(self.clock.t)
        return self.clock.t >= self.done_at


def turn(tl, clock, kind=None, probe=None, read=(), pre=0.001, launch=0.0005,
         upload=0.0003):
    """One step: host phases of ``pre`` seconds each, then (with ``kind``) an
    upload and a dispatch of ``launch`` seconds that hands ``probe``, then
    one readback per entry of ``read`` — (kind, seconds it blocks).  Returns
    the clock reads (t_begin, t_d0, t_d1)."""
    t_begin = clock.t
    tl.begin()
    clock.t += pre
    for phase in ("host_ops", "admission", "host_build"):
        tl.enter(phase)
        clock.t += pre
    t_d0 = t_d1 = None
    if kind is not None:
        tl.enter("upload")
        clock.t += upload
        tl.enter("dispatch", kind=kind)
        t_d0 = clock.t
        clock.t += launch
        if probe is not None:
            tl.in_flight(probe)
        t_d1 = clock.t
    for rkind, seconds in read:
        tl.enter("readback", kind=rkind, issued=False)
        clock.t += seconds
        tl.enter("host_post")
        clock.t += pre
    tl.end()
    return t_begin, t_d0, t_d1


def delta(tl, before):
    after = tl.snapshot()
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float))}


def started(clock):
    """A timeline with one decode in flight that is still running."""
    tl = StepTimeline(clock=clock)
    p0 = Probe(clock, clock.t + 0.02)
    turn(tl, clock, "decode_multi", p0)
    return tl, p0


def test_a_dispatch_seen_done_at_begin_starves_the_launch_behind_it():
    clock = Clock()
    tl, p0 = started(clock)
    # turn 1 issues p1 behind p0 (still running), then blocks on p0
    p1 = Probe(clock, p0.done_at + 0.004)
    snap = tl.snapshot()
    turn(tl, clock, "decode_multi", p1, read=[("decode_multi", 0.012)])
    assert delta(tl, snap)["starved_launches_total"] == 0
    t_end = clock.t                     # p1 polled at the step's end: running
    clock.t = p1.done_at + 0.003        # the host is late: p1 is long done
    snap = tl.snapshot()
    t_begin, t_d0, t_d1 = turn(tl, clock, "decode_multi",
                               Probe(clock, clock.t + 1),
                               read=[("decode_multi", 0.0)])
    d = delta(tl, snap)
    assert d["launches_total"] == d["starved_launches_total"] == 1
    assert d["device_wait_lo_seconds_total"] == pytest.approx(t_d0 - t_begin)
    assert d["device_wait_hi_seconds_total"] == pytest.approx(t_d1 - t_end)
    assert p1.polls == [t_end - 0.001, t_end, t_begin]  # done: no more
    assert d["decode_ready_readbacks_total"] == 1


def test_a_dispatch_still_running_at_the_launch_books_nothing():
    clock = Clock()
    tl, p0 = started(clock)
    snap = tl.snapshot()
    t_begin, t_d0, t_d1 = turn(tl, clock, "decode_multi",
                               Probe(clock, clock.t + 1),
                               read=[("decode_multi", 0.015)])
    d = delta(tl, snap)
    assert d["launches_total"] == 1
    assert not any(d[k] for k in WAIT_KEYS[1:])
    assert d["decode_ready_readbacks_total"] == 0
    # polled at begin, at each enter up to the dispatch, and at its close
    assert len(p0.polls) == 7 and p0.polls[0] == t_begin
    assert p0.polls[-2:] == [t_d0, t_d1]


def test_with_nothing_in_flight_the_wait_runs_from_the_readback_that_blocked():
    clock = Clock()
    tl = StepTimeline(clock=clock)
    # serial steps: issue, then read the same dispatch back at once
    turn(tl, clock, "step", Probe(clock, clock.t + 1), read=[("step", 0.02)])
    t_read = clock.t - 0.001
    snap = tl.snapshot()
    _, t_d0, t_d1 = turn(tl, clock, "step", Probe(clock, clock.t + 1),
                         read=[("step", 0.02)], launch=0.0007)
    d = delta(tl, snap)
    assert d["starved_launches_total"] == 1
    assert d["device_wait_hi_seconds_total"] == pytest.approx(t_d1 - t_read)
    assert d["device_wait_lo_seconds_total"] == pytest.approx(
        d["device_wait_hi_seconds_total"] - 0.0007)     # hi - the launch
    # the same with no probe at all (unified, speculation, seq-parallel)
    snap = tl.snapshot()
    t_read = clock.t - 0.001
    _, t_d0, _ = turn(tl, clock, "unified", None, read=[(None, 0.03)])
    d = delta(tl, snap)
    assert d["starved_launches_total"] == 1
    assert d["device_wait_lo_seconds_total"] == pytest.approx(t_d0 - t_read)
    assert d["mixed_ready_readbacks_total"] == 0        # nothing says so


def test_a_step_with_no_work_cuts_the_wait_off():
    clock = Clock()
    tl = StepTimeline(clock=clock)
    turn(tl, clock, "step", Probe(clock, clock.t + 1), read=[("step", 0.02)])
    turn(tl, clock)                     # nothing to issue, nothing in flight
    clock.t += 5.0                      # the engine thread sleeps: idle
    snap = tl.snapshot()
    t_begin, t_d0, t_d1 = turn(tl, clock, "step", Probe(clock, clock.t + 1),
                               read=[("step", 0.02)])
    d = delta(tl, snap)
    # the request's own host work before its launch is waiting; idle is not
    assert d["device_wait_lo_seconds_total"] == pytest.approx(t_d0 - t_begin)
    assert d["device_wait_hi_seconds_total"] == pytest.approx(t_d1 - t_begin)
    assert tl.snapshot()["device_wait_hi_seconds_total"] < 0.1


def test_ready_readbacks_are_counted_by_the_class_of_the_step():
    clock = Clock()
    tl, p0 = started(clock)
    clock.t = p0.done_at + 0.001
    # a prefill turn that reads back the decode before it, long done
    snap = tl.snapshot()
    p1 = Probe(clock, clock.t + 0.05)
    turn(tl, clock, "step", p1, read=[("decode_multi", 0.0)])
    d = delta(tl, snap)
    assert d["prefill_ready_readbacks_total"] == 1
    assert d["decode_ready_readbacks_total"] == 0
    # a turn that only finishes: p1 still runs, the host blocks on it
    snap = tl.snapshot()
    turn(tl, clock, read=[("step", 0.06)])
    d = delta(tl, snap)
    assert sum(d[f"{c}_ready_readbacks_total"] for c in CLASSES) == 0
    assert d["prefill_steps_total"] == 1    # the class of what it finished
    # polled at the two clock reads left of the step that issued it, then
    # at begin and at each enter up to the readback's open
    assert len(p1.polls) == 7 and max(p1.polls) < p1.done_at


def test_upload_is_booked_by_class_and_is_part_of_the_launch():
    clock = Clock()
    tl = StepTimeline(clock=clock)
    for kind, upload in (("step", 0.0004), ("decode_multi", 0.0021),
                         ("decode_multi", 0.0019), ("unified", 0.001)):
        turn(tl, clock, kind, None, read=[(kind, 0.01)], upload=upload)
    snap = tl.snapshot()
    assert snap["prefill_upload_seconds_total"] == pytest.approx(0.0004)
    assert snap["decode_upload_seconds_total"] == pytest.approx(0.004)
    assert snap["mixed_upload_seconds_total"] == pytest.approx(0.001)
    for c in CLASSES:
        assert snap[f"{c}_upload_seconds_total"] \
            <= snap[f"{c}_launch_seconds_total"]
        assert snap[f"{c}_launch_seconds_total"] == pytest.approx(
            snap[f"{c}_upload_seconds_total"]
            + 0.0005 * snap[f"{c}_steps_total"])
    assert sum(snap[f"{c}_upload_seconds_total"] for c in CLASSES) \
        == pytest.approx(snap["phases"]["upload"], rel=1e-12)
