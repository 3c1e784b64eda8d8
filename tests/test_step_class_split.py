"""PR 43: the turn's phases by class.  ``StepTimeline.end`` adds a step's
``upload`` + ``dispatch`` (launch) and its ``readback`` (the host blocked on
the device: its slack in that turn) to the class of the dispatch the step
issues (docs/observability.md, "Launch and readback by class")."""

import pytest

from dynamo_tpu.obs.timeline import CLASSES, StepTimeline, step_timeline
from test_request_stages import stages, tiny  # noqa: F401  (fixtures)


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# (phase, kwargs of enter, seconds it lasts)
AHEAD_DECODE = [("upload", {}, 0.0007),
                ("dispatch", dict(kind="decode_multi"), 0.0005),
                ("readback", dict(kind="decode_multi", issued=False), 0.011),
                ("host_post", {}, 0.002)]
PREFILL_TURN = [("upload", {}, 0.0009), ("dispatch", dict(kind="step"), 0.0006),
                ("readback", dict(kind="decode_multi", issued=False), 0.009),
                ("host_post", {}, 0.004)]
UNIFIED = [("upload", {}, 0.001), ("dispatch", dict(kind="unified"), 0.0004),
           ("readback", {}, 0.03), ("host_post", {}, 0.001)]
ONLY_FINISHES = [("readback", dict(kind="step", issued=False), 0.02),
                 ("host_post", {}, 0.001)]


def play(tl, clock, script):
    tl.begin("host_build")
    clock.t += 0.003
    for phase, kw, seconds in script:
        tl.enter(phase, **kw)
        clock.t += seconds
    tl.end()


@pytest.mark.parametrize("cls", CLASSES)
def test_launch_and_readback_add_up_to_the_device_facing_time_and_the_upload(cls):
    clock = Clock()
    tl = StepTimeline(clock=clock)
    scripts = [AHEAD_DECODE, PREFILL_TURN, AHEAD_DECODE, UNIFIED,
               ONLY_FINISHES, PREFILL_TURN, AHEAD_DECODE]
    uploads = {c: 0.0 for c in CLASSES}
    for script in scripts:
        play(tl, clock, script)
        of = {"decode_multi": "decode", "step": "prefill",
              "unified": "mixed"}[script[1][1].get("kind") or
                                  script[0][1]["kind"]]
        uploads[of] += sum(s for p, _, s in script if p == "upload")
    snap = tl.snapshot()
    assert [snap[f"{c}_steps_total"] for c in CLASSES] == [3, 3, 1]
    launch = snap[f"{cls}_launch_seconds_total"]
    readback = snap[f"{cls}_readback_seconds_total"]
    assert launch + readback == pytest.approx(
        snap[f"{cls}_device_seconds_total"] + uploads[cls], rel=1e-12)
    assert launch + readback <= snap[f"{cls}_wall_seconds_total"]
    # a prefill turn's readback is of the decode before it (and the turn
    # that only finishes reads back what the prefill turn before it issued)
    assert snap["prefill_readback_seconds_total"] == pytest.approx(
        2 * 0.009 + 0.02)
    assert snap["decode_launch_seconds_total"] == pytest.approx(3 * 0.0012)
    # over the classes: every upload, dispatch and readback second
    assert sum(snap[f"{c}_launch_seconds_total"]
               + snap[f"{c}_readback_seconds_total"] for c in CLASSES) \
        == pytest.approx(sum(snap["phases"][p] for p in
                             ("upload", "dispatch", "readback")), rel=1e-12)
    assert all(isinstance(snap[f"{c}_{k}_seconds_total"], float)
               for c in CLASSES for k in ("launch", "readback"))


def test_the_class_split_holds_on_a_real_engine(stages):
    snap = step_timeline.snapshot()
    for c in ("prefill", "decode"):
        assert snap[f"{c}_steps_total"] > 0
        assert 0 < snap[f"{c}_launch_seconds_total"]
        assert snap[f"{c}_launch_seconds_total"] \
            + snap[f"{c}_readback_seconds_total"] \
            <= snap[f"{c}_wall_seconds_total"]
    assert sum(snap[f"{c}_launch_seconds_total"]
               + snap[f"{c}_readback_seconds_total"] for c in CLASSES) \
        == pytest.approx(sum(snap["phases"][p] for p in
                             ("upload", "dispatch", "readback")), rel=1e-9)
