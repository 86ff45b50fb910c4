"""How much of the window's wall time the event loop's thread of the
busiest process of a kind spent in some stage: its busy stages plus
``loop-wait``. What is missing from 100 is time in no stage. The name
is ``stage.accounted_pct.<kind>``. A program that does not time its
loop's wait accounts for nothing."""

from harness.stages import growth, LOOP_WAIT, on_loop


def read(run, metric):
    kind = metric["name"].split(".", 2)[2]
    stages = growth(run, kind)
    if LOOP_WAIT not in stages:
        return None
    return 100.0 * sum(seconds for stage, (seconds, _) in stages.items()
                       if on_loop(stage)) / run.seconds
