"""Mean host span of a tracker ``drain()`` that had votes, dispatch
included (the role entry's annotation, from the trace)."""

from harness.readings import mean_span_ms
from harness.role_entry import DRAIN_SPAN


def read(run, metric):
    return mean_span_ms(run, DRAIN_SPAN)
