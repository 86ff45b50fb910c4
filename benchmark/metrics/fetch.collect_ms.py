"""Mean host span of a tracker ``collect()``: the wait for the device
and the fetch of what was chosen (the role entry's annotation, from the
trace)."""

from harness.readings import mean_span_ms
from harness.role_entry import COLLECT_SPAN


def read(run, metric):
    return mean_span_ms(run, COLLECT_SPAN)
