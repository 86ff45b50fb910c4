"""CPU time of the busiest process of one kind over the window, as a
share of one core: user plus system time of all the process's threads,
from ``/proc`` at the window's two ends. The kind is the last part of
the metric's name and the start of the process's label. 100 % is one
core kept busy; the chip owner's process has several threads and can
pass it."""


def read(run, metric):
    kind = metric["name"].split(".")[-1]
    seconds = [s for label, s in run.cpu_s.items()
               if label.startswith(kind)]
    if not seconds:
        return None
    return 100.0 * max(seconds) / run.seconds
