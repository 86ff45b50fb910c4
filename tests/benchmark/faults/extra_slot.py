#!/usr/bin/env python3
"""An answer altered where it is produced: now and then the tracker
reports a slot that no acceptor voted for."""

import sys

from _entry import role_entry


def reports_an_extra_slot(base):
    class ExtraSlot(base):
        collects = 0

        def collect(self, dispatch):
            out = super().collect(dispatch)
            ExtraSlot.collects += 1
            if ExtraSlot.collects % 20 == 0:
                out = out + [(10 ** 9 + ExtraSlot.collects, 0)]
            return out

    return ExtraSlot


if __name__ == "__main__":
    role_entry.main(sys.argv[1:], wrap_tracker=reports_an_extra_slot)
