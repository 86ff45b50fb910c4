#!/usr/bin/env python3
"""The control: the benchmark's role entry with one guarantee of the
configuration broken. The tracker takes a single acceptor's vote for a
write quorum (f + 1 = 2 of the group, or one of each grid row, is what
the configuration states), so slots are reported chosen before a quorum
holds them. Everything else runs as in the benchmark."""

import dataclasses
import sys

from _entry import role_entry


def one_ack_is_enough(base):
    class OneAck(base):
        def __init__(self, config, *args, **kwargs):
            super().__init__(
                dataclasses.replace(config, f=0, flexible=False),
                *args, **kwargs)

    return OneAck


if __name__ == "__main__":
    role_entry.main(sys.argv[1:], wrap_tracker=one_ack_is_enough)
