#!/usr/bin/env python3
"""The control of the Mencius cell: its role entry with one guarantee
broken. The chip owner's board counts a slot's votes over every leader
group's acceptors (one row over all of them, f + 1 of any), and each
strided range of votes it takes comes with one more, from the acceptor
of the same index in the next group. So a slot is reported chosen after
one vote of its own group: a quorum of its own group's acceptors is
what the configuration states. Everything else runs as in the
benchmark."""

import sys

from _entry import role_entry  # noqa: F401 -- benchmark/ on the path
from harness import role_entry_mencius
import numpy as np


def counts_a_foreign_vote(base):
    from frankenpaxos_tpu.quorums import QuorumSpec
    from frankenpaxos_tpu.quorums.spec import ANY

    class ForeignVote(base):
        def __init__(self, *args, spec=None, **kwargs):
            self.groups = 1
            if spec is not None:
                self.groups = spec.masks.shape[0]
                spec = QuorumSpec(
                    masks=np.ones((1, spec.masks.shape[1]), np.uint8),
                    thresholds=spec.thresholds[:1], combine=ANY,
                    universe=spec.universe)
            super().__init__(*args, spec=spec, **kwargs)

        def record_range(self, slot_start, slot_end, round, group_index,
                         acceptor_index, stride=1):
            super().record_range(slot_start, slot_end, round, group_index,
                                 acceptor_index, stride)
            if stride > 1:
                super().record_range(
                    slot_start, slot_end, round,
                    (group_index + 1) % self.groups, acceptor_index,
                    stride)

    return ForeignVote


if __name__ == "__main__":
    role_entry_mencius.main(sys.argv[1:],
                            wrap_tracker=counts_a_foreign_vote)
