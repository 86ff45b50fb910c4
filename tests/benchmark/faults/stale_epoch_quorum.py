#!/usr/bin/env python3
"""The reconfigured deployment's role entry with one guarantee broken: a
proxy leader whose epoch store answers for every epoch past the first
with the PREVIOUS epoch's members. It fans a slot's proposal out to two
of the acceptors that were replaced, and its tracker counts the slot's
votes under them: slots are reported chosen on the votes of acceptors
that are not members of the slot's epoch. The leaders, the acceptors and
the replicas run as in the benchmark, and so does everything above the
store: the recorders see what the tracker was really fed and what it
reported."""

import dataclasses
import sys

from _entry import role_entry  # noqa: F401  (puts the harness on the path)
from harness import role_entry_reconfig


def one_epoch_late():
    if role_entry_reconfig.ROOT not in sys.path:
        sys.path.insert(0, role_entry_reconfig.ROOT)
    from frankenpaxos_tpu.protocols.multipaxos import proxy_leader

    class StaleStore(proxy_leader.EpochStore):
        def _stale(self, config):
            before = super().config(config.epoch - 1)
            if before is None:
                return config
            return dataclasses.replace(config, members=before.members)

        def config(self, epoch):
            found = super().config(epoch)
            return None if found is None else self._stale(found)

        def epoch_of_slot(self, slot):
            return self._stale(super().epoch_of_slot(slot))

        def spec(self, config):
            return super().spec(self._stale(config))

    proxy_leader.EpochStore = StaleStore


if __name__ == "__main__":
    one_epoch_late()
    role_entry_reconfig.main(sys.argv[1:])
