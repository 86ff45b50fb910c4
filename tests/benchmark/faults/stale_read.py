#!/usr/bin/env python3
"""A stale read: a replica's store answers a ``Get`` of a key that has
been overwritten with the value the key held before its newest write.
Writes are applied as given, so the logs and the stores are sound; only
what a client is told is old. For deployments whose role entry is
``role_entry_ids.py`` (a loaded table: every key is overwritten by its
first update)."""

import sys

from _entry import HARNESS_PARENT  # noqa: F401  (puts harness/ in reach)
from harness import role_entry_ids


def answers_with_the_value_before_the_newest(store_class):
    apply = store_class.typed_run
    before: dict = {}

    def typed_run(self, input):
        writes = getattr(input, "key_values", None)
        if writes is not None:
            for key, _ in writes:
                if key in self.kvs:
                    before[key] = self.kvs[key]
            return apply(self, input)
        reply = apply(self, input)
        return type(reply)(tuple((key, before.get(key, value))
                                 for key, value in reply.key_values))

    store_class.typed_run = typed_run


if __name__ == "__main__":
    role_entry_ids.main(sys.argv[1:],
                        wrap_store=answers_with_the_value_before_the_newest)
