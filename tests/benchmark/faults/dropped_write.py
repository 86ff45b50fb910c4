#!/usr/bin/env python3
"""An acknowledged write lost: every third write a replica's store is
given it answers and does not apply."""

import sys

from _entry import role_entry


def drops_every_third_write(store_class):
    apply = store_class.typed_run
    seen = [0]

    def typed_run(self, input):
        if hasattr(input, "key_values"):
            seen[0] += 1
            if seen[0] % 3 == 0:
                return apply(self, type(input)(key_values=()))
        return apply(self, input)

    store_class.typed_run = typed_run


if __name__ == "__main__":
    role_entry.main(sys.argv[1:], wrap_store=drops_every_third_write)
