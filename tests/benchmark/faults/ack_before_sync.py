#!/usr/bin/env python3
"""The durable cell's role entry with its guarantee broken: a storage
whose ``sync()`` returns without having written its last drain. What a
drain appends is kept back in the process and reaches the file, and the
fsync, one drain late; the role releases its answers all the same, since
``sync()`` returned. The last drain before a kill is therefore
acknowledged and on no disk. Everything else runs as in the benchmark:
the sidecar above it records what the file system really holds."""

import sys

from _entry import role_entry  # noqa: F401  (puts the harness on the path)
from harness import role_entry_durable


def one_drain_late(base):
    class LateStorage(base):
        def __init__(self, root: str):
            super().__init__(root)
            self._held = None            # (segment, bytes) not yet written

        def _write_held(self) -> None:
            if self._held is not None:
                name, data = self._held
                self._held = None
                super().append(name, data)

        def append(self, name: str, data: bytes) -> None:
            self._write_held()
            self._held = (name, data)

        def delete(self, name: str) -> None:
            # A compaction deletes what it replaced: its own segment is
            # written whole, so that the fault stays the last drain's.
            self._write_held()
            for segment in self.segments():
                super().sync(segment)
            super().delete(name)

    return LateStorage


if __name__ == "__main__":
    role_entry_durable.main(sys.argv[1:], wrap_storage=one_drain_late)
