#!/usr/bin/env python3
"""One role process of a benchmarked deployment whose values are wide.

``role_entry.py`` with another recorder on a replica's store. That one
keeps every executed value whole and dumps the store as JSON, which at
1 KB a record and 100 k records is hundreds of MB a replica on disk and
again in the launcher. This one keeps, for every executed write, its
key, the first 16 characters of its value (the write's id, as the
generators write it) and the value's length, and writes to
``<label>.replica.npz``:

  keys, values, lengths        every executed write in order: the key's
                               place in ``key_names``, the id's 16
                               characters, the value's length
  key_names                    the keys that were written, sorted
  store_keys, store_values,    the store's final contents, one row a
  store_lengths                key, in the same three columns

``<label>.json`` then carries no ``stores`` and no ``key_names``; the
configuration's reference reads the arrays. Everything else (the
tracker's recorder, the timed claim, the traced span, the counters) is
``role_entry.main``, untouched.

How: ``role_entry.main`` installs its own recorder on
``KeyValueStore`` and then calls ``wrap_store``; the one given here puts
the store's own ``__init__`` and ``typed_run`` (taken before) back
underneath this file's recorder, so ``role_entry``'s sees no store and
dumps none. A planted fault's ``wrap_store`` goes on top, between the
replica and the recorded store, as there.
"""

from __future__ import annotations

import atexit
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HARNESS_PARENT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, HARNESS_PARENT):
    if path not in sys.path:
        sys.path.insert(0, path)

from harness import role_entry  # noqa: E402

ID_DIGITS = 16


def main(argv: list, wrap_tracker=None, wrap_store=None) -> None:
    import numpy as np

    from frankenpaxos_tpu.deploy import process_label
    from frankenpaxos_tpu.statemachine import impls

    record_dir, cli_argv = argv[0], argv[2:]
    label = process_label(cli_argv[cli_argv.index("--role") + 1],
                          cli_argv[cli_argv.index("--index") + 1])
    store_init = impls.KeyValueStore.__init__
    store_run = impls.KeyValueStore.typed_run
    stores: list = []
    executed_keys: list = []
    executed_ids: list = []
    executed_lengths: list = []

    def recording_init(self, *args, **kwargs):
        store_init(self, *args, **kwargs)
        stores.append(self)

    def recording_run(self, input):
        writes = getattr(input, "key_values", None)
        if writes is not None:
            for key, value in writes:
                executed_keys.append(key)
                executed_ids.append(value[:ID_DIGITS])
                executed_lengths.append(len(value))
        return store_run(self, input)

    def record_ids(store_class) -> None:
        store_class.__init__ = recording_init
        store_class.typed_run = recording_run
        if wrap_store is not None:
            wrap_store(store_class)

    def dump() -> None:
        if not stores:
            return
        names = sorted(set(executed_keys))
        place = {name: n for n, name in enumerate(names)}
        final = [(place[key], value[:ID_DIGITS], len(value))
                 for store in stores for key, value in store.kvs.items()]
        np.savez(
            os.path.join(record_dir, f"{label}.replica.npz"),
            keys=np.fromiter((place[k] for k in executed_keys),
                             dtype=np.int32, count=len(executed_keys)),
            values=np.array(executed_ids, dtype=f"S{ID_DIGITS}"),
            lengths=np.array(executed_lengths, dtype=np.int32),
            key_names=np.array(names, dtype="U"),
            store_keys=np.array([k for k, _, _ in final], dtype=np.int32),
            store_values=np.array([v for _, v, _ in final],
                                  dtype=f"S{ID_DIGITS}"),
            store_lengths=np.array([n for _, _, n in final],
                                   dtype=np.int32))

    atexit.register(dump)
    role_entry.main(argv, wrap_tracker=wrap_tracker, wrap_store=record_ids)


if __name__ == "__main__":
    main(sys.argv[1:])
