#!/usr/bin/env python3
"""An acknowledged write lost inside a batch: a replica answers the last
command of every slot that holds more than one, and does not apply it.
Slots of one command are executed as they should be."""

import sys

from _entry import role_entry


class LastOfBatch(bytes):
    """The payload of a command that ends a multi-command slot."""


def marked(rows):
    """A replica's rows (``LazyValueArray.rows``), the last command of
    each multi-command slot marked."""
    for row in rows:
        if isinstance(row, list) and len(row) > 1:
            address, pseudonym, client_id, payload = row[-1]
            row = row[:-1] + [(address, pseudonym, client_id,
                               LastOfBatch(payload))]
        yield row


def skips_the_last_command_of_every_batch(store_class):
    from frankenpaxos_tpu.protocols.multipaxos import replica

    execute = replica.Replica._execute_rows

    def execute_rows(self, rows, replies):
        return execute(self, marked(rows), replies)

    replica.Replica._execute_rows = execute_rows
    run = store_class.run

    def running(self, input):
        if type(input) is LastOfBatch:
            typed = self.input_serializer.from_bytes(input)
            return self.output_serializer.to_bytes(
                self.typed_run(type(typed)(key_values=())))
        return run(self, input)

    store_class.run = running


if __name__ == "__main__":
    role_entry.main(sys.argv[1:],
                    wrap_store=skips_the_last_command_of_every_batch)
