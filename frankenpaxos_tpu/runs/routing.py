"""ClientRequest routing for the run pipeline.

The paxingest destination ladder the multipaxos and mencius clients
each hard-coded: ingest disseminators absorb client fan-in when the
config deploys them (a resend re-rolls the pick, so a dead batcher
costs a retry, not a wedge), plain batchers come next, and the
protocol's own leader-selection rule is the fallback. Protocols that
route differently (per-group leaders, rounds) pass that rule in as
``leader_fallback`` -- the ladder itself is protocol-neutral.

paxfan: with a :class:`~frankenpaxos_tpu.ingest.fan.ShardRouter`
(``fan``) and a session key, the ingest tier is no longer a random
pick -- the key pins to one batcher on the consistent ring, a dead
batcher's keys fail over to clockwise survivors, and every other key
keeps its shard. The random pick remains the keyless fallback (and
the single-batcher degenerate case routes identically either way).
"""

from __future__ import annotations

import random
from typing import Callable, Optional


def make_fan_router(config, *, revive_after_s: float = 1.0,
                    batchers: bool = False):
    """A ShardRouter over the config's ingest tier; with ``batchers``
    (a protocol whose plain batchers take a client's array) over its
    plain batchers when it deploys no ingest tier; None when there is
    no such tier (the ladder falls through)."""
    count = getattr(config, "num_ingest_batchers", 0)
    if count <= 0 and batchers:
        count = getattr(config, "num_batchers", 0)
    if count <= 0:
        return None
    from frankenpaxos_tpu.ingest.fan import ShardRouter

    return ShardRouter(count, revive_after_s=revive_after_s)


def pick_request_destination(config, rng: random.Random,
                             leader_fallback: Callable,
                             fan=None, key: Optional[tuple] = None):
    """Destination for a single ClientRequest:
    ingest batchers (ring-pinned when ``fan``+``key`` are given,
    random otherwise) > batchers > ``leader_fallback()``."""
    if getattr(config, "num_ingest_batchers", 0) > 0:
        if fan is not None and key is not None:
            return config.ingest_batcher_addresses[
                fan.route(key[0], key[1])]
        return config.ingest_batcher_addresses[
            rng.randrange(config.num_ingest_batchers)]
    if getattr(config, "num_batchers", 0) > 0:
        return config.batcher_addresses[
            rng.randrange(config.num_batchers)]
    return leader_fallback()


def pick_array_destination(config, rng: random.Random,
                           leader_fallback: Callable,
                           fan=None, key: Optional[tuple] = None):
    """Destination for a staged ClientRequestArray: ingest batchers >
    plain batchers, when ``fan`` routes over them (a protocol whose
    batchers re-bucket an array's commands into batches) >
    ``leader_fallback()``. A staged array spans many pseudonyms of one
    client, so its ring key is the client-scoped sentinel the caller
    passes (conventionally ``(client_token, -1)``): a client's arrays
    stay with one batcher."""
    if getattr(config, "num_ingest_batchers", 0) > 0:
        addresses = config.ingest_batcher_addresses
    elif fan is not None and getattr(config, "num_batchers", 0) > 0:
        addresses = config.batcher_addresses
    else:
        return leader_fallback()
    if fan is not None and key is not None:
        return addresses[fan.route(key[0], key[1])]
    return addresses[rng.randrange(len(addresses))]
