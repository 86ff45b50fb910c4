"""The least time the chip could take for the traced span's epoch-checked
quorum work over the time its device was busy.

``least_bytes`` counts the work, not the implementation: what any program
that decides these votes under per-slot epochs has to move, whatever its
kernels are called and however it pads or chunks. Memory bandwidth bounds
it; the predicate is a handful of integer adds a vote."""

from harness.readings import span_growth
from harness.trace_reduce import peaks_of

VOTES = "multipaxos_proxy_leader_epoch_votes_total"
LAUNCHES = "multipaxos_proxy_leader_epoch_launches_total"
PLANES = "multipaxos_proxy_leader_epoch_planes"

#: A vote as it comes in: slot, round, voter, an int32 each.
VOTE_BYTES = 12
#: A slot's state beside its votes: round and owner (int32) and the chosen
#: flag, each read and written.
SLOT_STATE_BYTES = 2 * (4 + 4 + 1)


def least_bytes(votes: int, launches: int, nodes: int, planes: int,
                groups: int) -> int:
    """A launch reads the ``[planes, groups, nodes]`` masks (a byte each)
    and the ``[planes, groups]`` thresholds (int32) once. A vote comes in,
    reads and writes its slot's column of ``nodes`` cells and the slot's
    state, and one byte of answer goes out."""
    a_launch = planes * groups * nodes + 4 * planes * groups
    a_vote = VOTE_BYTES + 2 * nodes + SLOT_STATE_BYTES + 1
    return launches * a_launch + votes * a_vote


def read(run, metric):
    peak = peaks_of(run.device["kind"])["hbm_bytes_per_s"]
    busy_s = run.trace["busy_s"]
    votes = span_growth(run, VOTES)
    planes = run.span["after"]["metrics"].get(PLANES)
    if peak is None or busy_s <= 0 or votes <= 0 or not planes:
        return None
    work = least_bytes(int(votes), int(span_growth(run, LAUNCHES)),
                       run.config["board"]["nodes"], int(planes),
                       len(run.config["quorum"]["rows"]))
    return 100.0 * (work / peak) / busy_s
