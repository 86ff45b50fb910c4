"""What a Mencius deployment's own counters say over the window
(``/metrics``). The name is ``mencius.<what>``:

  noop_slot_share_pct   100 x the slots chosen as noops over all slots
                        chosen, in the replica that was sent the most
                        (``mencius_replica_chosen_slots_total`` by
                        ``kind``): how much of the log the lagging leader
                        groups filled with noop ranges
  votes_per_drain       votes the chip owner's device trackers took over
                        their drains
  launches_per_drain    jitted calls over those drains: 1.0 is one
                        launch a drain
  dense_vote_share_pct  100 x the votes that reached the device in a
                        dense block over all votes: strided runs and noop
                        ranges planned as blocks, not scattered

A program whose Mencius keeps none of these series gives nothing to read,
and neither does a window in which nothing was chosen or drained."""

from harness.readings import window_growth

CHOSEN = 'mencius_replica_chosen_slots_total{kind="%s"}'
DRAINS = 'mencius_proxy_leader_tpu_drains_total{path="device"}'
VOTES = 'mencius_proxy_leader_tpu_votes_total{path="device"}'
LAUNCHES = "mencius_proxy_leader_tpu_launches_total"
DENSE = "mencius_proxy_leader_dense_votes_total"


def replica_growth(run, label: str, name: str) -> float:
    first = run.scrapes["start"].get(label, {})
    return run.scrapes["end"][label].get(name, 0.0) - first.get(name, 0.0)


def noop_slot_share_pct(run):
    chosen = [(replica_growth(run, label, CHOSEN % "noop"),
               replica_growth(run, label, CHOSEN % "command"))
              for label in run.scrapes["end"] if label.startswith("replica")]
    if not chosen:
        return None
    noops, commands = max(chosen, key=sum)
    if noops + commands <= 0:
        return None
    return 100.0 * noops / (noops + commands)


def read(run, metric):
    what = metric["name"].split(".", 1)[1]
    if what == "noop_slot_share_pct":
        return noop_slot_share_pct(run)
    drains = window_growth(run, DRAINS)
    votes = window_growth(run, VOTES)
    if drains <= 0 or votes <= 0:
        return None
    if what == "votes_per_drain":
        return votes / drains
    if what == "launches_per_drain":
        return window_growth(run, LAUNCHES) / drains
    if what == "dense_vote_share_pct":
        return 100.0 * window_growth(run, DENSE) / votes
    raise ValueError(f"no reading called {metric['name']!r}")
