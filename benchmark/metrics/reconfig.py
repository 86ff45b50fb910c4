"""What the reconfigurations cost, from the program's own counters over
the window (``/metrics``). The name is ``reconfig.<what>``:

  handover_ms           mean of the leaders' stage ``epoch-handover``: from
                        a commit's first send to its activation, the wait
                        in which proposals are held back
  epochs_in_window      epochs the leaders activated
                        (``multipaxos_leader_epoch_changes_total``)
  votes_per_launch      votes the chip owner's epoch trackers took a jitted
                        call (``multipaxos_proxy_leader_epoch_votes_total``
                        over ``..._epoch_launches_total``)
  planes                K, the epochs whose planes a check reads, at the
                        window's end (``..._epoch_planes``)

A program without the series gives nothing to read."""

from harness.readings import window_growth
from harness.stages import growth

CHANGES = "multipaxos_leader_epoch_changes_total"
VOTES = "multipaxos_proxy_leader_epoch_votes_total"
LAUNCHES = "multipaxos_proxy_leader_epoch_launches_total"
PLANES = "multipaxos_proxy_leader_epoch_planes"


def leaders_growth(run, name: str) -> float:
    """Growth of ``name`` over the window, over all leader processes."""
    return sum(scrape.get(name, 0.0)
               - run.scrapes["start"].get(label, {}).get(name, 0.0)
               for label, scrape in run.scrapes["end"].items()
               if label.startswith("leader"))


def read(run, metric):
    what = metric["name"].split(".", 1)[1]
    if what == "handover_ms":
        found = growth(run, "leader").get("epoch-handover")
        return None if found is None else 1e3 * found[0] / found[1]
    if what == "planes":
        return run.scrapes["end"][run.chip_owner].get(PLANES)
    if what == "epochs_in_window":
        return leaders_growth(run, CHANGES) or None
    if what == "votes_per_launch":
        launches = window_growth(run, LAUNCHES)
        return window_growth(run, VOTES) / launches if launches else None
    raise ValueError(f"no reading called {metric['name']!r}")
