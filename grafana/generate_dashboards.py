"""Generate per-protocol Grafana dashboards from the deploy registry.

The reference provisions a hand-written dashboard per protocol
(/grafana/dashboards/: echo, epaxos, mencius, scalog, ... 15 total).
Here every deployed protocol gets one generated from its actual role
list, charting the uniform per-role metrics the CLI exports for every
role (``<protocol>_<role>_requests_total{type=...}`` and
``..._requests_latency_seconds`` -- see
``runtime.monitoring.instrument_actor``). The multipaxos and batching
dashboards are hand-written (richer, protocol-specific) and are not
regenerated.

Run from the repo root::

    python grafana/generate_dashboards.py
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from frankenpaxos_tpu.deploy import PROTOCOL_NAMES, get_protocol  # noqa: E402

HAND_WRITTEN = {"multipaxos"}
OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "dashboards")

_DATASOURCE = {"type": "prometheus", "uid": "${DS_PROMETHEUS}"}


def _panel(panel_id: int, title: str, expr: str, legend: str, unit: str,
           x: int, y: int, w: int = 12, extra: list = ()) -> dict:
    targets = [{"expr": expr, "legendFormat": legend, "refId": "A"}]
    for n, (more_expr, more_legend) in enumerate(extra):
        targets.append({"expr": more_expr, "legendFormat": more_legend,
                        "refId": chr(ord("B") + n)})
    return {
        "id": panel_id,
        "type": "timeseries",
        "title": title,
        "gridPos": {"h": 8, "w": w, "x": x, "y": y},
        "datasource": _DATASOURCE,
        "fieldConfig": {"defaults": {"unit": unit}, "overrides": []},
        "targets": targets,
    }


# The SHARED runtime row (paxtrace, obs/): the same panels on every
# protocol dashboard, over the uniform fpx_runtime_* metrics the
# transports/WAL export for every role (see obs.RuntimeMetrics) --
# drain-stage time share, inbound queue depth, WAL group-commit fsync
# latency, and (paxload, serve/) the admission/backpressure band:
# admitted-vs-rejected rates, shed/reject reasons, bounded-queue depth
# + in-flight span, client retry discipline. Panel ids 9000+ so they
# never collide with the per-role panels (generated) or the
# hand-written multipaxos ones.
RUNTIME_ROW_TITLE = ("Runtime (drain stages / queue depth / WAL fsync / "
                     "admission)")

#: Total grid height of the runtime row: header (1) + the paxtrace
#: band (8) + the paxload admission band (8) + the paxwire transport
#: band (8) + the paxworld global-serving band (8) + the paxingest
#: ingestion band (8) + the paxfan shard band (8) + the paxpulse
#: device-pipeline band (8). dashboard() and inject_runtime_row()
#: both lay out protocol panels below this line.
RUNTIME_ROW_H = 57


def runtime_row_panels(y: int = 0) -> list:
    fsync = _panel(
        9003, "WAL fsync latency p99 / mean",
        "histogram_quantile(0.99, sum by (le) "
        "(rate(fpx_runtime_wal_fsync_seconds_bucket[5s])))",
        "p99", "s", x=12, y=y + 1, w=6)
    # The fsync panel charts the p99 AND the mean on one graph.
    fsync["targets"].append({
        "expr": ("sum(rate(fpx_runtime_wal_fsync_seconds_sum[5s])) / "
                 "sum(rate(fpx_runtime_wal_fsync_seconds_count[5s]))"),
        "legendFormat": "mean",
        "refId": "B",
    })
    # The log's own counts (wal.WalMetrics through obs._WalSeries):
    # only roles that have a WAL export them.
    wal_counts = _panel(
        9026, "WAL: synced bytes/s, records/s, compactions",
        "sum by (role) (rate(fpx_runtime_wal_synced_bytes_total[5s]))",
        "bytes {{role}}", "Bps", x=18, y=y + 1, w=6,
        extra=[
            ("sum by (role) "
             "(rate(fpx_runtime_wal_synced_records_total[5s]))",
             "records {{role}}"),
            ("sum by (role) "
             "(rate(fpx_runtime_wal_compactions_total[5s]))",
             "compactions {{role}}"),
            ("sum by (role) "
             "(rate(fpx_runtime_wal_compaction_seconds_sum[5s]))",
             "compacting s/s {{role}}"),
            ("fpx_runtime_wal_recovered_records_total",
             "recovered at start {{role}}"),
        ])
    admitted = _panel(
        9004, "Admission: admitted vs rejected",
        "sum by (role) "
        "(rate(fpx_runtime_admission_admitted_total[5s]))",
        "admitted {{role}}", "ops", x=0, y=y + 9, w=6)
    admitted["targets"].append({
        "expr": ("sum(rate(fpx_runtime_admission_rejected_total[5s]))"),
        "legendFormat": "rejected (all)",
        "refId": "B",
    })
    reasons = _panel(
        9005, "Rejections by reason / sheds by policy",
        "sum by (reason) "
        "(rate(fpx_runtime_admission_rejected_total[5s]))",
        "{{reason}}", "ops", x=6, y=y + 9, w=6)
    reasons["targets"].append({
        "expr": ("sum by (policy) "
                 "(rate(fpx_runtime_admission_shed_total[5s]))"),
        "legendFormat": "shed {{policy}}",
        "refId": "B",
    })
    depth = _panel(
        9006, "Bounded-inbox depth / in-flight span",
        "fpx_runtime_admission_queue_depth",
        "inbox {{role}}", "short", x=12, y=y + 9, w=6)
    depth["targets"].append({
        "expr": "fpx_runtime_admission_inflight",
        "legendFormat": "inflight {{role}}",
        "refId": "B",
    })
    commit_rate = _panel(
        9016, "Device pipeline: committed / proposed rate",
        "sum by (role) (rate(fpx_pipeline_committed_total[5s]))",
        "committed {{role}}", "ops", x=0, y=y + 49, w=4,
        extra=[
            ("sum by (role) (rate(fpx_pipeline_proposed_total[5s]))",
             "proposed {{role}}"),
            ("sum by (role) (rate(fpx_pipeline_drains_total[5s]))",
             "drains {{role}}"),
        ])
    shard_band = _panel(
        9017, "Device pipeline: per-shard committed + skew",
        "fpx_pipeline_shard_committed",
        "shard {{shard}}", "short", x=4, y=y + 49, w=4,
        extra=[("fpx_pipeline_shard_skew_ratio",
                "skew {{role}}")])
    lag_band = _panel(
        9019, "Device pipeline: watermark lag + pad waste",
        "sum by (bucket) "
        "(rate(fpx_pipeline_watermark_lag_total[5s]))",
        "lag bucket {{bucket}}", "ops", x=12, y=y + 49, w=4,
        extra=[("sum by (role) "
                "(rate(fpx_pipeline_pad_lanes_total[5s]))",
                "pad lanes {{role}}")])
    fill_band = _panel(
        9020, "Device pipeline: proposal batch fill",
        "fpx_pipeline_batch_fill",
        "fill {{role}}", "percentunit", x=16, y=y + 49, w=4)
    return [
        {
            "id": 9000,
            "type": "row",
            "title": RUNTIME_ROW_TITLE,
            "collapsed": False,
            "gridPos": {"h": 1, "w": 24, "x": 0, "y": y},
            "panels": [],
        },
        _panel(
            9001, "Drain-stage time share",
            "sum by (stage) "
            "(rate(fpx_runtime_drain_stage_seconds_sum[5s]))",
            "{{stage}}", "s", x=0, y=y + 1, w=6),
        _panel(
            9002, "Inbound queue depth (msgs/drain)",
            "fpx_runtime_inbound_queue_depth",
            "{{role}}", "short", x=6, y=y + 1, w=6),
        fsync,
        wal_counts,
        admitted,
        reasons,
        depth,
        _panel(
            9007, "Client retries (backoff/failover/giveup)",
            "sum by (kind) "
            "(rate(fpx_runtime_client_retries_total[5s]))",
            "{{kind}}", "ops", x=18, y=y + 9, w=6),
        # paxwire batched-transport band (docs/TRANSPORT.md): writev
        # batching effectiveness, ack coalescing rate, batched bytes.
        _panel(
            9008, "Transport: frames per writev",
            "fpx_runtime_transport_frames_per_writev",
            "{{role}}", "short", x=0, y=y + 17, w=8),
        _panel(
            9009, "Transport: coalesced acks/s + outbound stalls",
            "sum by (role) "
            "(rate(fpx_runtime_transport_coalesced_acks_total[5s]))",
            "{{role}}", "ops", x=8, y=y + 17, w=8,
            extra=[("sum by (role) "
                    "(rate(fpx_runtime_outbound_stalls_total[5s]))",
                    "{{role}} stalls")]),
        _panel(
            9010, "Transport: batched bytes/s + outbound buffer",
            "sum by (role) "
            "(rate(fpx_runtime_transport_batch_bytes[5s]))",
            "{{role}}", "Bps", x=16, y=y + 17, w=8,
            extra=[("fpx_runtime_outbound_buffer_bytes",
                    "{{role}} outbound hwm")]),
        # paxworld global-serving band (scenarios/, docs/GLOBAL.md):
        # per-region committed goodput vs rejected/shed load -- the
        # fleet view the SLO matrix gates in CI.
        _panel(
            9011, "Global serving: goodput by region",
            "sum by (region) "
            "(rate(fpx_runtime_region_goodput_cmds_total[5s]))",
            "{{region}}", "ops", x=0, y=y + 25, w=12),
        _panel(
            9012, "Global serving: rejected/shed by region",
            "sum by (region) "
            "(rate(fpx_runtime_region_shed_total[5s]))",
            "{{region}}", "ops", x=12, y=y + 25, w=12),
        # paxingest ingestion band (ingest/, docs/TRANSPORT.md):
        # commands moving as pre-batched run descriptors, descriptor
        # bytes, and the per-run batch fill -- batchers and leaders
        # both export these.
        _panel(
            9013, "Ingest: batched cmds/s",
            "sum by (role) "
            "(rate(fpx_runtime_ingest_batched_cmds_total[5s]))",
            "{{role}}", "ops", x=0, y=y + 33, w=8),
        _panel(
            9014, "Ingest: descriptor bytes/s",
            "sum by (role) "
            "(rate(fpx_runtime_ingest_descriptor_bytes[5s]))",
            "{{role}}", "Bps", x=8, y=y + 33, w=8),
        _panel(
            9015, "Ingest: batch fill (cmds/run)",
            "sum by (role) "
            "(rate(fpx_runtime_ingest_batch_fill_sum[5s])) / "
            "sum by (role) "
            "(rate(fpx_runtime_ingest_batch_fill_count[5s]))",
            "{{role}}", "short", x=16, y=y + 33, w=8),
        # paxfan shard band (ingest/fan.py, docs/TRANSPORT.md
        # "Scale-out fan-in"): per-shard fan-in health for the
        # N-batcher ring -- sessions pinned per shard plus the
        # structural ring-skew gauge, commands routed per shard, the
        # descriptor-pipelining window occupancy, and failovers
        # absorbed (leader changes + wedged-window voids).
        _panel(
            9022, "Ingest shards: owned sessions + ring skew",
            "fpx_runtime_ingest_shard_owned_keys",
            "shard {{shard}}", "short", x=0, y=y + 41, w=6,
            extra=[("fpx_runtime_ingest_shard_ring_skew",
                    "skew shard {{shard}}")]),
        _panel(
            9023, "Ingest shards: routed cmds/s",
            "sum by (shard) "
            "(rate(fpx_runtime_ingest_shard_routed_cmds_total[5s]))",
            "shard {{shard}}", "ops", x=6, y=y + 41, w=6),
        _panel(
            9024, "Ingest shards: pipeline window depth",
            "fpx_runtime_ingest_shard_pipeline_depth",
            "shard {{shard}}", "short", x=12, y=y + 41, w=6),
        _panel(
            9025, "Ingest shards: failovers absorbed",
            "sum by (shard) "
            "(rate(fpx_runtime_ingest_shard_failovers_total[5s]))",
            "shard {{shard}}", "ops", x=18, y=y + 41, w=6),
        # paxpulse device-pipeline band (ops/telemetry.py +
        # obs/telemetry.py, docs/OBSERVABILITY.md): the counters that
        # ride INSIDE the jitted drain loop as arrays and reach the
        # host through one batched collect() per reporting interval --
        # commit/propose rates, per-shard skew, quorum-progress
        # occupancy, watermark lag, pad-lane waste, proposal fill, and
        # the paxruns depset/fast-quorum counters the runs/ layer
        # exports.
        commit_rate,
        shard_band,
        _panel(
            9018, "Device pipeline: quorum occupancy (votes at choose)",
            "sum by (votes) "
            "(rate(fpx_pipeline_quorum_occupancy_total[5s]))",
            "{{votes}} votes", "ops", x=8, y=y + 41, w=4),
        lag_band,
        fill_band,
        _panel(
            9021, "Depset / fast-quorum engine",
            "sum by (role) "
            "(rate(fpx_runtime_depset_batched_deps_total[5s]))",
            "deps {{role}}", "ops", x=20, y=y + 41, w=4,
            extra=[
                ("sum by (role) (rate("
                 "fpx_runtime_depset_span_fallbacks_total[5s]))",
                 "span fallback {{role}}"),
                ("sum by (role) (rate("
                 "fpx_runtime_fastquorum_checks_total[5s]))",
                 "fastquorum checks {{role}}"),
            ]),
    ]


def dashboard(protocol: str, roles: list) -> dict:
    panels = runtime_row_panels(y=0)
    # Role panels start right under the runtime row; Grafana renders
    # stored gridPos verbatim, so a gap here would show as a blank
    # band on every dashboard.
    for row, role in enumerate(roles):
        pretty = role.replace("_", " ").capitalize()
        metric = f"{protocol}_{role}"
        panels.append(_panel(
            2 * row, f"{pretty} request throughput",
            f"sum(rate({metric}_requests_total[1s])) by (type)",
            "{{type}}", "ops", x=0, y=RUNTIME_ROW_H + 8 * row))
        panels.append(_panel(
            2 * row + 1, f"{pretty} handler latency (mean)",
            f"sum(rate({metric}_requests_latency_seconds_sum[1s])) "
            f"by (type) / "
            f"sum(rate({metric}_requests_latency_seconds_count[1s])) "
            f"by (type)",
            "{{type}}", "s", x=12, y=RUNTIME_ROW_H + 8 * row))
    return {
        "uid": f"fpx-{protocol}",
        "title": f"FrankenPaxos TPU / {protocol}",
        "schemaVersion": 39,
        "version": 1,
        "editable": True,
        "timezone": "browser",
        "time": {"from": "now-5m", "to": "now"},
        "refresh": "1s",
        "templating": {"list": [{
            "name": "DS_PROMETHEUS",
            "type": "datasource",
            "query": "prometheus",
            "label": "Prometheus",
        }]},
        "panels": panels,
    }


def inject_runtime_row(path: str) -> None:
    """Prepend the shared runtime row to a HAND-WRITTEN dashboard
    (multipaxos, batching) without touching its own panels: existing
    9000-series panels are replaced and the board's own panels are
    re-based to start exactly at RUNTIME_ROW_H -- idempotent under
    re-runs AND under runtime-row height changes (the paxload band
    grew it from 9 to 17)."""
    with open(path) as f:
        board = json.load(f)
    own = [p for p in board["panels"] if p["id"] < 9000]
    row = runtime_row_panels(y=0)
    own_top = min((p["gridPos"]["y"] for p in own), default=0)
    delta = RUNTIME_ROW_H - own_top
    for panel in own:
        panel["gridPos"]["y"] += delta
    board["panels"] = row + own
    with open(path, "w") as f:
        json.dump(board, f, indent=2)
        f.write("\n")
    print(f"injected runtime row into {path}")


def main() -> None:
    for protocol in PROTOCOL_NAMES:
        if protocol in HAND_WRITTEN:
            continue
        roles = list(get_protocol(protocol).roles)
        path = os.path.join(OUT_DIR, f"{protocol}.json")
        with open(path, "w") as f:
            json.dump(dashboard(protocol, roles), f, indent=2)
            f.write("\n")
        print(f"wrote {path} ({len(roles)} roles)")
    for name in sorted(HAND_WRITTEN | {"batching"}):
        inject_runtime_row(os.path.join(OUT_DIR, f"{name}.json"))


if __name__ == "__main__":
    main()
