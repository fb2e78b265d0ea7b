"""obs/net/ — the live fleet telemetry plane (docs/OBSERVABILITY.md
"Live fleet telemetry").

Per-process observability (obs/) stayed strictly per-process through PR 17:
every role writes its own JSONL and serves its own /metrics, and the only
cross-process views are offline (obs_report, obs/attribution).  This package
makes telemetry a first-class fleet service on the existing substrate, the
same move PR 16 made for replay:

  relay.py      ObsRelay — an observer hook on MetricsLogger + periodic
                registry snapshots, streamed to the lease-discovered
                collector over the netcore framed-socket codec through a
                bounded NON-BLOCKING spool.  Full spool = shed newest row
                with a counted reasoned row; collector death = local JSONL
                continues untouched.  Telemetry is never load-bearing.
  collector.py  ObsCollector — the `obs_collector` lease role: ingests row
                streams from every host, keeps a ring-buffered downsampling
                time-series store keyed (host, role, kind, metric), folds a
                fleet-wide RunHealth (per-host fold, aggregate status with
                offenders NAMED), and re-exports aggregated Prometheus text
                + a /fleetz JSON endpoint on the existing ObsHTTPServer.
  alerts.py     declarative SLO engine over the store (threshold / absence
                / budget / rate rules) emitting schema'd `alert` rows with
                firing/resolved edges.

scripts/obs_top.py is the live terminal dashboard over /fleetz + /metrics.
Everything here is jax-free (analysis/imports.py declares it): relays run
inside every role including device-less ones, and the collector owns no
device at all.
"""

from rainbow_iqn_apex_tpu.obs.net.alerts import (
    AlertEngine,
    AlertRule,
    default_rules,
)
from rainbow_iqn_apex_tpu.obs.net.collector import ObsCollector
from rainbow_iqn_apex_tpu.obs.net.relay import ObsRelay

__all__ = [
    "AlertEngine",
    "AlertRule",
    "ObsCollector",
    "ObsRelay",
    "default_rules",
]
