#!/usr/bin/env python
"""obs_report: one command from a run dir's JSONL to "is this run healthy
and where is the time going".

    python scripts/obs_report.py <run_dir | metrics.jsonl> [--json]

Reads every *.jsonl under the run dir (a run writes metrics.jsonl; serving
side-cars land next to it), validates each line against the obs/ schema
(strict JSON — a bare NaN is a lint error, not a parse pass), and prints:

  * per-role throughput: env frames/sec (learn rows), learner steps/sec and
    learn-step p50/p99 (timing rows), serve request/batch totals;
  * replay occupancy, batch occupancy + pad tax (serve rows);
  * compile counts and span aggregates (timing rows);
  * fault totals by event, shed totals, dead hosts;
  * final eval and overall health (last health row + worst status seen).

Exit codes: 0 = report printed; 1 = no rows found (empty/missing run);
2 = report printed but some lines failed lint (broken producer).

The schema is versioned (obs/schema.py); this tool is the reference
consumer the golden-schema test keeps honest.  docs/OBSERVABILITY.md walks
through reading a report.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from rainbow_iqn_apex_tpu.obs.pipeline_trace import (  # noqa: E402
    critical_path,
    format_critical_path,
)
from rainbow_iqn_apex_tpu.obs.schema import validate_row  # noqa: E402
from scripts.lint_jsonl import lint_line  # noqa: E402


def find_jsonl(path: str) -> List[str]:
    if os.path.isfile(path):
        return [path]
    hits = sorted(glob.glob(os.path.join(path, "**", "*.jsonl"), recursive=True))
    return hits


def load_rows(paths: List[str]) -> Tuple[List[Dict[str, Any]], List[str]]:
    rows, errors = [], []
    for path in paths:
        with open(path) as fh:
            for lineno, line in enumerate(fh, 1):
                if not line.strip():
                    continue
                err = lint_line(line)
                if err is not None:
                    errors.append(f"{path}:{lineno}: {err}")
                    continue
                row = json.loads(line)
                schema_errs = validate_row(row)
                if schema_errs:
                    errors.append(f"{path}:{lineno}: {'; '.join(schema_errs)}")
                rows.append(row)
    return rows, errors


def _last(rows: List[Dict[str, Any]], kind: str) -> Dict[str, Any]:
    for row in reversed(rows):
        if row.get("kind") == kind:
            return row
    return {}


def _last_with(rows: List[Dict[str, Any]], kind: str, key: str) -> Dict[str, Any]:
    """Last row of ``kind`` that carries ``key`` — the final flush at close
    emits without per-loop gauges, so "last row" alone can hide them."""
    for row in reversed(rows):
        if row.get("kind") == kind and row.get(key) is not None:
            return row
    return {}


def _device_time_lines(dt) -> List[str]:
    """The 'device_time' row as text: idle share, milliseconds a learn step
    by scope, what ran outside the tick, the long idle gaps by span, idle
    inside a dispatch by scope path, and what the compiler made."""
    if not dt:
        return []
    if dt.get("error"):
        return [f"device_time: capture not reduced ({dt['error']})"]
    lines = [
        f"device_time: {dt.get('steps')} learn steps, "
        f"{dt.get('dispatches')} dispatches on {dt.get('chips')} chip(s); "
        f"window {dt.get('window_s')}s busy {dt.get('busy_s')}s "
        f"idle {dt.get('idle_share')}%  outside_tick "
        f"{dt.get('outside_tick_ms_per_dispatch')}ms/dispatch  "
        f"unresolved {dt.get('unresolved_share')}%"]
    for scope, ms in sorted((dt.get("scope_ms_per_step") or {}).items(),
                            key=lambda kv: -kv[1]):
        lines.append(f"  scope {scope}: {ms}ms/learn step")
    for span, ms in sorted((dt.get("idle_gap_ms_by_span") or {}).items(),
                           key=lambda kv: -kv[1]):
        lines.append(f"  idle gaps over 1ms under {span}: {round(ms, 3)}ms")
    # idle inside a dispatch, by the scope path of the op that ends each gap
    # (a fused loop's host span is always `segment`; the path says where)
    for path, ms in sorted((dt.get("idle_ms_by_path_per_step") or {}).items(),
                           key=lambda kv: -kv[1])[:12]:
        lines.append(f"  idle before {path}: {ms}ms/learn step")
    if dt.get("idle_between_dispatches_s") is not None:
        lines.append(
            f"  idle between dispatches: {dt['idle_between_dispatches_s']}s")
    if dt.get("compiler_made_ms_per_dispatch") is not None:
        lines.append(f"  compiler-made instructions: "
                     f"{dt['compiler_made_ms_per_dispatch']}ms/dispatch")
    for made in (dt.get("compiler_made") or [])[:6]:
        lines.append(
            f"    {made['instruction']} {made['opcode']} {made['shape']}: "
            f"{made['ms']}ms, read under {made['consumer']}")
    return lines


def _mean(vals: List[float]) -> float:
    return sum(vals) / len(vals) if vals else 0.0


def _fleet_section(by_kind: Dict[str, List[Dict[str, Any]]]) -> Dict[str, Any]:
    """Fold route/scale/rollout rows into the fleet report: who got served
    (per-tenant accept/shed), how even the fleet ran (per-engine depth and
    version spread from the LAST route row's snapshot), what the autoscaler
    did, and how fast weight rollouts converged."""
    route = by_kind.get("route", [])
    scale = by_kind.get("scale", [])
    rollout = by_kind.get("rollout", [])
    tenants: Dict[str, Dict[str, int]] = {}
    shed_by_reason: Dict[str, int] = {}
    for row in route:
        for tenant, counts in (row.get("tenants") or {}).items():
            agg = tenants.setdefault(tenant, {"accepted": 0, "shed": 0})
            agg["accepted"] += int(counts.get("accepted", 0))
            agg["shed"] += int(counts.get("shed", 0))
        for reason, n in (row.get("shed_by_reason") or {}).items():
            shed_by_reason[reason] = shed_by_reason.get(reason, 0) + int(n)
    engines = {}
    for row in reversed(route):
        if row.get("engines"):
            engines = row["engines"]
            break
    versions = [e.get("version") for e in engines.values()
                if e.get("version") is not None]
    converged = [r for r in rollout if r.get("event") == "converged"]
    return {
        "accepted": sum(int(r.get("accepted", 0)) for r in route),
        "shed": sum(int(r.get("shed", 0)) for r in route),
        "rerouted": sum(int(r.get("rerouted", 0)) for r in route),
        "lost": sum(int(r.get("lost", 0)) for r in route),
        "cancelled": sum(int(r.get("cancelled", 0)) for r in route),
        "shed_by_reason": shed_by_reason,
        "tenants": tenants,
        "engines": engines,
        "version_spread": (max(versions) - min(versions)) if versions else None,
        "scale_out": sum(1 for r in scale if r.get("action") == "out"),
        "scale_in": sum(1 for r in scale if r.get("action") == "in"),
        "rollouts": sum(1 for r in rollout if r.get("event") == "publish"),
        "rollouts_refused": sum(1 for r in rollout
                                if r.get("event") == "refused_backward"),
        "rollout_convergence_s": (converged[-1].get("convergence_s")
                                  if converged else None),
    }


def _games_section(by_kind: Dict[str, List[Dict[str, Any]]]) -> Dict[str, Any]:
    """Fold multi-game rows (multitask/; docs/MULTITASK.md): the newest
    `games` row's per-game learn share / replay occupancy, the latest eval
    score per game (eval rows keyed by ``game``), and the newest suite
    human-normalized aggregates.  Empty dict for single-game runs."""
    games_rows = by_kind.get("games", [])
    eval_mt = by_kind.get("eval_mt", [])
    per_game_eval: Dict[str, Dict[str, Any]] = {}
    for row in by_kind.get("eval", []):
        if row.get("game"):
            per_game_eval[str(row["game"])] = row
    if not (games_rows or eval_mt or per_game_eval):
        return {}
    last = games_rows[-1] if games_rows else {}
    games: Dict[str, Dict[str, Any]] = {}
    for name, snap in (last.get("games") or {}).items():
        games[name] = dict(snap)
    for name, row in per_game_eval.items():
        entry = games.setdefault(name, {})
        entry.setdefault("score_mean", row.get("score_mean"))
        if row.get("human_normalized") is not None:
            entry.setdefault("human_normalized", row["human_normalized"])
    agg = eval_mt[-1] if eval_mt else last
    return {
        "n": len(games),
        "schedule": last.get("schedule"),
        "rows": len(games_rows),
        "evals": len(eval_mt),
        "hn_median": agg.get("hn_median"),
        "hn_mean": agg.get("hn_mean"),
        "games": games,
    }


def _league_section(by_kind: Dict[str, List[Dict[str, Any]]]) -> Dict[str, Any]:
    """Fold league rows (league/; docs/LEAGUE.md): the newest status row's
    per-member table (fitness, generation, exploit/explore counts, restarts,
    last copy source), exploit/adoption event totals, and whether the
    population ever collapsed.  Empty dict for league-less runs."""
    league = by_kind.get("league", [])
    if not league:
        return {}
    status = [r for r in league if r.get("event") == "status"]
    last = status[-1] if status else {}
    events: Dict[str, int] = {}
    for row in league:
        ev = str(row.get("event", "unknown"))
        events[ev] = events.get(ev, 0) + 1
    return {
        "rows": len(league),
        "events": events,
        "exploits": events.get("exploit", 0),
        "adoptions": events.get("adopt", 0),
        "adopt_refused": events.get("adopt_refused", 0),
        "skips": events.get("exploit_skipped", 0),
        "alive": last.get("alive"),
        "collapsed_ever": any(r.get("collapsed") for r in status),
        "members": last.get("members") or {},
    }


def _failover_section(
    by_kind: Dict[str, List[Dict[str, Any]]]
) -> Dict[str, Any]:
    """Fold learner-failover rows (parallel/failover.py;
    docs/RESILIENCE.md "learner failover"): takeover count and MTTR, the
    claim-vs-restore latency split the RUNBOOK triage keys on, claim races
    lost, and fenced stale publishes/write-backs by surface (a non-empty
    surface table means a ZOMBIE predecessor kept running after takeover
    and every one of its writes was refused).  Empty dict for runs without
    failover rows."""
    rows = by_kind.get("failover", [])
    if not rows:
        return {}
    events: Dict[str, int] = {}
    fenced_by_surface: Dict[str, int] = {}
    for row in rows:
        ev = str(row.get("event", "unknown"))
        events[ev] = events.get(ev, 0) + 1
        if ev == "fenced_stale":
            surface = str(row.get("surface", "unknown"))
            fenced_by_surface[surface] = fenced_by_surface.get(surface, 0) + 1
    takeovers = [r for r in rows if r.get("event") == "takeover"]
    restores = [r for r in rows if r.get("event") == "restore"]
    claims = [r for r in rows if r.get("event") == "claim"]
    last_takeover = takeovers[-1] if takeovers else {}
    last_restore = restores[-1] if restores else {}
    return {
        "rows": len(rows),
        "events": events,
        "takeovers": len(takeovers),
        "mttr_s": last_takeover.get("mttr_s"),
        "warm": last_takeover.get("warm"),
        "epoch": last_takeover.get("epoch"),
        "restore_s": last_restore.get("restore_s"),
        "claims_won": sum(1 for r in claims if r.get("won")),
        "claims_lost": sum(1 for r in claims if not r.get("won")),
        "fenced_stale": events.get("fenced_stale", 0),
        "fenced_by_surface": fenced_by_surface,
    }


def _net_section(by_kind: Dict[str, List[Dict[str, Any]]]) -> Dict[str, Any]:
    """Fold cross-host serving rows (serving/net/): per-peer transport
    health — newest rtt/bytes from the periodic stats rows, flap counts
    (disconnects/reconnects/probe timeouts) from the lifecycle events —
    plus the newest gossip freshness.  Empty dict for in-process runs."""
    net = by_kind.get("net", [])
    gossip = by_kind.get("gossip", [])
    if not (net or gossip):
        return {}
    peers: Dict[str, Dict[str, Any]] = {}
    flaps = 0
    for row in net:
        peer = str(row.get("peer", "?"))
        snap = peers.setdefault(peer, {
            "reconnects": 0, "disconnects": 0, "probe_timeouts": 0})
        event = row.get("event")
        if event == "stats":
            # newest stats row wins: these are lifetime counters/gauges
            snap["rtt_ms"] = row.get("rtt_ms")
            snap["bytes_sent"] = row.get("bytes_sent")
            snap["bytes_recv"] = row.get("bytes_recv")
            snap["connected"] = row.get("connected")
            snap["reconnects"] = int(row.get("reconnects", 0) or 0)
            snap["probe_timeouts"] = int(row.get("probe_timeouts", 0) or 0)
        elif event == "disconnect":
            snap["disconnects"] += 1
            flaps += 1
        elif event in ("reconnect", "probe_timeout", "bad_frame"):
            flaps += 1
    last_gossip = gossip[-1] if gossip else {}
    return {
        "rows": len(net),
        "flaps": flaps,
        "peers": peers,
        "gossip_rows": len(gossip),
        "gossip_peers": last_gossip.get("peers"),
        "gossip_fresh": last_gossip.get("fresh"),
        "gossip_stale": last_gossip.get("stale"),
    }


def _replaynet_section(
    by_kind: Dict[str, List[Dict[str, Any]]]
) -> Dict[str, Any]:
    """Fold cross-host replay rows (replay/net/): the newest plane stats
    row (peer counts, aggregate size, mean rtt, spool depth, acked/shed
    append totals, sample/write-back totals) plus lifecycle event counts —
    the RUNBOOK "learner is starving on remote replay" triage reads this
    section first.  Empty dict for in-process-replay runs."""
    rows = by_kind.get("replay_net", [])
    if not rows:
        return {}
    events: Dict[str, int] = {}
    for row in rows:
        ev = str(row.get("event", "unknown"))
        events[ev] = events.get(ev, 0) + 1
    stats = [r for r in rows if r.get("event") == "stats"]
    last = stats[-1] if stats else {}
    flaps = sum(events.get(e, 0) for e in (
        "disconnect", "reconnect", "probe_timeout", "bad_frame",
        "spool_shed", "peer_dead"))
    return {
        "rows": len(rows),
        "events": events,
        "flaps": flaps,
        "peers": last.get("peers"),
        "dead_peers": last.get("dead_peers"),
        "size": last.get("size"),
        "rtt_ms": last.get("rtt_ms"),
        "spool_depth": last.get("spool_depth"),
        "acked_rows": last.get("acked_rows"),
        "shed_ticks": last.get("shed_ticks"),
        "fenced_rows": last.get("fenced_rows"),
        "shed_lanes": last.get("shed_lanes"),
        "batches": last.get("batches"),
        "rows_sampled": last.get("rows_sampled"),
        "updates_sent": last.get("updates_sent"),
        "updates_dropped": last.get("updates_dropped"),
        "rerouted": last.get("rerouted"),
    }


def _obsnet_section(
    by_kind: Dict[str, List[Dict[str, Any]]]
) -> Dict[str, Any]:
    """Fold live-telemetry-plane rows (obs/net/): relay lifecycle/shed
    counts, the newest relay stats row, the newest collector fleet fold,
    and alert edge totals — the offline answer to "was the live view
    complete while this ran".  Empty dict when the plane was off."""
    rows = by_kind.get("obs_net", [])
    alerts = by_kind.get("alert", [])
    fleet = by_kind.get("fleet_health", [])
    if not rows and not alerts and not fleet:
        return {}
    events: Dict[str, int] = {}
    for row in rows:
        ev = str(row.get("event", "unknown"))
        events[ev] = events.get(ev, 0) + 1
    stats = [r for r in rows if r.get("event") == "stats"]
    last = stats[-1] if stats else {}
    last_fleet = fleet[-1] if fleet else {}
    firing = sum(1 for a in alerts if a.get("state") == "firing")
    resolved = sum(1 for a in alerts if a.get("state") == "resolved")
    worst = "ok"
    for r in fleet:
        s = r.get("status")
        if s == "failing" or (s == "degraded" and worst == "ok"):
            worst = s
    return {
        "rows": len(rows),
        "events": events,
        "flaps": sum(events.get(e, 0) for e in
                     ("disconnect", "reconnect", "spool_shed")),
        "sent_rows": last.get("sent_rows"),
        "shed_rows": last.get("shed_rows"),
        "spool_depth": last.get("spool_depth"),
        "reconnects": last.get("reconnects"),
        "alerts_firing_edges": firing,
        "alerts_resolved_edges": resolved,
        "fleet_rows": len(fleet),
        "fleet_last_status": last_fleet.get("status"),
        "fleet_worst_status": worst if fleet else None,
        "fleet_hosts": last_fleet.get("hosts_total"),
        "fleet_offenders": last_fleet.get("offenders", []),
    }


def _quant_section(by_kind: Dict[str, List[Dict[str, Any]]]) -> Dict[str, Any]:
    """Fold quant/publish/quant_fallback rows: is the quantized path live,
    what did the gate last measure, and how many publish bytes the delta/
    int8 path saved vs shipping fp32 full (docs/PERFORMANCE.md "quant")."""
    quant = by_kind.get("quant", [])
    fallbacks = by_kind.get("quant_fallback", [])
    publish = by_kind.get("publish", [])
    # the CURRENT state is whichever gate outcome is newest — 'quant' rows
    # are emitted only on PASS, so after a run of fallbacks the last quant
    # row is stale and reporting it as "active" would read the opposite of
    # the truth exactly when the RUNBOOK triage needs it
    last_gate = quant[-1] if quant else {}
    last_fb = fallbacks[-1] if fallbacks else {}
    # ts ties (same-millisecond rows) break toward the FALLBACK: reporting
    # not-active errs toward operator attention, never away from it
    if last_fb and last_fb.get("ts", 0) >= last_gate.get("ts", -1):
        newest = last_fb
    else:
        newest = last_gate
    bytes_total = sum(int(r.get("bytes") or 0) for r in publish)
    bytes_fp32 = sum(int(r.get("bytes_fp32") or 0) for r in publish)
    return {
        "gates": len(quant),
        "fallbacks": len(fallbacks),
        "last_agreement": newest.get("agreement"),
        "last_mode": newest.get("mode"),
        "active": (bool(newest.get("active", False))
                   if (quant or fallbacks) else None),
        "publishes": len(publish),
        "publish_bytes_total": bytes_total,
        "publish_bytes_fp32": bytes_fp32,
        "bytes_saved_frac": (round(1.0 - bytes_total / bytes_fp32, 4)
                             if bytes_fp32 else None),
    }


def aggregate(rows: List[Dict[str, Any]]) -> Dict[str, Any]:
    by_kind: Dict[str, List[Dict[str, Any]]] = {}
    for row in rows:
        by_kind.setdefault(str(row.get("kind")), []).append(row)

    learn = by_kind.get("learn", [])
    timing = by_kind.get("timing", [])
    serve = by_kind.get("serve", [])
    health = by_kind.get("health", [])
    faults = by_kind.get("fault", [])

    last_learn = _last(rows, "learn")
    last_timing = _last(rows, "timing")
    last_health = _last(rows, "health")
    last_eval = _last(rows, "eval")

    fault_counts: Dict[str, int] = {}
    for row in faults:
        ev = str(row.get("event", "unknown"))
        fault_counts[ev] = fault_counts.get(ev, 0) + 1

    serve_requests = sum(int(r.get("requests", 0)) for r in serve)
    serve_batches = sum(int(r.get("batches", 0)) for r in serve)
    shed_total = sum(int(r.get("shed", 0)) for r in serve)

    statuses = [str(r.get("status", "ok")) for r in health]
    order = {"ok": 0, "degraded": 1, "failing": 2}
    worst = max(statuses, key=lambda s: order.get(s, 0)) if statuses else None

    # the final flush at close() resets span windows right after the last
    # periodic row, so the very last timing row's spans can be empty — show
    # the last window that actually observed spans
    span_stats = last_timing.get("spans") or {}
    if not any(s.get("count") for s in span_stats.values()):
        for row in reversed(timing):
            spans = row.get("spans") or {}
            if any(s.get("count") for s in spans.values()):
                span_stats = spans
                break
    report = {
        "rows": len(rows),
        "row_kinds": {k: len(v) for k, v in sorted(by_kind.items())},
        "roles": {
            "actor": {
                "frames": int(last_learn.get("frames", 0)),
                "fps_last": float(last_learn.get("fps") or 0.0),
                "fps_mean": round(
                    _mean([float(r.get("fps") or 0.0)
                           for r in learn if r.get("fps")]), 2),
            },
            "learner": {
                "steps": int(last_learn.get("step", 0)
                             or last_timing.get("step", 0)),
                "steps_per_sec": float(
                    last_timing.get("learn_steps_per_sec", 0.0) or 0.0),
                "step_p50_s": last_timing.get("learn_p50_s"),
                "step_p99_s": last_timing.get("learn_p99_s"),
            },
            "replay": {
                "size": _last_with(rows, "health", "replay_size")
                .get("replay_size"),
                "occupancy": _last_with(rows, "health", "replay_occupancy")
                .get("replay_occupancy"),
            },
            "serve": {
                "requests": serve_requests,
                "batches": serve_batches,
                "shed": shed_total,
                "batch_occupancy_mean": round(
                    _mean([float(r.get("batch_occupancy_mean", 0.0))
                           for r in serve if r.get("batches")]), 3),
                "pad_fraction_mean": round(
                    _mean([float(r.get("pad_fraction", 0.0))
                           for r in serve if r.get("batches")]), 4),
                "latency_p99_ms": _last(rows, "serve").get("latency_p99_ms"),
            },
        },
        "compiles": last_timing.get("compiles"),
        "compile_cache_hits": last_timing.get("compile_cache_hits"),
        # the --trace-dir capture reduced by the run itself
        # (obs/device_scopes.py): device time by the program's scope names
        "device_time": {k: v for k, v in _last(rows, "device_time").items()
                        if k not in ("t", "ts", "host", "run", "kind",
                                     "schema")} or None,
        "spans": span_stats,
        "faults": fault_counts,
        # elasticity (docs/RESILIENCE.md "heal"): the detect->heal story in
        # counts — deaths vs revivals/readmits, fence episodes, respawns,
        # permanent evictions
        "elastic": {
            "host_dead": fault_counts.get("host_dead", 0),
            "host_alive": len(by_kind.get("host_alive", [])),
            "shard_readmits": len(by_kind.get("shard_readmit", [])),
            "fence_episodes": sum(
                1 for r in by_kind.get("actor_fenced", [])
                if r.get("action") != "resume"
            ),
            "respawns": fault_counts.get("actor_respawn", 0),
            "evictions": fault_counts.get("actor_evicted", 0),
        },
        # learner pipeline (docs/PERFORMANCE.md): write-back ring depth/lag
        # plus prefetch starvation signals — lag == configured depth with an
        # empty-wait count near zero means the hot path is device-bound (the
        # goal); a climbing empty-wait count means the SAMPLER is the
        # bottleneck and deeper write-back will not help.  With device
        # sampling on, the sample_ahead_* / mirror gauges split that further:
        # empty waits with sample_ahead_queue_depth pinned at 0 means the
        # PUSHER can't keep up — a growing stale-indices counter or a fat
        # mirror_reconcile_s points at the frontier (sampler-starved), an
        # otherwise idle frontier points at the host frame gather
        # (gather-starved).
        "pipeline": {
            "writeback_inflight": _last_with(rows, "health", "writeback_inflight")
            .get("writeback_inflight"),
            "writeback_lag_steps": _last_with(rows, "health", "writeback_lag_steps")
            .get("writeback_lag_steps"),
            "prefetch_queue_depth": _last_with(rows, "health", "prefetch_queue_depth")
            .get("prefetch_queue_depth"),
            "prefetch_empty_waits": _last_with(rows, "health", "prefetch_empty_waits")
            .get("prefetch_empty_waits"),
            "sample_ahead_queue_depth": _last_with(
                rows, "health", "sample_ahead_queue_depth")
            .get("sample_ahead_queue_depth"),
            "sample_ahead_stale_indices": _last_with(
                rows, "health", "sample_ahead_stale_indices")
            .get("sample_ahead_stale_indices"),
            "mirror_reconcile_s": _last_with(rows, "health", "mirror_reconcile_s")
            .get("mirror_reconcile_s"),
            # replay reuse (docs/PERFORMANCE.md "Replay reuse"): present
            # only when the run ran cfg.replay_ratio > 1 — K and the newest
            # retired sample's mean reuse-pass clip fraction (a climbing
            # fraction is the K-too-high early warning)
            "replay_ratio": _last_with(rows, "health", "replay_ratio")
            .get("replay_ratio"),
            "reuse_clip_frac": _last_with(rows, "health", "reuse_clip_frac")
            .get("reuse_clip_frac"),
        },
        # critical-path attribution (obs/pipeline_trace.py): which stage
        # owns the largest exclusive share of traced end-to-end latency —
        # sampler-starved vs device-bound vs publish-bound in one line.
        # None when the run was not traced (trace_sample_every = 0).
        "critical_path": critical_path(rows),
        # lag attribution: the newest `lag` row's percentiles (sample age at
        # learn time, ring retirement, publish->adopt per consumer)
        "lag": {k: v for k, v in _last(rows, "lag").items()
                if k not in ("t", "ts", "host", "run", "kind", "schema",
                             "step")},
        # serving fleet (docs/SERVING.md "fleet"): per-tenant accept/shed,
        # per-engine depth/version spread, scale events, rollout convergence
        "fleet": _fleet_section(by_kind),
        # cross-host serving plane (serving/net/): per-peer transport
        # rtt/reconnects/bytes + router-gossip freshness
        "net": _net_section(by_kind),
        # cross-host replay plane (replay/net/): newest plane stats +
        # lifecycle flap counts (the remote-replay starvation triage input)
        "replaynet": _replaynet_section(by_kind),
        # live telemetry plane (obs/net/): relay shed/reconnect counts,
        # alert edges, the collector's newest fleet fold + named offenders
        "obsnet": _obsnet_section(by_kind),
        # quantized inference + compressed distribution: gate agreement,
        # fallback count, publish bytes saved vs fp32-full
        "quant": _quant_section(by_kind),
        # multi-game runs (multitask/): per-game learn share / replay
        # occupancy / latest eval + suite human-normalized aggregates
        "games": _games_section(by_kind),
        # league runs (league/): per-member fitness/generation/exploits +
        # event totals (the PBT story in counts)
        "league": _league_section(by_kind),
        # learner failover (parallel/failover.py): takeovers + MTTR, the
        # claim/restore latency split, fenced zombie writes by surface
        "failover": _failover_section(by_kind),
        "shed_total": shed_total,
        "final_eval": {
            k: v for k, v in last_eval.items()
            if k.startswith("score") or k in ("episodes", "human_normalized")
        },
        "health": {
            "last_status": last_health.get("status"),
            "worst_status": worst,
            "rows": len(health),
            "hosts_dead": last_health.get("hosts_dead", []),
            "hosts_evicted": last_health.get("hosts_evicted", []),
            # consumers whose publish->adopt p99 breached the propagation
            # budget in the newest window (obs/pipeline_trace.py)
            "lag_consumers": last_health.get("lag_consumers", []),
        },
    }
    return report


def render(report: Dict[str, Any]) -> str:
    roles = report["roles"]
    lines = [
        "== obs_report ==",
        f"rows: {report['rows']}  kinds: {report['row_kinds']}",
        (f"actor:   frames={roles['actor']['frames']}  "
         f"fps last={roles['actor']['fps_last']:.1f} "
         f"mean={roles['actor']['fps_mean']:.1f}"),
        (f"learner: steps={roles['learner']['steps']}  "
         f"steps/s={roles['learner']['steps_per_sec']:.2f}  "
         f"step p50={roles['learner']['step_p50_s']}s "
         f"p99={roles['learner']['step_p99_s']}s"),
        (f"replay:  size={roles['replay']['size']}  "
         f"occupancy={roles['replay']['occupancy']}"),
        (f"serve:   requests={roles['serve']['requests']}  "
         f"batches={roles['serve']['batches']}  "
         f"shed={roles['serve']['shed']}  "
         f"batch_occupancy={roles['serve']['batch_occupancy_mean']}  "
         f"pad_tax={roles['serve']['pad_fraction_mean']}  "
         f"latency_p99_ms={roles['serve']['latency_p99_ms']}"),
        (f"compiles: {report['compiles']}  "
         f"cache_hits: {report.get('compile_cache_hits')}"),
    ]
    lines.extend(_device_time_lines(report.get("device_time")))
    for name, snap in sorted((report["spans"] or {}).items()):
        lines.append(f"span {name}: {snap}")
    lines.append(f"faults: {report['faults'] or 'none'}")
    p = report["pipeline"]
    if any(v is not None for v in p.values()):
        line = (
            f"pipeline: writeback_inflight={p['writeback_inflight']} "
            f"lag={p['writeback_lag_steps']} "
            f"prefetch_depth={p['prefetch_queue_depth']} "
            f"empty_waits={p['prefetch_empty_waits']}"
        )
        if p.get("mirror_reconcile_s") is not None:  # device sampling on
            line += (
                f" sample_ahead_depth={p['sample_ahead_queue_depth']} "
                f"stale_indices={p['sample_ahead_stale_indices']} "
                f"mirror_reconcile_s={p['mirror_reconcile_s']}"
            )
        if p.get("replay_ratio") is not None:  # replay reuse on (K > 1)
            line += (
                f" replay_ratio={p['replay_ratio']} "
                f"reuse_clip_frac={p['reuse_clip_frac']}"
            )
        lines.append(line)
    cp = report.get("critical_path")
    if cp:
        lines.append(f"critical_path: {format_critical_path(cp)}")
        for stage, snap in sorted(cp["stages"].items(),
                                  key=lambda kv: -kv[1]["share"]):
            lines.append(f"  stage {stage}: {round(snap['share'] * 100)}% "
                         f"({snap['ms']}ms exclusive)")
    lag = report.get("lag") or {}
    if lag:
        parts = []
        for key in ("sample_age_s", "sample_age_ticks", "ring_retire_ms",
                    "router_dispatch_ms", "batch_slot_wait_ms"):
            if key in lag:
                parts.append(f"{key} p50={lag[key].get('p50')} "
                             f"p99={lag[key].get('p99')}")
        if parts:
            lines.append("lag:     " + "  ".join(parts))
        for consumer, snap in sorted(
                (lag.get("publish_adopt_ms_by_consumer") or {}).items()):
            lines.append(f"  publish->adopt {consumer}: "
                         f"p50={snap.get('p50')}ms p99={snap.get('p99')}ms")
        if lag.get("publish_adopt_budget_ms") is not None:
            lines.append(f"  publish->adopt budget: "
                         f"{lag['publish_adopt_budget_ms']}ms "
                         "(max_weight_lag x publish cadence)")
    f = report["fleet"]
    if f["accepted"] or f["shed"] or f["rollouts"] or f["engines"]:
        lines.append(
            f"fleet:   accepted={f['accepted']} shed={f['shed']} "
            f"rerouted={f['rerouted']} lost={f['lost']} "
            f"cancelled={f['cancelled']} "
            f"scale_out={f['scale_out']} scale_in={f['scale_in']} "
            f"rollouts={f['rollouts']} "
            f"(refused={f['rollouts_refused']}, "
            f"convergence_s={f['rollout_convergence_s']}) "
            f"version_spread={f['version_spread']}"
        )
        for tenant, counts in sorted(f["tenants"].items()):
            lines.append(f"  tenant {tenant}: accepted={counts['accepted']} "
                         f"shed={counts['shed']}")
        for eid, snap in sorted(f["engines"].items()):
            lines.append(f"  engine {eid}: depth={snap.get('depth')} "
                         f"version={snap.get('version')} "
                         f"alive={snap.get('alive')}")
    n = report.get("net") or {}
    if n:
        lines.append(
            f"net:     rows={n['rows']} flaps={n['flaps']} "
            f"gossip_rows={n['gossip_rows']} "
            f"gossip_fresh={n['gossip_fresh']}/{n['gossip_peers']} "
            f"(stale={n['gossip_stale']})"
        )
        for peer, snap in sorted(n["peers"].items()):
            lines.append(
                f"  peer {peer}: rtt_ms={snap.get('rtt_ms')} "
                f"reconnects={snap.get('reconnects')} "
                f"probe_timeouts={snap.get('probe_timeouts')} "
                f"bytes_sent={snap.get('bytes_sent')} "
                f"bytes_recv={snap.get('bytes_recv')}"
                + ("" if snap.get("connected", True) else " DISCONNECTED")
            )
    rn = report.get("replaynet") or {}
    if rn:
        lines.append(
            f"replaynet: peers={rn['peers']} (dead={rn['dead_peers']}) "
            f"size={rn['size']} rtt_ms={rn['rtt_ms']} flaps={rn['flaps']} "
            f"spool_depth={rn['spool_depth']} acked_rows={rn['acked_rows']} "
            f"shed_ticks={rn['shed_ticks']} fenced_rows={rn['fenced_rows']} "
            f"batches={rn['batches']} updates_sent={rn['updates_sent']} "
            f"(dropped={rn['updates_dropped']}, rerouted={rn['rerouted']})"
        )
        if rn.get("events"):
            lines.append(f"  replaynet events: {rn['events']}")
    on = report.get("obsnet") or {}
    if on:
        lines.append(
            f"obsnet:  rows={on['rows']} flaps={on['flaps']} "
            f"sent={on['sent_rows']} shed={on['shed_rows']} "
            f"reconnects={on['reconnects']} "
            f"alert_edges={on['alerts_firing_edges']}+"
            f"{on['alerts_resolved_edges']} "
            f"fleet last={on['fleet_last_status']} "
            f"worst={on['fleet_worst_status']} "
            f"hosts={on['fleet_hosts']}"
        )
        if on.get("fleet_offenders"):
            lines.append(f"  offenders: {on['fleet_offenders']}")
    q = report["quant"]
    if q["gates"] or q["fallbacks"] or q["publishes"]:
        lines.append(
            f"quant:   gates={q['gates']} fallbacks={q['fallbacks']} "
            f"active={q['active']} agreement={q['last_agreement']} "
            f"mode={q['last_mode']} publishes={q['publishes']} "
            f"bytes={q['publish_bytes_total']} "
            f"(saved_frac={q['bytes_saved_frac']})"
        )
    mg = report.get("games") or {}
    if mg:
        lines.append(
            f"games:   n={mg['n']} schedule={mg['schedule']} "
            f"rows={mg['rows']} evals={mg['evals']} "
            f"hn_median={mg['hn_median']} hn_mean={mg['hn_mean']}"
        )
        for name, snap in sorted(mg["games"].items()):
            lines.append(
                f"  game {name}: learn_share={snap.get('learn_share')} "
                f"occupancy={snap.get('replay_occupancy')} "
                f"eval={snap.get('score_mean')} "
                f"hn={snap.get('human_normalized')}"
                + (" DEAD" if snap.get("dead") else "")
            )
    lg = report.get("league") or {}
    if lg:
        lines.append(
            f"league:  members={len(lg['members'])} alive={lg['alive']} "
            f"exploits={lg['exploits']} adoptions={lg['adoptions']} "
            f"refused={lg['adopt_refused']} skips={lg['skips']}"
            + (" COLLAPSED" if lg.get("collapsed_ever") else "")
        )
        for mid, snap in sorted(lg["members"].items(),
                                key=lambda kv: int(kv[0])):
            fit = snap.get("fitness")
            lines.append(
                f"  member m{mid}: fitness="
                f"{round(fit, 4) if fit is not None else None} "
                f"gen={snap.get('generation')} "
                f"exploits={snap.get('exploits')} "
                f"restarts={snap.get('restarts')} "
                f"state={snap.get('state')} "
                f"last_copy_source={snap.get('last_copy_source')} "
                f"lr={snap.get('lr')} n_step={snap.get('n_step')}"
            )
    fo = report.get("failover") or {}
    if fo:
        lines.append(
            f"failover: takeovers={fo['takeovers']} mttr_s={fo['mttr_s']} "
            f"restore_s={fo['restore_s']} warm={fo['warm']} "
            f"epoch={fo['epoch']} claims_won={fo['claims_won']} "
            f"claims_lost={fo['claims_lost']} "
            f"fenced_stale={fo['fenced_stale']}"
        )
        for surface, n in sorted(fo["fenced_by_surface"].items()):
            lines.append(f"  fenced surface {surface}: {n} refused")
    e = report["elastic"]
    if any(e.values()):
        lines.append(
            f"elastic: host_dead={e['host_dead']} host_alive={e['host_alive']} "
            f"readmits={e['shard_readmits']} fences={e['fence_episodes']} "
            f"respawns={e['respawns']} evictions={e['evictions']}"
        )
    lines.append(f"final_eval: {report['final_eval'] or 'none'}")
    h = report["health"]
    lines.append(
        f"health: last={h['last_status']} worst={h['worst_status']} "
        f"rows={h['rows']} hosts_dead={h['hosts_dead']} "
        f"hosts_evicted={h['hosts_evicted']}"
        + (f" lag_consumers={h['lag_consumers']}"
           if h.get("lag_consumers") else "")
    )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="run dir (or one .jsonl file)")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as one JSON object")
    args = ap.parse_args(argv)

    paths = find_jsonl(args.path)
    if not paths:
        print(f"obs_report: no .jsonl under {args.path}", file=sys.stderr)
        return 1
    rows, errors = load_rows(paths)
    if not rows:
        print(f"obs_report: {len(paths)} file(s) but zero rows", file=sys.stderr)
        return 1
    report = aggregate(rows)
    report["lint_errors"] = len(errors)
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        print(render(report))
    if errors:
        for err in errors[:20]:
            print(f"LINT {err}", file=sys.stderr)
        if len(errors) > 20:
            print(f"LINT ... {len(errors) - 20} more", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
