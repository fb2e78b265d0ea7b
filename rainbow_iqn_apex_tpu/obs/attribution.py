"""Run attribution from a run directory's JSONL rows (jax-free).

``health_attribution`` folds every ``metrics.jsonl`` a glob matches into one
summary: health status counts with the last and worst status, heal /
fleet / net / quant / trace / multi-game / reuse / league tallies, and a
one-line critical-path echo from the span rows.  It answers, after the fact,
whether the run a command drove was healthy while it ran — an exit code only
says how the command ended.
"""

from __future__ import annotations

import glob
import json

from rainbow_iqn_apex_tpu.obs.pipeline_trace import (
    critical_path,
    format_critical_path,
)


def health_attribution(metrics_glob) -> dict:
    """Soak attribution from obs/ ``health`` rows (docs/OBSERVABILITY.md):
    a process's exit code says whether it exited clean; the health rows say
    whether the RUN it drove was actually healthy while it ran (a chaos soak can
    exit rc=0 while degraded the whole window, and a timeout can kill a
    perfectly healthy run).  Reads every metrics.jsonl the glob matches and
    returns status counts + the last/worst status seen, or rows=0 when the
    run wrote no health rows (pre-obs artifact or a crash before the first
    flush)."""
    counts = {"ok": 0, "degraded": 0, "failing": 0}
    # elasticity rows (docs/RESILIENCE.md "heal"): a soak window that went
    # degraded AND healed reads very differently from one that stayed
    # degraded — the heal tallies carry that distinction into the summary
    heals = {"host_alive": 0, "shard_readmit": 0, "actor_fenced": 0}
    # serving-fleet rows (docs/SERVING.md "fleet"): a run that drove a
    # router/fleet (bench_serve soak) gets its route/scale/rollout activity
    # attributed the same way — sheds and scale churn are the run's story
    fleet = {"route": 0, "scale": 0, "rollout": 0}
    # cross-host serving rows (serving/net/; docs/SERVING.md "cross-host"):
    # a run that drove remote engines gets its wire story attributed —
    # transport flaps vs clean stats windows, and whether router gossip
    # actually flowed (a net soak with zero gossip rows ran solo-router)
    net = {"net": 0, "gossip": 0}
    net_flaps = 0
    # quantization rows (docs/PERFORMANCE.md "quant"): a window that kept
    # falling back to fp32 is a different finding (accuracy gate refusing)
    # than one that quantized cleanly — the tally carries it into the summary
    quant = {"quant": 0, "quant_fallback": 0, "publish": 0}
    # pipeline-tracing rows (docs/OBSERVABILITY.md "tracing"): span_link/lag
    # volume says whether a run was traced at all, and the span rows feed
    # the one-line critical_path echo below — a soak postmortem reads WHICH
    # stage bounded the run straight off the summary
    trace = {"span_link": 0, "lag": 0}
    # multi-game rows (multitask/; docs/MULTITASK.md): a run that drove a
    # multi-game run gets its per-game story attributed — how many games
    # ran, each game's latest eval + human-normalized score, and the suite
    # aggregate, straight off the summary (the "one game collapsed
    # while others train" postmortem key)
    games_tally = {"games": 0, "eval_mt": 0}
    by_game: dict = {}
    last_hn = None
    # replay-reuse rows (docs/PERFORMANCE.md "Replay reuse"): learn rows of
    # a cfg.replay_ratio > 1 run carry replay_ratio/reuse_index/clip_frac —
    # the tally says a run ran reusing, at which K, and how hard the
    # IMPACT clip was working (the K-too-high early warning) straight off
    # the summary
    reuse = {"rows": 0}
    reuse_last: dict = {}
    # league rows (league/; docs/LEAGUE.md): a run that drove a PBT
    # population gets its selection story attributed — exploit/adoption
    # counts, refused adoptions (the bit-exact copy contract breaking),
    # and the newest member count — straight off the summary
    league = {"rows": 0, "exploits": 0, "adoptions": 0, "refused": 0}
    league_last: dict = {}
    span_rows = []
    last = None
    for path in sorted(glob.glob(metrics_glob)):
        try:
            with open(path) as f:
                for line in f:
                    try:
                        row = json.loads(line)
                    except ValueError:
                        continue  # lint_jsonl's job, not attribution's
                    kind = row.get("kind")
                    if kind == "health":
                        status = row.get("status")
                        if status in counts:
                            counts[status] += 1
                            last = status
                    elif kind in heals:
                        heals[kind] += 1
                    elif kind in fleet:
                        fleet[kind] += 1
                    elif kind in net:
                        net[kind] += 1
                        if kind == "net" and row.get("event") in (
                                "disconnect", "reconnect", "probe_timeout",
                                "bad_frame"):
                            net_flaps += 1
                    elif kind in quant:
                        quant[kind] += 1
                    elif kind in games_tally:
                        games_tally[kind] += 1
                        if kind == "eval_mt":
                            last_hn = {"hn_median": row.get("hn_median"),
                                       "hn_mean": row.get("hn_mean")}
                    elif kind == "eval" and row.get("game"):
                        snap = by_game.setdefault(
                            str(row["game"]), {"evals": 0})
                        snap["evals"] += 1
                        snap["score_mean"] = row.get("score_mean")
                        if row.get("human_normalized") is not None:
                            snap["human_normalized"] = row["human_normalized"]
                    elif kind == "league":
                        league["rows"] += 1
                        ev = row.get("event")
                        if ev == "exploit":
                            league["exploits"] += 1
                        elif ev == "adopt":
                            league["adoptions"] += 1
                        elif ev == "adopt_refused":
                            league["refused"] += 1
                        elif ev == "status":
                            league_last = {
                                "alive": row.get("alive"),
                                "collapsed": row.get("collapsed"),
                            }
                    elif kind == "learn" and row.get("replay_ratio"):
                        reuse["rows"] += 1
                        reuse_last = {
                            "replay_ratio": row.get("replay_ratio"),
                            "clip_frac": row.get("clip_frac"),
                        }
                    elif kind in trace:
                        trace[kind] += 1
                        # bounded retention: the echo needs stage shares,
                        # not every span of a long traced soak; the tally
                        # above still counts the dropped tail (no silent cap
                        # — trace["span_link"] > len(span_rows) says so)
                        if kind == "span_link" and len(span_rows) < 50_000:
                            span_rows.append(row)
        except OSError:
            continue
    order = {"ok": 0, "degraded": 1, "failing": 2}
    worst = max((s for s, n in counts.items() if n),
                key=lambda s: order[s], default=None)
    out = {"rows": sum(counts.values()), "counts": counts,
           "last": last, "worst": worst, "heals": heals, "fleet": fleet,
           "quant": quant, "trace": trace,
           # one-line stage attribution; None when the run was untraced
           "critical_path": format_critical_path(critical_path(span_rows))}
    if net["net"] or net["gossip"]:
        out["net"] = {**net, "flaps": net_flaps}
    if games_tally["games"] or games_tally["eval_mt"] or by_game:
        out["games"] = {**games_tally, "by_game": by_game,
                        "aggregate": last_hn}
    if reuse["rows"]:
        out["reuse"] = {**reuse, **reuse_last}
    if league["rows"]:
        out["league"] = {**league, **league_last}
    return out
