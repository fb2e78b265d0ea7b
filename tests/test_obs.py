"""obs/ — registry, spans, health, export, NaN-safe logging, and the
golden-schema contract: every JSONL row any loop emits is strict JSON,
schema-versioned, and carries its kind's required keys (ISSUE 3).

The golden run at the bottom drives the real single-process trainer with a
chaos nan_loss injection so the collected run dir contains every row kind a
consumer must handle: learn/eval/fault/serve/health/timing/span (+ trace,
resume, swap), then obs_report and lint_jsonl — the reference consumers —
must both accept it.
"""

import json
import os
import threading
import urllib.request

import numpy as np
import pytest

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.obs import (
    MetricRegistry,
    ObsHTTPServer,
    RunHealth,
    RunObs,
    SCHEMA_VERSION,
    TraceWindow,
    Tracer,
    prometheus_text,
    sanitize,
    validate_row,
)
from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger
from rainbow_iqn_apex_tpu.utils.profiling import StepTimer

import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts"))
from lint_jsonl import lint_file, lint_line  # noqa: E402


# ---------------------------------------------------------------- sanitize


def test_sanitize_non_finite_floats():
    out = sanitize({"a": float("nan"), "b": float("inf"), "c": -float("inf"),
                    "d": 1.5, "e": [float("nan"), 2], "f": np.float32(3.0),
                    "g": np.int64(4)})
    assert out["a"] is None and out["b"] == "inf" and out["c"] == "-inf"
    assert out["d"] == 1.5 and out["e"] == [None, 2]
    assert out["f"] == 3.0 and out["g"] == 4
    json.dumps(out, allow_nan=False)  # strict-serialisable


def test_metrics_logger_rows_are_strict_json_with_envelope(tmp_path):
    path = str(tmp_path / "m.jsonl")
    m = MetricsLogger(path, "r1", echo=False, host=3)
    m.log("learn", step=1, frames=8, loss=float("nan"), q=float("inf"))
    m.close()
    (line,) = open(path).read().splitlines()
    assert "NaN" not in line and "Infinity" not in line
    row = json.loads(line)
    assert row["schema"] == SCHEMA_VERSION
    assert row["host"] == 3 and "ts" in row and row["run"] == "r1"
    assert row["loss"] is None and row["q"] == "inf"
    assert validate_row(row) == []


def test_metrics_logger_observer_sees_rows(tmp_path):
    m = MetricsLogger(None, "r", echo=False)
    seen = []
    m.add_observer(seen.append)
    m.add_observer(lambda row: 1 / 0)  # broken observer must not raise
    m.log("fault", event="rollback")
    assert seen and seen[0]["kind"] == "fault"


def test_lint_jsonl_rejects_bare_nan(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"kind": "x", "v": NaN}\n'
                 'not json at all\n'
                 '{"no_kind": 1}\n')
    errs = lint_file(str(p))
    assert len(errs) == 2  # NaN line + unparsable line; kindless object passes
    assert "non-finite" in errs[0]
    assert lint_line('{"a": 1}') is None


# ---------------------------------------------------------------- registry


def test_registry_counter_gauge_histogram():
    reg = MetricRegistry()
    c = reg.counter("reqs", "serve")
    c.inc()
    c.inc(4)
    assert reg.counter("reqs", "serve") is c and c.get() == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    reg.gauge("depth", "serve").set(7)
    assert reg.gauge("depth", "serve").get() == 7
    with pytest.raises(TypeError):
        reg.gauge("reqs", "serve")  # name+role already a counter
    h = reg.histogram("lat_ms", "serve")
    for v in range(100):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["p50"] == 50 and snap["max"] == 99
    assert snap["p99"] == 99
    h.snapshot(reset=True)
    assert h.snapshot()["count"] == 0 and h.total_count == 100


def test_registry_thread_safety():
    reg = MetricRegistry()
    c = reg.counter("n")

    def work():
        for _ in range(10_000):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(4)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert c.get() == 40_000


# ------------------------------------------------------------------- spans


def test_tracer_nesting_and_exemplars(tmp_path):
    path = str(tmp_path / "m.jsonl")
    m = MetricsLogger(path, "r", echo=False)
    tr = Tracer(MetricRegistry(), m, role="learner")
    for _ in range(3):
        with tr.span("outer"):
            with tr.span("inner"):
                pass
    m.close()
    rows = [json.loads(l) for l in open(path)]
    assert [r["name"] for r in rows] == ["inner", "outer"]  # one exemplar each
    inner, outer = rows
    assert inner["parent_id"] == outer["span_id"]  # nested under outer
    assert inner["parent_id"] != 0 and outer["parent_id"] == 0
    snap = tr.span_stats()
    assert snap["outer_ms"]["count"] == 3 and snap["inner_ms"]["count"] == 3
    tr.reset_exemplars()
    with tr.span("outer"):
        pass  # would emit again; logger closed file but log() guards on _fh


def test_step_timer_p99():
    t = StepTimer(warmup=0)
    for _ in range(12):
        t.lap()
    stats = t.stats()
    assert {"p50_s", "p90_s", "p99_s", "steps_per_sec"} <= set(stats)


def test_trace_window_captures_artifacts(tmp_path):
    logdir = str(tmp_path / "trace")
    m = MetricsLogger(str(tmp_path / "m.jsonl"), "r", echo=False)
    tw = TraceWindow(logdir, start_step=3, num_steps=2, logger=m)
    for step in range(1, 8):
        tw.step(step)
    assert not tw.active
    tw.close()
    m.close()
    assert any((tmp_path / "trace").rglob("*"))  # profiler wrote artifacts
    rows = [json.loads(l) for l in open(tmp_path / "m.jsonl")]
    events = [r["event"] for r in rows if r["kind"] == "trace"]
    assert events == ["trace_started", "trace_captured"]


def test_trace_window_resumed_past_window_never_arms(tmp_path):
    tw = TraceWindow(str(tmp_path / "t"), start_step=5, num_steps=2)
    tw.step(100)  # resumed run already past the window
    assert not tw.active and not tw._armed


# ------------------------------------------------------------------ health


def test_health_ok_degraded_failing_transitions():
    reg = MetricRegistry()
    h = RunHealth(reg, logger=None, max_nan_strikes=3)
    assert h.tick(10)["status"] == "ok"
    h.observe_row({"kind": "fault", "event": "io_retry"})
    row = h.tick(20)
    assert row["status"] == "degraded" and row["io_retries"] == 1
    assert h.tick(30)["status"] == "ok"  # window cleared, no new faults
    for strikes in (1, 2, 3):
        h.observe_row({"kind": "fault", "event": "nonfinite_step",
                       "strikes": strikes})
    assert h.tick(40)["status"] == "failing"  # strike budget reached
    h.note_finite_step()
    assert h.tick(50)["status"] == "ok"


def test_health_stall_without_progress_is_failing():
    h = RunHealth(MetricRegistry(), max_nan_strikes=3)
    h.tick(10)
    h.observe_row({"kind": "fault", "event": "stalled_step", "elapsed_s": 9.9})
    assert h.tick(10)["status"] == "failing"  # zero steps since last tick
    h.observe_row({"kind": "fault", "event": "stalled_step", "elapsed_s": 9.9})
    assert h.tick(25)["status"] == "degraded"  # stalled but stepping again


def test_health_dead_host_and_sheds():
    h = RunHealth(MetricRegistry(), max_nan_strikes=3)
    h.observe_row({"kind": "fault", "event": "host_dead", "dead_host": 1})
    row = h.tick(5)
    assert row["status"] == "degraded" and row["hosts_dead"] == [1]
    assert h.tick(10)["status"] == "degraded"  # a dead host stays degraded
    h2 = RunHealth(MetricRegistry(), max_nan_strikes=3)
    h2.observe_row({"kind": "serve", "requests": 5, "batches": 1, "shed": 2})
    assert h2.tick(1)["status"] == "degraded" and h2.total_shed == 2


def test_healthz_reports_wedged_run_as_failing():
    """A wedged loop never ticks again: the stall row must flip the LIVE
    /healthz status to failing (503) without waiting for a tick, and a
    completed step afterwards must clear it."""
    h = RunHealth(MetricRegistry(), max_nan_strikes=3)
    h.tick(10)
    h.observe_row({"kind": "fault", "event": "stalled_step", "elapsed_s": 300})
    assert h.healthz()["status"] == "failing"  # no tick needed
    h.note_finite_step()  # a learn step completed: the wedge resolved
    assert h.healthz()["status"] != "failing"


def test_train_aborted_is_failing_and_healthz_live():
    h = RunHealth(MetricRegistry(), max_nan_strikes=3)
    h.tick(1)
    h.observe_row({"kind": "fault", "event": "train_aborted"})
    hz = h.healthz()  # live status flips before the next tick
    assert hz["status"] == "failing" and "ts" in hz


# ------------------------------------------------------------------ export


def test_prometheus_text_exposition():
    reg = MetricRegistry()
    reg.counter("serve_requests_total", "serve").inc(5)
    reg.gauge("queue_depth").set(2)
    h = reg.histogram("latency_ms", "serve")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    text = prometheus_text(reg)
    assert '# TYPE ria_serve_requests_total counter' in text
    assert 'ria_serve_requests_total{role="serve"} 5' in text
    assert "ria_queue_depth 2" in text
    assert 'ria_latency_ms{role="serve",quantile="0.5"} 2' in text
    assert 'ria_latency_ms_count{role="serve"} 3' in text


def test_http_metrics_and_healthz_endpoints():
    reg = MetricRegistry()
    reg.counter("hits").inc(3)
    state = {"status": "ok"}
    srv = ObsHTTPServer(reg, lambda: dict(state), port=0).start()
    try:
        body = urllib.request.urlopen(srv.url + "/metrics", timeout=5).read()
        assert b"ria_hits 3" in body
        resp = urllib.request.urlopen(srv.url + "/healthz", timeout=5)
        assert resp.status == 200
        assert json.loads(resp.read())["status"] == "ok"
        state["status"] = "failing"
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(srv.url + "/healthz", timeout=5)
        assert exc.value.code == 503
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(srv.url + "/nope", timeout=5)
    finally:
        srv.stop()


def test_serve_metrics_mirrors_shared_registry(tmp_path):
    from rainbow_iqn_apex_tpu.serving.metrics import ServeMetrics

    reg = MetricRegistry()
    sm = ServeMetrics(registry=reg)
    sm.record_batch(6, padded=8, queue_depth=3)
    sm.record_shed(2)
    sm.record_latency_ms(4.2)
    sm.record_swap(ok=True)
    assert reg.counter("serve_requests_total", "serve").get() == 6
    assert reg.counter("serve_shed_total", "serve").get() == 2
    assert reg.counter("serve_swaps_total", "serve").get() == 1
    assert reg.gauge("serve_queue_depth", "serve").get() == 3
    assert reg.histogram("serve_latency_ms", "serve").total_count == 1
    # public API unchanged: window snapshot + lifetime stats still there
    stats = sm.stats()
    assert stats["total_requests"] == 6 and stats["shed"] == 2
    assert sm.emit()["requests"] == 6


# ------------------------------------------------- golden schema, end to end

GOLDEN_KINDS = {"learn", "eval", "fault", "serve", "health", "timing", "span"}


@pytest.fixture(scope="module")
def golden_run(tmp_path_factory):
    """One tiny real run of the single-process trainer with a nan_loss chaos
    injection (fault rows) + a ServeMetrics side-car (serve/swap rows) + an
    armed trace window: the full row-kind surface in one run dir."""
    from rainbow_iqn_apex_tpu.train import train

    tmp = tmp_path_factory.mktemp("golden")
    cfg = Config(
        env_id="toy:catch", compute_dtype="float32", frame_height=80,
        frame_width=80, history_length=2, hidden_size=64, num_cosines=16,
        num_tau_samples=4, num_tau_prime_samples=4, num_quantile_samples=4,
        batch_size=16, learning_rate=1e-3, adam_eps=1e-8, multi_step=3,
        gamma=0.9, memory_capacity=4096, learn_start=256, frames_per_learn=2,
        target_update_period=200, num_envs_per_actor=8, metrics_interval=100,
        eval_interval=0, checkpoint_interval=0, eval_episodes=2,
        prefetch_depth=0, seed=7,
        results_dir=str(tmp / "results"), checkpoint_dir=str(tmp / "ckpt"),
        trace_dir=str(tmp / "trace"), trace_start_step=20, trace_num_steps=5,
        fault_spec="nan_loss@30", guard_snapshot_interval=10,
    )
    summary = train(cfg, max_frames=900)
    run_dir = os.path.join(cfg.results_dir, cfg.run_id)
    # serving side-car rows land in the same run dir (the colocated layout)
    sm_logger = MetricsLogger(os.path.join(run_dir, "serve.jsonl"),
                              cfg.run_id, echo=False)
    from rainbow_iqn_apex_tpu.serving.metrics import ServeMetrics

    sm = ServeMetrics(sm_logger, registry=MetricRegistry())
    sm.record_batch(6, padded=8, queue_depth=1)
    sm.record_latency_ms(3.3)
    sm.record_swap(ok=True, step=100, source="test")
    sm.emit()
    sm_logger.close()
    return run_dir, summary


def test_golden_every_row_valid_and_all_kinds_present(golden_run):
    run_dir, summary = golden_run
    assert summary["rollbacks"] >= 1  # the injection really fired
    rows, kinds = [], set()
    for name in sorted(os.listdir(run_dir)):
        if not name.endswith(".jsonl"):
            continue
        path = os.path.join(run_dir, name)
        assert lint_file(path) == [], path
        for line in open(path):
            row = json.loads(line)
            assert validate_row(row) == [], row
            rows.append(row)
            kinds.add(row["kind"])
    assert GOLDEN_KINDS <= kinds, kinds
    # fault rows carry the chaos story
    events = {r["event"] for r in rows if r["kind"] == "fault"}
    assert {"injected_nan_batch", "nonfinite_step", "rollback"} <= events
    # health must have noticed (the injected-NaN window is degraded)
    statuses = [r["status"] for r in rows if r["kind"] == "health"]
    assert "degraded" in statuses


def test_obs_report_on_golden_run(golden_run, capsys):
    from obs_report import main as report_main

    run_dir, _ = golden_run
    assert report_main([run_dir]) == 0
    out = capsys.readouterr().out
    assert "obs_report" in out and "learner:" in out and "health:" in out
    assert report_main([run_dir, "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["rows"] > 0
    assert report["roles"]["learner"]["steps"] > 0
    assert report["roles"]["serve"]["requests"] == 6
    assert report["faults"].get("rollback", 0) >= 1
    assert report["health"]["last_status"] in ("ok", "degraded")
    assert report["lint_errors"] == 0


def test_obs_report_empty_dir_exits_nonzero(tmp_path):
    from obs_report import main as report_main

    assert report_main([str(tmp_path)]) == 1


def test_run_obs_http_endpoint_serves_driver_registry(tmp_path):
    """The apex-driver side of the acceptance: a RunObs built with
    obs_http_port exposes /metrics + /healthz while the run lives."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    m = MetricsLogger(None, "r", echo=False)
    obs = RunObs(Config(obs_http_port=port), m, role="learner")
    try:
        obs.registry.counter("probe").inc()
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read()
        assert b"ria_probe 1" in body
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5)
        assert resp.status == 200
    finally:
        obs.close()


def test_policy_server_serves_metrics_and_healthz():
    """The serving side of the acceptance: a PolicyServer built with
    obs_http_port answers /metrics (shared-registry exposition) and /healthz
    (queue/shed/worker status) for its lifetime."""
    import socket

    import jax
    from rainbow_iqn_apex_tpu.ops.learn import init_train_state
    from rainbow_iqn_apex_tpu.serving import PolicyServer

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cfg = Config(
        compute_dtype="float32", frame_height=44, frame_width=44,
        history_length=2, hidden_size=64, num_cosines=16, num_tau_samples=8,
        num_tau_prime_samples=8, num_quantile_samples=4,
        serve_batch_buckets="4", serve_deadline_ms=3.0,
        obs_http_port=port,
    )
    state = init_train_state(cfg, 4, jax.random.PRNGKey(0))
    server = PolicyServer(cfg, 4, state.params, devices=jax.devices()[:1])
    with server:
        obs = np.zeros((44, 44, 2), np.uint8)
        server.act(obs, timeout=30.0)
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5)
        assert resp.status == 200
        hz = json.loads(resp.read())
        assert hz["status"] == "ok" and hz["worker_alive"]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "ria_serve_requests_total" in body
    # endpoint is torn down with the server
    with pytest.raises(OSError):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=2)


def test_health_attribution(tmp_path):
    from rainbow_iqn_apex_tpu.obs.attribution import health_attribution

    run = tmp_path / "runs" / "r0"
    run.mkdir(parents=True)
    with open(run / "metrics.jsonl", "w") as f:
        f.write(json.dumps({"kind": "health", "status": "ok"}) + "\n")
        f.write(json.dumps({"kind": "health", "status": "degraded"}) + "\n")
        f.write(json.dumps({"kind": "learn", "step": 1}) + "\n")
        f.write("garbage line\n")
    attr = health_attribution(str(tmp_path / "runs" / "*" / "metrics.jsonl"))
    assert attr["rows"] == 2 and attr["counts"]["degraded"] == 1
    assert attr["last"] == "degraded" and attr["worst"] == "degraded"
    empty = health_attribution(str(tmp_path / "nope" / "*.jsonl"))
    assert empty["rows"] == 0 and empty["worst"] is None


# ------------------------------------------------- elasticity rows (PR 4)
# host_alive / shard_readmit / actor_fenced: the heal half of the fault
# story — schema'd, health-folded, and lintable like every other kind.


def test_elastic_row_kinds_schema_and_lint(tmp_path):
    """The three elasticity kinds validate with their required keys, reject
    rows missing them, and pass the strict-JSON linter end to end."""
    path = str(tmp_path / "elastic.jsonl")
    logger = MetricsLogger(path, "run0", echo=False, host=0)
    logger.log("host_alive", alive_host=1, epoch=2, step=10, frames=100)
    logger.log("shard_readmit", shard=0, epoch=2, step=10, frames=100)
    logger.log("actor_fenced", action="fence", lag=3, max_lag=2, step=10)
    logger.log("actor_fenced", action="resume", lag=0, max_lag=2, step=12)
    logger.close()
    assert lint_file(path) == []
    for line in open(path):
        assert validate_row(json.loads(line)) == []
    # required keys are enforced, not decorative
    assert validate_row({"kind": "host_alive", "schema": SCHEMA_VERSION,
                         "ts": 1.0, "host": 0, "run": "r"}) != []
    assert validate_row({"kind": "shard_readmit", "schema": SCHEMA_VERSION,
                         "ts": 1.0, "host": 0, "run": "r", "shard": 1}) != []
    assert validate_row({"kind": "actor_fenced", "schema": SCHEMA_VERSION,
                         "ts": 1.0, "host": 0, "run": "r", "lag": 1}) != []


def test_trace_row_kinds_schema_and_lint(tmp_path):
    """The pipeline-tracing kinds (span_link / lag, ISSUE 9) validate with
    their required keys, reject rows missing them, and pass the strict-JSON
    linter — the golden-schema contract extended to the tracing surface."""
    path = str(tmp_path / "trace.jsonl")
    logger = MetricsLogger(path, "run0", echo=False, host=0)
    logger.log("span_link", stage="learn_step", trace_id="l0-8", span_id=3,
               parent_id=0, t0=1234.5, dur_ms=12.25, role="learner",
               links=["a0-4"], step=8)
    logger.log("lag", step=8,
               sample_age_s={"count": 4, "p50": 1.2, "p99": 3.0, "max": 3.1},
               publish_adopt_ms_by_consumer={
                   "actor_inproc": {"count": 2, "p50": 1.0, "p99": 2.0,
                                    "max": 2.0}},
               publish_adopt_budget_ms=500.0)
    logger.close()
    assert lint_file(path) == []
    for line in open(path):
        assert validate_row(json.loads(line)) == []
    # required keys are enforced, not decorative
    assert validate_row({"kind": "span_link", "schema": SCHEMA_VERSION,
                         "ts": 1.0, "host": 0, "run": "r",
                         "stage": "act"}) != []
    assert validate_row({"kind": "lag", "schema": SCHEMA_VERSION,
                         "ts": 1.0, "host": 0, "run": "r"}) != []


def test_health_heals_on_host_alive_and_eviction():
    """The heal edges close the degradation they opened: host_alive removes
    the host from the dead set, and a permanent eviction stops holding the
    run degraded (a deliberately resized fleet is healthy at its new size)
    while staying on the books as evicted."""
    h = RunHealth(MetricRegistry(), max_nan_strikes=3)
    h.observe_row({"kind": "fault", "event": "host_dead", "dead_host": 1})
    h.observe_row({"kind": "fault", "event": "host_dead", "dead_host": 2})
    row = h.tick(5)
    assert row["status"] == "degraded" and row["hosts_dead"] == [1, 2]
    # host 1 revives; its shard is readmitted
    h.observe_row({"kind": "host_alive", "alive_host": 1, "epoch": 1})
    h.observe_row({"kind": "shard_readmit", "shard": 0, "epoch": 1})
    row = h.tick(10)
    assert row["hosts_dead"] == [2] and row["readmits"] == 1
    assert row["status"] == "degraded"  # host 2 still dead
    # host 2 is permanently evicted: degraded no longer, but visible
    h.observe_row({"kind": "fault", "event": "actor_evicted", "role_host": 2})
    assert h.tick(15)["status"] == "degraded"  # the eviction's own window
    row = h.tick(20)
    assert row["status"] == "ok"
    assert row["hosts_dead"] == [] and row["hosts_evicted"] == [2]


def test_health_fenced_actor_holds_degraded_until_resume():
    h = RunHealth(MetricRegistry(), max_nan_strikes=3)
    h.observe_row({"kind": "actor_fenced", "action": "fence", "host": 3,
                   "lag": 4, "max_lag": 2})
    assert h.tick(5)["status"] == "degraded"
    row = h.tick(10)  # still fenced: no clean window until it resumes
    assert row["status"] == "degraded" and row["hosts_fenced"] == [3]
    h.observe_row({"kind": "actor_fenced", "action": "resume", "host": 3,
                   "lag": 0, "max_lag": 2})
    h.tick(15)  # the resume edge's window
    assert h.tick(20)["status"] == "ok"


def test_health_attribution_counts_heals(tmp_path):
    from rainbow_iqn_apex_tpu.obs.attribution import health_attribution

    run = tmp_path / "runs" / "r0"
    run.mkdir(parents=True)
    with open(run / "metrics.jsonl", "w") as f:
        f.write(json.dumps({"kind": "health", "status": "degraded"}) + "\n")
        f.write(json.dumps({"kind": "host_alive", "alive_host": 1,
                            "epoch": 1}) + "\n")
        f.write(json.dumps({"kind": "shard_readmit", "shard": 0,
                            "epoch": 1}) + "\n")
        f.write(json.dumps({"kind": "actor_fenced", "action": "fence",
                            "lag": 3, "max_lag": 2}) + "\n")
        f.write(json.dumps({"kind": "health", "status": "ok"}) + "\n")
    attr = health_attribution(str(tmp_path / "runs" / "*" / "metrics.jsonl"))
    assert attr["rows"] == 2 and attr["last"] == "ok"
    assert attr["heals"] == {"host_alive": 1, "shard_readmit": 1,
                             "actor_fenced": 1}
