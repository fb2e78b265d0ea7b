# Convenience targets; CI drives the same commands directly.

PY ?= python

.PHONY: test test-fast serve-smoke serve-bench chaos-smoke obs-smoke soak-smoke failover-smoke fleet-smoke quant-smoke trace-smoke multitask-smoke net-smoke replaynet-smoke obsnet-smoke netchaos-smoke league-smoke static-smoke

# tier-1: fast unit + integration tests on the virtual 8-device CPU mesh
test-fast:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m "not slow"

# static-invariant smoke (docs/OBSERVABILITY.md "Static invariants"): the
# `static`-marked analyzer tests (golden fixtures + the finding-free
# meta-test — tier-1 too), then the full-package analyzer run against the
# checked-in EMPTY baseline (exit 1 on any finding).  The CLI deliberately
# imports jax-free — the jax-free checker self-hosts that claim.
static-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_analysis.py -q -m static
	$(PY) scripts/static_analysis.py

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q

# policy-server smoke: start -> request -> shutdown, in-process transport,
# no network listener — the `serve`-marked subset of tier-1
serve-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_serving.py -q -m serve

# load-generator bench (acceptance: occupancy > 4, zero sheds, swap mid-run)
serve-bench:
	JAX_PLATFORMS=cpu $(PY) scripts/bench_serve.py --clients 64 --requests 2000

# fleet smoke (docs/SERVING.md "fleet"): the `serve`-marked fleet tests
# (router invariants on real engines) plus the heavy-traffic soak — a
# 2-engine in-process fleet under bursty open-loop arrivals with a slow-
# client cohort, one engine killed cold mid-load (re-route, zero lost
# accepted requests), two weight rollouts (one deliberately backward =
# refused), enforced p99/shed gates — and the run dir must lint as strict
# schema-versioned JSONL (route/scale/rollout rows included)
fleet-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fleet.py -q -m serve
	rm -rf /tmp/ria_fleet_smoke
	JAX_PLATFORMS=cpu $(PY) scripts/bench_serve.py --fleet-soak \
	  --engines 2 --duration 8 --out /tmp/ria_fleet_smoke
	$(PY) scripts/lint_jsonl.py /tmp/ria_fleet_smoke

# cross-host serving smoke (docs/SERVING.md "cross-host"): the `net`-marked
# unit tests (frame codec hardening, transport/registry/gossip/rollout over
# real loopback sockets — tier-1 too), then the REAL multi-process fleet:
# 2 shared-nothing routers (gossip-federated) over 3 engine-host processes
# discovered purely via lease files, one host SIGKILLed mid-load; gates
# (self-asserted, exit 1): zero lost accepted requests, re-route fired, the
# int8-delta rollout converged on every survivor with BIT-EXACT
# reconstruction asserted by digest, and the run dir lints as strict
# schema-versioned JSONL (route/net/gossip/rollout rows included); then the
# --net soak variant records the wire-rollout byte ratio as one net_soak row
net-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_net.py -q -m net
	rm -rf /tmp/ria_net_smoke
	JAX_PLATFORMS=cpu $(PY) scripts/net_smoke.py --engines 3 --routers 2 \
	  --duration 6 --out /tmp/ria_net_smoke
	$(PY) scripts/lint_jsonl.py /tmp/ria_net_smoke
	rm -rf /tmp/ria_net_soak
	JAX_PLATFORMS=cpu $(PY) scripts/bench_serve.py --fleet-soak --net \
	  --engines 2 --duration 8 --out /tmp/ria_net_soak
	$(PY) scripts/lint_jsonl.py /tmp/ria_net_soak

# cross-host replay smoke (docs/RESILIENCE.md "replay plane"): the
# `net`-marked replay plane tests (framing hoist, append/sample/update
# round trip, bitwise twin + chi-square sampling parity, epoch fencing,
# drop/readmit, step-fenced snapshots — tier-1 too), then the REAL
# multi-process soak: 2 actor hosts + 1 learner + 2 shard-server
# processes discovered purely via lease files, one server SIGKILLed
# mid-load and respawned at a bumped epoch; gates (self-asserted, exit
# 1): the learner never stalls, zero appended-and-acked rows lost on the
# survivor, readmit restores sampling from the revived incarnation, the
# step-fenced server-side snapshot acked — and the run dir lints as
# strict schema-versioned JSONL (replay_net rows included)
replaynet-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_replay_net.py -q -m net
	rm -rf /tmp/ria_replaynet_smoke
	JAX_PLATFORMS=cpu $(PY) scripts/replay_net_smoke.py --duration 12 \
	  --out /tmp/ria_replaynet_smoke
	$(PY) scripts/lint_jsonl.py /tmp/ria_replaynet_smoke

# live-telemetry-plane smoke (docs/OBSERVABILITY.md "Live fleet
# telemetry"): the `obsnet`-marked tests (label escaping, /healthz crash
# path, relay shed-not-stall, fleet fold transitions, alert edges,
# obs_top golden — tier-1 too), then the REAL multi-process soak: 1 obs
# collector + 3 toy trainers discovered purely via lease files, the
# collector SIGKILLed cold mid-load and respawned at a bumped epoch;
# gates (self-asserted, exit 1): training rows never stall, relays
# shed + reconnect, the fleet view re-converges to ok on the NEW
# incarnation — and the run dir lints as strict schema-versioned JSONL
# (obs_net/alert/fleet_health rows included); obs_report must render the
# `obsnet:` section off the soak's rows
obsnet-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_obs_net.py -q -m obsnet
	rm -rf /tmp/ria_obsnet_smoke
	JAX_PLATFORMS=cpu $(PY) scripts/obs_net_smoke.py --duration 12 \
	  --out /tmp/ria_obsnet_smoke
	$(PY) scripts/lint_jsonl.py /tmp/ria_obsnet_smoke/obs_net_smoke
	$(PY) scripts/obs_report.py /tmp/ria_obsnet_smoke/obs_net_smoke \
	  | tee /tmp/ria_obsnet_smoke/report.txt
	grep -q "obsnet:" /tmp/ria_obsnet_smoke/report.txt

# network-chaos smoke (docs/RESILIENCE.md "degraded network"): the
# `netchaos`-marked tests (spec grammar, seeded determinism, per-fault
# socket semantics, disarmed-identity, plane recovery under injected
# corruption/latency/partition — tier-1 too), then the REAL multi-process
# soak: router + 2 engine hosts, 2 replay shards + learner appenders, obs
# collector, warm standby — all under a seeded rotating fault schedule
# (corruption -> latency+rate-limit -> dual one-way partitions -> heal);
# gates (self-asserted, exit 1): every fault phase actually injected, zero
# lost accepted serve requests, zero acked replay rows lost, NO split
# brain across the asymmetric partition (exactly one learner epoch after
# heal), fleet re-converges within the MTTR bound, chaos rows name the
# injected site — and the run dir lints as strict schema-versioned JSONL.
# The DISARMED interposer's seam is an identity (maybe_wrap returns the
# socket object unchanged):
# tests/test_net_chaos.py::test_defaults_off_and_maybe_wrap_identity
netchaos-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest -q -m netchaos
	rm -rf /tmp/ria_netchaos_smoke
	JAX_PLATFORMS=cpu $(PY) scripts/net_chaos_soak.py \
	  --out /tmp/ria_netchaos_smoke
	$(PY) scripts/lint_jsonl.py /tmp/ria_netchaos_smoke/net_chaos_soak

# chaos smoke: every named fault-injection point exercised end to end
# (NaN rollback, corrupt-checkpoint fallback, torn-snapshot CRC, retried
# checkpoint IO, stall watchdog, heartbeat loss) — the `chaos`-marked
# subset of tier-1 (docs/RESILIENCE.md)
chaos-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_resilience.py -q -m chaos

# elastic soak smoke: a real multi-process kill/revive schedule must HEAL —
# 2 actor hosts killed, 1 revived (respawn -> lease rejoin -> shard
# readmission), the other evicted after its FailureBudget, stale-epoch spool
# rows fenced, no actor acting past max-weight-lag, final health ok; the
# harness asserts all of it from its own JSONL (docs/RESILIENCE.md).  The
# same path runs tier-1 under the `chaos` marker (tests/test_elastic.py).
soak-smoke:
	rm -rf /tmp/ria_soak_smoke
	JAX_PLATFORMS=cpu $(PY) scripts/chaos_soak.py --frames 2000 \
	  --kill-schedule seeded --out /tmp/ria_soak_smoke
	$(PY) scripts/lint_jsonl.py /tmp/ria_soak_smoke/results

# learner-failover smoke (docs/RESILIENCE.md "learner failover"): the
# failover unit/race tests, then the real-process kill: SIGKILL the toy
# learner mid-run with a live standby — the harness gates that the standby
# claims within the lease timeout, mailbox versions stay strictly monotone
# across the takeover, every adoption is digest-exact (zero stale adopts),
# the successor's post-takeover state is bitwise a plain kill->resume from
# the same checkpoint, and the run dir lints.  Emits one report-only
# failover_mttr row.
failover-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_failover.py -q -m chaos
	rm -rf /tmp/ria_failover_smoke
	JAX_PLATFORMS=cpu $(PY) scripts/chaos_soak.py --kill-learner \
	  --out /tmp/ria_failover_smoke
	$(PY) scripts/lint_jsonl.py /tmp/ria_failover_smoke/results

# trace smoke (docs/OBSERVABILITY.md "tracing"): a tiny TRACED apex run
# (trace_sample_every=4) must yield span_link/lag rows that (1) lint as
# strict schema-versioned JSONL, (2) export to VALID Perfetto trace_event
# JSON (cross-host flow events, schema-checked by trace_export --check),
# and (3) drive obs_report to a `critical_path:` stage verdict
trace-smoke:
	rm -rf /tmp/ria_trace_smoke
	JAX_PLATFORMS=cpu $(PY) train_agent_apex.py --role apex \
	  --env-id toy:catch --compute-dtype float32 --history-length 2 \
	  --hidden-size 64 --num-cosines 16 --num-tau-samples 4 \
	  --num-tau-prime-samples 4 --num-quantile-samples 4 --batch-size 16 \
	  --learning-rate 1e-3 --multi-step 3 --gamma 0.9 --memory-capacity 4096 \
	  --learn-start 512 --frames-per-learn 2 --target-update-period 200 \
	  --num-envs-per-actor 8 --metrics-interval 100 --eval-interval 0 \
	  --checkpoint-interval 0 --eval-episodes 2 --t-max 3072 \
	  --trace-sample-every 4 --weight-publish-interval 200 \
	  --run-id trace_smoke --results-dir /tmp/ria_trace_smoke/results \
	  --checkpoint-dir /tmp/ria_trace_smoke/ckpt
	$(PY) scripts/lint_jsonl.py /tmp/ria_trace_smoke/results/trace_smoke
	$(PY) scripts/trace_export.py /tmp/ria_trace_smoke/results/trace_smoke \
	  -o /tmp/ria_trace_smoke/trace.json --check
	$(PY) scripts/obs_report.py /tmp/ria_trace_smoke/results/trace_smoke \
	  | tee /tmp/ria_trace_smoke/report.txt
	grep -q "critical_path:" /tmp/ria_trace_smoke/report.txt

# quant smoke (docs/PERFORMANCE.md "quantization"): the quantize unit tests
# (codec bit-exactness, delta resync, gate fallback, off-mode bitwise), one
# REAL-engine int8 serve via bench_serve --quant (the agreement gate must
# ACTIVATE the quantized path and both numeric modes must answer the same
# load correctly), and the run dir must lint as strict schema-versioned
# JSONL (quant/quant_fallback/publish rows included)
quant-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_quantize.py -q
	rm -rf /tmp/ria_quant_smoke
	JAX_PLATFORMS=cpu $(PY) scripts/bench_serve.py --quant \
	  --clients 16 --requests 300 --out /tmp/ria_quant_smoke
	$(PY) scripts/lint_jsonl.py /tmp/ria_quant_smoke

# multitask smoke (docs/MULTITASK.md): the `multitask`-marked tests, then a
# seeded 2-game toy apex run that must (1) lint as strict schema-versioned
# JSONL (games/eval_mt rows included), (2) drive obs_report to a `games:`
# per-game section, and (3) contain a per-game eval row for BOTH games
multitask-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_multitask.py -q -m multitask
	rm -rf /tmp/ria_mt_smoke
	JAX_PLATFORMS=cpu $(PY) train_agent_apex.py --role apex \
	  --games toy:catch,toy:chain --compute-dtype float32 \
	  --history-length 2 --hidden-size 64 --num-cosines 16 \
	  --num-tau-samples 4 --num-tau-prime-samples 4 \
	  --num-quantile-samples 4 --batch-size 16 --learning-rate 1e-3 \
	  --multi-step 3 --gamma 0.9 --memory-capacity 4096 --learn-start 512 \
	  --frames-per-learn 2 --target-update-period 200 --num-envs-per-actor 8 \
	  --metrics-interval 100 --eval-interval 200 --checkpoint-interval 0 \
	  --eval-episodes 2 --t-max 3072 --run-id mt_smoke \
	  --results-dir /tmp/ria_mt_smoke/results \
	  --checkpoint-dir /tmp/ria_mt_smoke/ckpt
	$(PY) scripts/lint_jsonl.py /tmp/ria_mt_smoke/results/mt_smoke
	$(PY) scripts/obs_report.py /tmp/ria_mt_smoke/results/mt_smoke \
	  | tee /tmp/ria_mt_smoke/report.txt
	grep -q "games:" /tmp/ria_mt_smoke/report.txt
	$(PY) -c "import json; rows = [json.loads(l) for l in \
	  open('/tmp/ria_mt_smoke/results/mt_smoke/metrics.jsonl')]; \
	  games = {r.get('game') for r in rows if r.get('kind') == 'eval'}; \
	  assert games == {'toy:catch', 'toy:chain'}, games; \
	  mt = [r for r in rows if r.get('kind') == 'eval_mt']; \
	  assert mt and mt[-1].get('hn_median') is not None, 'no eval_mt row'; \
	  print('multitask-smoke: per-game eval rows present for', \
	        sorted(games), 'hn_median=%s' % mt[-1]['hn_median'])"

# league smoke (docs/LEAGUE.md): the `league`-marked tier-1 tests (seeded
# exploit determinism, bit-exact mailbox-chain copy, fitness ordering with
# missing/NaN evals, respawn keeps member id + generation, default-off
# bitwise parity), then the REAL multi-process soak: a seeded 2-member
# population of genuine toy-scale train() loops under the LeagueController,
# one FORCED truncation exploit; self-asserted gates (exit 1): the loser's
# adopted weights are digest-identical to the winner's published outbox
# reconstruction, the loser's genome was perturbed (not equal to the
# source's), member leases carried member/generation, and the league dir
# lints as strict schema-versioned JSONL; then obs_report must render the
# `league:` per-member section off the controller's rows
league-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_league.py -q -m league
	rm -rf /tmp/ria_league_smoke
	JAX_PLATFORMS=cpu $(PY) scripts/league_soak.py --members 2 \
	  --out /tmp/ria_league_smoke
	$(PY) scripts/lint_jsonl.py /tmp/ria_league_smoke
	$(PY) scripts/obs_report.py /tmp/ria_league_smoke \
	  | tee /tmp/ria_league_smoke/report.txt
	grep -q "league:" /tmp/ria_league_smoke/report.txt

# obs smoke: a short anakin run must yield a lintable, reportable run dir —
# obs_report prints per-role throughput / learn-step percentiles / health,
# lint_jsonl proves every row is strict, schema-versioned JSON
# (docs/OBSERVABILITY.md)
obs-smoke:
	rm -rf /tmp/ria_obs_smoke
	JAX_PLATFORMS=cpu $(PY) train_agent_apex.py --role anakin \
	  --env-id toy:catch --compute-dtype float32 --history-length 2 \
	  --hidden-size 64 --num-cosines 16 --num-tau-samples 4 \
	  --num-tau-prime-samples 4 --num-quantile-samples 4 --batch-size 16 \
	  --learning-rate 1e-3 --multi-step 3 --gamma 0.9 --memory-capacity 4096 \
	  --learn-start 512 --frames-per-learn 2 --target-update-period 200 \
	  --num-envs-per-actor 8 --metrics-interval 200 --eval-interval 0 \
	  --checkpoint-interval 0 --eval-episodes 4 --t-max 2048 \
	  --run-id obs_smoke --results-dir /tmp/ria_obs_smoke/results \
	  --checkpoint-dir /tmp/ria_obs_smoke/ckpt
	$(PY) scripts/obs_report.py /tmp/ria_obs_smoke/results/obs_smoke
	$(PY) scripts/lint_jsonl.py /tmp/ria_obs_smoke/results/obs_smoke
