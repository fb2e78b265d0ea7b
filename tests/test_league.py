"""League / population-based training (ISSUE 13; docs/LEAGUE.md).

Coverage map (the ISSUE's test satellite):
1. Config validation: reasoned errors for malformed league_* specs at loop
   start (empty/1-member population, overlapping quantiles, perturb factor
   <= 0, zero fitness window, member id without a league dir).
2. Seeded exploit determinism: same seed -> identical plans AND identical
   perturbed genomes; different seed -> different explore step.
3. Bit-exact weight copy via the mailbox chain: winner outbox (int8-delta
   chain) -> controller chain-file copy -> loser inbox -> fresh-decoder
   replay, digest-identical at every hop; monotone generation refusal.
4. Fitness ordering with missing/NaN evals: NaN rows skipped, unmeasured
   members excluded from BOTH quantiles, deterministic tie-breaks.
5. Dead-member respawn keeps member id + generation (RoleSupervisor role
   identity + genome-file persistence), eviction after budget; per-role
   restart/evict counters exposed (stats() + registry).
6. Default-off bitwise parity: league fields at defaults run ZERO league
   code, and a league member whose genome equals the config (no directive
   ever) trains to the SAME final weights as a league-less run.
7. Mid-run adoption at a drain boundary: a planted directive swaps weights
   digest-exactly and retunes lr/n-step/omega live (train.py path; the
   set_n_step eligibility re-fence is unit-checked against a fresh build).
"""

import json
import os

import numpy as np
import pytest

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.league import exploit as exploit_mod
from rainbow_iqn_apex_tpu.league.controller import LeagueController
from rainbow_iqn_apex_tpu.league.fitness import (
    FitnessTracker,
    quantile_split,
    rank_members,
)
from rainbow_iqn_apex_tpu.league.population import (
    Genome,
    check_league_config,
    genome_from_config,
    genome_path,
    load_genome,
    overlay_config,
    perturb_genome,
    save_genome,
)
from rainbow_iqn_apex_tpu.parallel.elastic import WeightMailbox

pytestmark = pytest.mark.league

TOY = dict(
    env_id="toy:catch", compute_dtype="float32", history_length=2,
    hidden_size=32, num_cosines=8, num_tau_samples=4,
    num_tau_prime_samples=4, num_quantile_samples=4, batch_size=16,
    learning_rate=1e-3, multi_step=3, gamma=0.9, memory_capacity=2048,
    learn_start=128, frames_per_learn=2, target_update_period=100,
    num_envs_per_actor=4, metrics_interval=40, eval_interval=0,
    checkpoint_interval=0, eval_episodes=1, weight_publish_interval=80,
    t_max=512,
)


def _params(seed=0, shapes=(("a/w", (3, 4)), ("b", (4,)))):
    rng = np.random.default_rng(seed)
    out = {}
    for path, shape in shapes:
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = rng.standard_normal(shape).astype(np.float32)
    return out


# -------------------------------------------------------- 1. config validation
def test_league_off_validates_quietly():
    check_league_config(Config())  # no-op


@pytest.mark.parametrize("fields,needle", [
    (dict(league_dir="/tmp/x", league_population=1), "league_population"),
    (dict(league_population=2), "league_dir"),
    (dict(league_member_id=0), "league_member_id"),
    (dict(league_dir="/tmp/x", league_population=2,
          league_bottom_quantile=0.6, league_top_quantile=0.6),
     "must not exceed 1.0"),
    (dict(league_dir="/tmp/x", league_population=2,
          league_bottom_quantile=0.0), "strictly in (0, 1)"),
    (dict(league_dir="/tmp/x", league_population=2,
          league_perturb_factor=0.0), "league_perturb_factor"),
    (dict(league_dir="/tmp/x", league_population=2,
          league_resample_prob=1.5), "league_resample_prob"),
    (dict(league_dir="/tmp/x", league_population=2,
          league_fitness_window=0), "league_fitness_window"),
    (dict(league_dir="/tmp/x", league_population=2,
          league_exploit_interval_s=0.0), "league_exploit_interval_s"),
    (dict(league_dir="/tmp/x", league_member_id=0,
          results_dir="/tmp/elsewhere"), "results_dir"),
])
def test_malformed_league_specs_raise_reasoned_errors(fields, needle):
    with pytest.raises(ValueError, match="docs/LEAGUE.md"):
        try:
            check_league_config(Config(**fields))
        except ValueError as e:
            assert needle in str(e)
            raise


# -------------------------------------------------- 2. seeded exploit planning
def test_seeded_exploit_plans_and_perturbs_are_deterministic():
    genomes = {i: genome_from_config(Config()) for i in range(4)}
    gens = {i: 0 for i in range(4)}

    def plans(seed):
        return exploit_mod.plan_exploits(
            [0], [3], genomes, gens, np.random.default_rng(seed),
            perturb_factor=1.2, resample_prob=0.1)

    a, b = plans(7), plans(7)
    assert a == b  # ExploitPlan is frozen; equality covers the genome too
    assert a[0].loser == 3 and a[0].winner == 0 and a[0].generation == 1
    c = plans(8)
    assert c[0].genome != a[0].genome  # a different seed explores elsewhere


def test_perturb_always_moves_continuous_genes():
    g = genome_from_config(Config())
    for seed in range(20):
        p = perturb_genome(g, np.random.default_rng(seed), 1.2)
        assert p.learning_rate != g.learning_rate
        assert p != g


def test_explore_perturbs_the_winners_genome_not_the_losers():
    winner = Genome(learning_rate=1e-3, n_step=3, priority_exponent=0.5,
                    replay_ratio=1)
    loser = Genome(learning_rate=9e-5, n_step=9, priority_exponent=0.9,
                   replay_ratio=1)
    plan = exploit_mod.plan_exploits(
        [0], [1], {0: winner, 1: loser}, {0: 0, 1: 0},
        np.random.default_rng(0), perturb_factor=1.2,
        resample_prob=0.0)[0]
    # the child genome is one perturbation step around the WINNER's lr —
    # nowhere near the loser's
    assert 1e-3 / 1.3 < plan.genome.learning_rate < 1e-3 * 1.3


# ---------------------------------------------- 3. bit-exact copy via mailbox
def test_weight_copy_is_bit_exact_across_the_chain(tmp_path):
    from rainbow_iqn_apex_tpu.utils.quantize import tree_digest

    d = str(tmp_path)
    out = WeightMailbox(exploit_mod.outbox_path(d, 1), base_interval=3)
    params = _params(1)
    for v in range(1, 6):  # base + deltas + a second base
        params = {"a": {"w": params["a"]["w"] * 1.01 + 0.003},
                  "b": params["b"] - 0.001}
        out.publish_params(params, v)
    published = WeightMailbox(exploit_mod.outbox_path(d, 1)).read_params()
    want = tree_digest(published)

    plan = exploit_mod.ExploitPlan(
        loser=0, winner=1, generation=1,
        genome=perturb_genome(genome_from_config(Config()),
                              np.random.default_rng(0), 1.2))
    copied, digest = exploit_mod.copy_weights(d, plan)
    assert digest == want  # controller reconstruction == winner publication

    # loser half: a FRESH decoder replays the copied chain bit-exactly
    adopted = WeightMailbox(exploit_mod.inbox_path(d, 0)).read_params()
    assert tree_digest(adopted) == want
    np.testing.assert_array_equal(adopted["a"]["w"], published["a"]["w"])
    np.testing.assert_array_equal(adopted["b"], published["b"])


def test_generation_counter_is_monotone(tmp_path):
    d = str(tmp_path)
    WeightMailbox(exploit_mod.outbox_path(d, 1)).publish_params(_params(), 1)
    genome = genome_from_config(Config())
    plan = exploit_mod.ExploitPlan(loser=0, winner=1, generation=1,
                                   genome=genome)
    exploit_mod.copy_weights(d, plan)
    with pytest.raises(RuntimeError, match="monotone"):
        exploit_mod.copy_weights(d, plan)  # duplicate generation refused
    # a HIGHER generation goes through
    exploit_mod.copy_weights(
        d, exploit_mod.ExploitPlan(loser=0, winner=1, generation=2,
                                   genome=genome))


def test_copy_from_unpublished_winner_is_skipped_with_reason(tmp_path):
    plan = exploit_mod.ExploitPlan(
        loser=0, winner=1, generation=1,
        genome=genome_from_config(Config()))
    with pytest.raises(RuntimeError, match="has no readable outbox"):
        exploit_mod.copy_weights(str(tmp_path), plan)


# ------------------------------------------------ 4. fitness ordering & window
def test_fitness_ordering_tolerates_missing_and_nan_evals():
    ft = FitnessTracker(3)
    ft.note_row(0, {"kind": "eval", "score_mean": 3.0,
                    "human_normalized": 0.8})
    ft.note_row(0, {"kind": "eval", "score_mean": 3.0,
                    "human_normalized": 0.6})
    ft.note_row(1, {"kind": "eval", "score_mean": float("nan")})  # skipped
    ft.note_row(1, {"kind": "eval_mt", "hn_median": 0.3, "hn_mean": 0.4})
    ft.note_row(2, {"kind": "eval", "score_mean": None})  # skipped
    ft.note_row(3, {"kind": "learn", "loss": 0.1})  # wrong kind: ignored
    assert ft.fitness(0) == pytest.approx(0.7)
    assert ft.fitness(1) == pytest.approx(0.3)
    assert ft.fitness(2) is None and ft.fitness(3) is None
    assert ft.rows_skipped == 2
    ranked = rank_members(ft, [0, 1, 2, 3])
    assert [m for m, _f in ranked] == [0, 1]  # unmeasured members excluded
    top, bottom = quantile_split(ranked, 0.5, 0.5)
    assert top == [0] and bottom == [1]


def test_fitness_window_slides_and_baseline_less_games_rank_raw():
    ft = FitnessTracker(2)
    for v in (0.1, 0.2, 0.9):  # window 2: the 0.1 falls out
        ft.note_row(0, {"kind": "eval", "score_mean": v})  # no baseline key
    assert ft.fitness(0) == pytest.approx(0.55)


def test_quantile_split_needs_two_scored_members():
    ft = FitnessTracker(2)
    ft.note_score(0, 1.0)
    assert quantile_split(rank_members(ft, [0, 1, 2]), 0.5, 0.5) == ([], [])


def test_rank_ties_break_toward_lower_member_id():
    ft = FitnessTracker(2)
    ft.note_score(2, 1.0)
    ft.note_score(1, 1.0)
    assert [m for m, _f in rank_members(ft, [1, 2])] == [1, 2]


# ------------------------------------- 5. respawn keeps id+generation; counters
class FakeProc:
    def __init__(self):
        self.rc = None

    def poll(self):
        return self.rc

    def kill(self):
        self.rc = -9


def _controller(tmp_path, clock, n=3, **over):
    cfg = Config(league_dir=str(tmp_path), league_population=n,
                 league_fitness_window=2, league_exploit_interval_s=1e9,
                 league_bottom_quantile=0.34, league_top_quantile=0.34,
                 league_resample_prob=0.0, **over)
    procs = {}

    def spawn(member, epoch):
        p = FakeProc()
        procs[(member, epoch)] = p
        return p

    from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry

    registry = MetricRegistry()
    ctl = LeagueController(cfg, spawn, registry=registry,
                           clock=lambda: clock[0])
    return ctl, procs, registry


def test_dead_member_respawns_same_id_and_keeps_generation(tmp_path):
    clock = [0.0]
    ctl, procs, registry = _controller(tmp_path, clock)
    # bump member 1 to generation 3 on disk (as an adoption would)
    g, _gen = load_genome(genome_path(str(tmp_path), 1))
    save_genome(genome_path(str(tmp_path), 1), g, 3, 1)
    procs[(1, 0)].rc = 1  # member 1 dies
    ctl.poll(step=1)
    clock[0] += 100.0  # past the respawn backoff
    ctl.poll(step=2)
    assert (1, 1) in procs, "respawned the SAME member id at epoch+1"
    assert load_genome(genome_path(str(tmp_path), 1))[1] == 3, \
        "generation survives member death"
    stats = ctl.sup.stats("member_m1")
    assert stats["restarts"] == 1 and stats["exits"] == 1
    assert registry.counter("role_restarts", "member_m1").get() == 1
    row = ctl.status_row(step=2)
    assert row["members"]["1"]["restarts"] == 1
    assert row["members"]["1"]["generation"] == 3


def test_crash_looping_member_is_evicted_after_budget(tmp_path):
    clock = [0.0]
    ctl, procs, registry = _controller(tmp_path, clock)
    attempts = Config().respawn_attempts
    for _ in range(attempts + 1):
        epoch = ctl.sup.epoch("member_m2")
        procs[(2, epoch)].rc = 1
        ctl.poll(step=1)
        clock[0] += 1000.0
        ctl.poll(step=2)
    assert ctl.sup.state("member_m2") == "evicted"
    assert ctl.members[2].evicted
    assert registry.counter("role_evictions", "member_m2").get() == 1
    assert 2 not in ctl.alive_members()
    # an evicted member's stale scores stop shaping the quantiles
    assert ctl.fitness.fitness(2) is None


def test_collapsed_population_is_reported(tmp_path):
    clock = [0.0]
    ctl, procs, _reg = _controller(tmp_path, clock, n=2)
    attempts = Config().respawn_attempts
    for _ in range(attempts + 1):
        epoch = ctl.sup.epoch("member_m1")
        procs[(1, epoch)].rc = 1
        ctl.poll(step=1)
        clock[0] += 1000.0
        ctl.poll(step=2)
    assert ctl.collapsed()
    row = ctl.status_row(step=3)
    assert row["collapsed"] is True


def test_exploit_skip_when_winner_never_published(tmp_path):
    clock = [0.0]
    ctl, _procs, _reg = _controller(tmp_path, clock)
    ctl.fitness.note_score(0, 1.0)
    ctl.fitness.note_score(1, 0.5)
    ctl.fitness.note_score(2, 0.1)
    done = ctl.force_sweep(step=1)
    assert done == [] and ctl.exploit_skips == 1  # no outbox yet: skipped


# --------------------------------------------------- 6+7. trainer integration
def _member_cfg(tmp_path, member_id, **over):
    d = str(tmp_path)
    return Config(
        run_id=f"m{member_id}", seed=11,
        results_dir=os.path.join(d, f"m{member_id}", "results"),
        checkpoint_dir=os.path.join(d, f"m{member_id}", "ckpt"),
        league_dir=d, league_member_id=member_id, **{**TOY, **over})


def test_default_off_is_bitwise_and_member_noop_matches(tmp_path):
    """(a) League fields at defaults construct NO league member.  (b) A
    league member whose genome equals the config — and who never receives
    a directive — trains to byte-identical final weights vs the plain
    loop: the wiring (outbox publishes, directive polls) perturbs no RNG
    stream and no numerics."""
    import jax

    from rainbow_iqn_apex_tpu.league.member import LeagueMember
    from rainbow_iqn_apex_tpu.train import train
    from rainbow_iqn_apex_tpu.utils.quantize import tree_digest
    from rainbow_iqn_apex_tpu.utils.checkpoint import Checkpointer

    assert LeagueMember.from_config(Config()) is None
    cfg_base = Config(**TOY)
    assert overlay_config(
        cfg_base, genome_from_config(cfg_base)) is cfg_base

    # writeback_depth=0 makes every drain a no-op, so the member loop's
    # extra drain boundaries (outbox-publish cadence) change nothing and
    # the two runs are step-for-step comparable; at depth K > 0 the member
    # run drains priorities K steps earlier at publish boundaries BY
    # DESIGN (never publish unverified params), which legitimately
    # reshapes the sampling stream.  prefetch_depth=0 for the same reason:
    # the prefetch thread samples ahead of the main thread's appends, so
    # what a batch sees depends on thread timing (the test failed 1 run in 6
    # on a loaded box)
    d = str(tmp_path)
    plain = Config(run_id="plain", seed=11,
                   results_dir=os.path.join(d, "plain", "results"),
                   checkpoint_dir=os.path.join(d, "plain", "ckpt"),
                   **{**TOY, "checkpoint_interval": 200,
                      "writeback_depth": 0, "prefetch_depth": 0})
    train(plain)
    member = _member_cfg(tmp_path, 0, checkpoint_interval=200,
                         writeback_depth=0, prefetch_depth=0)
    train(member)

    def final_params(cfg):
        ckpt = Checkpointer(os.path.join(cfg.checkpoint_dir, cfg.run_id))
        from rainbow_iqn_apex_tpu.ops.learn import init_train_state
        from rainbow_iqn_apex_tpu.envs import make_vector_env

        env = make_vector_env(cfg.env_id, 1, seed=0)
        template = init_train_state(
            cfg, env.num_actions, jax.random.PRNGKey(0),
            state_shape=(*env.frame_shape, cfg.history_length))
        state, _extra = ckpt.restore(template)
        return state.params

    assert tree_digest(final_params(plain)) == tree_digest(
        final_params(member))
    # and the member run DID exercise the league surface
    rows = [json.loads(line) for line in open(os.path.join(
        str(tmp_path), "m0", "results", "m0", "metrics.jsonl"))]
    assert any(r.get("kind") == "league" for r in rows)
    assert WeightMailbox(
        exploit_mod.outbox_path(str(tmp_path), 0)).version() >= 1


def test_midrun_adoption_swaps_weights_and_retunes_live(tmp_path):
    """A directive planted before the run: the member adopts at its first
    drain boundary — weights digest-identical to the copied chain, lr and
    n-step live-retuned, genome + generation persisted for respawn."""
    import jax

    from rainbow_iqn_apex_tpu.envs import make_vector_env
    from rainbow_iqn_apex_tpu.ops.learn import init_train_state
    from rainbow_iqn_apex_tpu.train import train

    d = str(tmp_path)
    cfg = _member_cfg(tmp_path, 0, t_max=768)
    env = make_vector_env("toy:catch", 1, seed=0)
    winner = init_train_state(
        cfg, env.num_actions, jax.random.PRNGKey(99),
        state_shape=(*env.frame_shape, cfg.history_length))
    WeightMailbox(exploit_mod.outbox_path(d, 1)).publish_params(
        jax.tree.map(np.asarray, winner.params), 1)
    new_genome = Genome(learning_rate=2e-3, n_step=5,
                        priority_exponent=0.6, replay_ratio=1)
    plan = exploit_mod.ExploitPlan(loser=0, winner=1, generation=1,
                                   genome=new_genome)
    _p, digest = exploit_mod.copy_weights(d, plan)
    exploit_mod.write_directive(d, plan, digest, step=0)

    train(cfg)
    rows = [json.loads(line) for line in open(os.path.join(
        d, "m0", "results", "m0", "metrics.jsonl"))]
    adopts = [r for r in rows
              if r.get("kind") == "league" and r.get("event") == "adopt"]
    assert len(adopts) == 1, "exactly one adoption per generation"
    assert adopts[0]["digest"] == digest
    assert adopts[0]["genome"]["n_step"] == 5
    g, gen = load_genome(genome_path(d, 0))
    assert gen == 1 and g == new_genome
    # the run kept training after the swap (learn rows beyond the adopt)
    assert any(r.get("kind") == "learn"
               and r.get("step", 0) > adopts[0]["step"] for r in rows)


def test_set_n_step_refence_matches_fresh_build():
    """`PrioritizedReplay.set_n_step` must reproduce EXACTLY the
    eligibility a buffer built at the new n computes from scratch —
    including the truncation-window fence and the cursor dead zones."""
    from rainbow_iqn_apex_tpu.replay.buffer import PrioritizedReplay

    def build(n, use_native):
        buf = PrioritizedReplay(64, (4, 4), history=2, n_step=n, gamma=0.9,
                                lanes=2, seed=0, use_native=use_native)
        rng = np.random.default_rng(1)
        for t in range(20):
            buf.append_batch(
                rng.integers(0, 255, (2, 4, 4)).astype(np.uint8),
                rng.integers(0, 4, 2),
                rng.normal(size=2).astype(np.float32),
                np.zeros(2, bool),
                truncations=np.array([t == 9, False]))
        return buf

    for native in (False, True):
        for n_new in (5, 2):
            buf = build(3, native)
            buf.set_n_step(n_new)
            got = buf.tree.get(np.arange(64)) > 0
            ref = build(n_new, native).tree.get(np.arange(64)) > 0
            np.testing.assert_array_equal(got, ref)
            batch = buf.sample(16, 0.5)
            assert np.isfinite(batch.reward).all()
    with pytest.raises(ValueError, match="too small"):
        build(3, False).set_n_step(40)


def test_set_priority_exponent_applies_to_future_writebacks():
    from rainbow_iqn_apex_tpu.replay.buffer import PrioritizedReplay

    buf = PrioritizedReplay(32, (4, 4), history=1, n_step=1, gamma=0.9,
                            lanes=1, seed=0, use_native=False)
    for _ in range(8):
        buf.append_batch(np.zeros((1, 4, 4), np.uint8), np.zeros(1, int),
                         np.zeros(1, np.float32), np.zeros(1, bool))
    buf.set_priority_exponent(1.0)
    buf.update_priorities(np.array([2]), np.array([3.0]))
    got = buf.tree.get(np.array([2]))[0]
    assert got == pytest.approx((3.0 + buf.eps) ** 1.0)


def test_league_rows_validate_and_fold_into_health_and_report():
    """The `league` schema kind parses/validates, RunHealth degrades on a
    collapsed population and a refused adoption (NOT on a clean exploit),
    and obs_report + health_attribution fold the rows."""
    from rainbow_iqn_apex_tpu.obs.health import RunHealth
    from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry
    from rainbow_iqn_apex_tpu.obs.schema import validate_row

    def envelope(**f):
        return {"t": 0.0, "ts": 0.0, "host": 0, "run": "r", "schema": 1,
                "kind": "league", **f}

    assert validate_row(envelope(event="exploit", member=1)) == []
    assert validate_row(envelope(member=1)) != []  # event is required

    registry = MetricRegistry()
    health = RunHealth(registry)
    health.observe_row(envelope(event="exploit"))
    health.observe_row(envelope(event="adopt"))
    assert health.status() == "ok"  # normal PBT operation never degrades
    health.observe_row(envelope(event="adopt_refused",
                                reason="digest_mismatch"))
    assert health.status() == "degraded"
    health.tick(1)
    health.observe_row(envelope(event="status", alive=1, collapsed=True,
                                members={}))
    assert health.status() == "degraded"
    assert registry.gauge("league_members_alive", "health").get() == 1

    # obs_report league: section off the same rows
    import scripts.obs_report as obs_report

    rows = [
        envelope(event="exploit", member=1, source=0, generation=1,
                 digest="d", step=5),
        envelope(event="adopt", member=1, generation=1, digest="d", step=6),
        envelope(event="status", step=7, alive=2, collapsed=False,
                 exploit_events=1, exploit_skips=0,
                 members={"0": {"fitness": 0.5, "generation": 0,
                                "exploits": 0, "restarts": 0,
                                "state": "running"},
                          "1": {"fitness": 0.1, "generation": 1,
                                "exploits": 1, "restarts": 0,
                                "state": "running",
                                "last_copy_source": 0}}),
    ]
    report = obs_report.aggregate(rows)
    lg = report["league"]
    assert lg["exploits"] == 1 and lg["adoptions"] == 1
    assert lg["members"]["1"]["last_copy_source"] == 0
    rendered = obs_report.render(report)
    assert "league:" in rendered and "member m1" in rendered


def test_health_attribution_tallies_league_rows(tmp_path):
    from rainbow_iqn_apex_tpu.obs.attribution import health_attribution

    path = tmp_path / "metrics.jsonl"
    rows = [
        {"kind": "health", "status": "ok"},
        {"kind": "league", "event": "exploit"},
        {"kind": "league", "event": "adopt"},
        {"kind": "league", "event": "status", "alive": 2,
         "collapsed": False},
    ]
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    out = health_attribution(str(path))
    assert out["league"] == {"rows": 3, "exploits": 1, "adoptions": 1,
                             "refused": 0, "alive": 2, "collapsed": False}


def test_fixed_schedule_shares_gene_parses_and_renormalizes():
    """The genome's multitask-schedule-shares gene: "fixed:w1,...,wG"
    yields explicit per-game batch shares, dead games renormalise over
    survivors, malformed specs raise reasoned errors, and perturbation
    jitters the shares (still summing to 1)."""
    from rainbow_iqn_apex_tpu.multitask.replay import InterleaveSchedule

    sched = InterleaveSchedule("fixed:0.7,0.3", 2)
    np.testing.assert_allclose(sched.shares(np.array([1.0, 1.0])),
                               [0.7, 0.3])
    np.testing.assert_allclose(sched.shares(np.array([0.0, 1.0])),
                               [0.0, 1.0])  # dead game: survivors take all
    for bad in ("fixed:0.7", "fixed:a,b", "fixed:0,0", "fixed:nan,1",
                "fixed:inf,0.5"):
        with pytest.raises(ValueError, match="multitask_schedule"):
            InterleaveSchedule(bad, 2)
    g = Genome(learning_rate=1e-3, n_step=3, priority_exponent=0.5,
               replay_ratio=1, multitask_schedule="fixed:0.7,0.3")
    p = perturb_genome(g, np.random.default_rng(0), 1.2)
    assert p.multitask_schedule.startswith("fixed:")
    shares = [float(s) for s in p.multitask_schedule[6:].split(",")]
    assert abs(sum(shares) - 1.0) < 1e-6
    assert p.multitask_schedule != g.multitask_schedule


# ------------------------------------------- 8. review-hardening regressions
def test_clean_member_completion_is_done_not_crash(tmp_path):
    """A member that exits rc=0 (t_max reached) is terminal SUCCESS: no
    strike, no retrain-from-scratch respawn, no eviction, no collapse —
    and it is excluded from the loser side of later sweeps (it can never
    adopt a directive) while its health row never degrades the run."""
    clock = [0.0]
    ctl, procs, _reg = _controller(tmp_path, clock)
    procs[(1, 0)].rc = 0  # member 1 COMPLETES
    events = ctl.poll(step=1)
    assert [e["event"] for e in events] == ["actor_done"]
    assert ctl.sup.state("member_m1") == "done"
    clock[0] += 1000.0
    ctl.poll(step=2)
    assert (1, 1) not in procs, "a completed member is never respawned"
    assert ctl.sup.budget.failures("member_m1") == 0
    assert 1 in ctl.alive_members() and not ctl.collapsed()
    # done member ranked WORST -> would be the truncation loser, but a
    # member that cannot adopt must not soak up the exploit slot
    ctl.fitness.note_score(0, 1.0)
    ctl.fitness.note_score(2, 0.5)
    ctl.fitness.note_score(1, -1.0)
    done = ctl.force_sweep(step=3)
    assert done == [] and ctl.exploit_events == 0

    from rainbow_iqn_apex_tpu.obs.health import RunHealth
    from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry

    health = RunHealth(MetricRegistry())
    health.observe_row({"t": 0.0, "ts": 0.0, "host": 0, "run": "r",
                        "schema": 1, "kind": "fault", "event": "actor_done",
                        "role": "member_m1", "rc": 0})
    assert health.status() == "ok", "clean completion is not degradation"
    assert health.fault_counts["actor_done"] == 1


def test_genome_n_step_clamps_to_replay_geometry(tmp_path):
    """The explore prior reaches n=10 blind to any member's ring geometry
    (seg > history + n): the buffer exposes its bound, loop start clamps
    the persisted genome, and try_adopt clamps a directive's genome —
    without either, one unlucky in-prior draw crash-loops the member into
    eviction at every respawn."""
    import dataclasses

    from rainbow_iqn_apex_tpu.league.member import LeagueMember
    from rainbow_iqn_apex_tpu.replay.buffer import PrioritizedReplay

    mem = PrioritizedReplay(64, (4, 4), history=2, n_step=3, lanes=8)
    assert mem.max_n_step == 64 // 8 - 2 - 1  # seg - history - 1
    mem.set_n_step(mem.max_n_step)  # the bound itself is feasible
    with pytest.raises(ValueError, match="too small"):
        mem.set_n_step(mem.max_n_step + 1)

    d = str(tmp_path)
    cfg = _member_cfg(tmp_path, 0)
    big = dataclasses.replace(genome_from_config(cfg), n_step=10)

    # loop-start clamp: an infeasible PERSISTED genome (controller seed /
    # pre-fix adoption) is clamped and re-persisted before overlay
    save_genome(genome_path(d, 0), big, 0, 0)
    member = LeagueMember.from_config(cfg)
    member.clamp_n_step(4)
    assert member.genome.n_step == 4
    assert load_genome(genome_path(d, 0))[0].n_step == 4
    assert member.overlay(cfg).multi_step == 4

    # adoption clamp: a directive carrying n=10 lands with a feasible n
    WeightMailbox(exploit_mod.outbox_path(d, 1)).publish_params(
        _params(1), 1)
    plan = exploit_mod.ExploitPlan(loser=0, winner=1, generation=1,
                                   genome=big)
    _p, digest = exploit_mod.copy_weights(d, plan)
    exploit_mod.write_directive(d, plan, digest, step=0)
    seen = []
    adopted = member.try_adopt(
        0, lambda p: seen.append("weights"),
        retune=lambda g: seen.append(g.n_step), max_n_step=4)
    assert adopted is not None and seen == ["weights", 4]
    assert member.genome.n_step == 4
    assert load_genome(genome_path(d, 0))[0].n_step == 4


def test_crash_before_adopting_does_not_wedge_future_exploits(tmp_path):
    """A loser that crashes with a directive pending regresses the
    controller's in-memory generation on respawn (the handler re-reads a
    genome file the member never updated); once the respawned member
    adopts and persists the new generation, the NEXT sweep must plan past
    it — without the sweep-time disk refresh, the controller would plan
    the same generation forever and the inbox's monotone check would
    refuse every future exploit for that member."""
    clock = [0.0]
    ctl, procs, _reg = _controller(tmp_path, clock)
    d = str(tmp_path)
    WeightMailbox(exploit_mod.outbox_path(d, 0)).publish_params(
        _params(7), 1)
    ctl.fitness.note_score(0, 1.0)
    ctl.fitness.note_score(1, 0.5)
    ctl.fitness.note_score(2, -1.0)
    done = ctl.force_sweep(step=1)
    assert len(done) == 1 and done[0]["generation"] == 1
    assert ctl.members[2].generation == 1

    # member 2 dies BEFORE adopting; respawn re-reads disk (still gen 0)
    procs[(2, 0)].rc = 1
    ctl.poll(step=2)
    clock[0] += 1000.0
    ctl.poll(step=3)
    assert ctl.members[2].generation == 0  # the stale regression

    # the respawned incarnation adopts the pending directive (member-side
    # write: genome + generation persisted)
    directive = exploit_mod.read_directive(d, 2)
    save_genome(genome_path(d, 2),
                Genome.from_dict(directive["genome"]), 1, 2)

    ctl.fitness.note_score(2, -1.0)
    done = ctl.force_sweep(step=4)
    assert len(done) == 1 and done[0]["generation"] == 2, \
        "sweep refreshed from disk and planned PAST the adopted generation"
    assert ctl.exploit_skips == 0
    assert ctl.members[2].generation == 2


def test_sweep_reconciles_clamped_genome_at_same_generation(tmp_path):
    """An adoption-time n-step clamp persists a DIFFERENT genome at the
    SAME generation the sweep already recorded (member.py try_adopt); a
    strictly generation-forward refresh would skip it, leaving the
    controller reporting — and, once the clamped member wins, perturbing
    and re-issuing directives from — an n_step the member never runs."""
    import dataclasses

    clock = [0.0]
    ctl, _procs, _reg = _controller(tmp_path, clock)
    d = str(tmp_path)
    WeightMailbox(exploit_mod.outbox_path(d, 0)).publish_params(
        _params(7), 1)
    ctl.fitness.note_score(0, 1.0)
    ctl.fitness.note_score(1, 0.5)
    ctl.fitness.note_score(2, -1.0)
    done = ctl.force_sweep(step=1)
    assert len(done) == 1 and ctl.members[2].generation == 1
    planned_n = ctl.members[2].genome.n_step

    # member 2 adopts, but its ring geometry clamps the directive's
    # n_step to 1 and persists the FEASIBLE genome at the same generation
    directive = exploit_mod.read_directive(d, 2)
    adopted = dataclasses.replace(
        Genome.from_dict(directive["genome"]), n_step=1)
    assert adopted.n_step != planned_n
    save_genome(genome_path(d, 2), adopted, 1, 2)

    # next sweep: member 2 is now the WINNER (its record is not replanned)
    WeightMailbox(exploit_mod.outbox_path(d, 2)).publish_params(
        _params(8), 1)
    ctl.fitness.note_score(0, -1.0)
    ctl.fitness.note_score(0, -1.0)
    ctl.fitness.note_score(2, 2.0)
    ctl.fitness.note_score(2, 2.0)
    done = ctl.force_sweep(step=2)
    assert ctl.members[2].genome == adopted, \
        "equal-generation disk genome (the clamp) reconciled into the sweep"
    assert ctl.status_row(step=3)["members"]["2"]["n_step"] == 1
    assert len(done) == 1 and done[0]["source"] == 2
    # the loser's fresh directive explores around the FEASIBLE genome, not
    # the infeasible planned one
    issued = Genome.from_dict(
        exploit_mod.read_directive(d, done[0]["member"])["genome"])
    assert issued.n_step <= 2, \
        f"explored around clamped n=1, got n={issued.n_step}"
