"""The cell `kimi-linear-r2d2-fused` at sizes a test can hold: the float32
program passes the cell's own limits, the control (the reference with fp8
matmuls, put in the program's place) does not; the harness runs the cell end
to end; the driver runs the trainer's own program; the FLOP count against a
hand count; the reference's two copies are one text."""

import io
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmarks import check, flops_kimi_core, harness
from benchmarks.drivers.fused_r2d2_core import Driver
from benchmarks.tests import tiny

CELL = "kimi-linear-r2d2-fused"
TINY_CORE = os.path.join(harness.ROOT, "tests", "fixtures",
                         "kimi_core_tiny.json")  # the tier-1 tests' own


def tiny_fields() -> dict:
    f = tiny.load("configs", "kimi-linear-r2d2-1chip")["fields"]
    f.update(compute_dtype="float32", hidden_size=32, core_config=TINY_CORE,
             r2d2_burn_in=4, r2d2_seq_len=8, r2d2_overlap=4, batch_size=4,
             multi_step=2, learn_start=12 * 64, memory_capacity=12 * 64)
    return f


def tiny_driver(seed, **kw):
    return Driver(tiny_fields(), tiny.traffic("freeway-16lanes"), seed, 1, **kw)


def test_program_passes_and_control_fails():
    cell = tiny.load("workloads", CELL)
    limits, read_only = cell["limits"], cell.get("read_not_compared", ())
    exact = {"window_steps_missing": 0.0, "first_steps_missing": 0.0}
    drv = tiny_driver(5)
    drv.warm_up()
    assert drv.counters["moe_tokens_dropped"] == 0.0
    # the seeded selection bias deals the held experts their even share
    assert drv.counters["moe_held_assign_share"] == pytest.approx(4 / 16)
    prog = drv.program_side()
    ref = drv.reference_side(None, prog["priority_after"] != drv.priority0())
    sound, rows = check.verdict(
        {**check.compare(prog, ref, drv.params0), **exact}, limits, read_only)
    assert sound, rows
    # the control, and the fault of half the batch left out: each is failed
    # by the first gradient's angle (the fault also by the draw's write-back)
    for mode in ("fp8", "half"):
        numbers = check.compare(
            drv.reference_side(mode, None), ref, drv.params0)
        ok, rows = check.verdict({**numbers, **exact}, limits, read_only)
        assert not ok, rows
        assert numbers["grad1_median_angle"] > limits["grad1_median_angle"]


def test_the_seeded_selection_bias_fixes_the_choice_and_the_held_share():
    """Every seed does the same work: the bias outranks any score, each layer
    has top_k experts chosen, and of all chosen the even share are held."""
    from benchmarks import weights_core

    struct = jax.ShapeDtypeStruct

    def layer(experts, held):
        return {"moe": {"router": {"kernel": struct((6, experts), np.float32),
                                   "select_bias": struct((experts,), np.float32)},
                        "experts": {"gate": struct((held, 6, 5), np.float32)}}}

    shapes = {"core": {f"layer_{i}": layer(256, 8) for i in (2, 3, 4, 5, 10)}}
    shapes["core"]["layer_1"] = {"ffn": {"kernel": struct((6, 5), np.float32)}}
    first = 16
    for seed in (0, 2**31 + 5):
        core = weights_core.make_params(
            shapes, jax.random.PRNGKey(seed), 0.5, top_k=8,
            first_expert=first)["core"]
        held_chosen = []
        for name in ("layer_2", "layer_3", "layer_4", "layer_5", "layer_10"):
            bias = np.asarray(core[name]["moe"]["router"]["select_bias"])
            assert sorted(set(bias)) == [0.0, weights_core.SELECT]
            assert (bias > 0).sum() == 8
            held_chosen.append(int((bias[first:first + 8] > 0).sum()))
        # 5 layers x 8 slots x 8/256 = 1.25 slots: one, in the first expert layer
        assert held_chosen == [1, 0, 0, 0, 0]
    assert weights_core.SELECT > 1.0  # sigmoid scores lie in (0, 1)
    a, b = (np.asarray(weights_core.make_params(
        shapes, jax.random.PRNGKey(s), 0.5, top_k=8)["core"]["layer_3"]["moe"][
            "router"]["select_bias"]) for s in (1, 2))
    assert np.any(a != b)  # which experts: from the seed


def test_the_target_is_the_online_net_with_its_value_bias_ahead():
    """Every seed's first TD errors have the same mean: the target differs
    from the online net in the value head's bias alone, by TARGET_AHEAD."""
    from benchmarks.drivers.fused_r2d2_core import TARGET_AHEAD

    ts = tiny_driver(2**31 + 7).carry[0]
    online = dict(jax.tree_util.tree_leaves_with_path(ts.params))
    differ = {jax.tree_util.keystr(path): np.asarray(leaf) - np.asarray(online[path])
              for path, leaf in jax.tree_util.tree_leaves_with_path(
                  ts.target_params)
              if np.any(np.asarray(leaf) != np.asarray(online[path]))}
    assert list(differ) == ["['value_out']['b_mu']"]
    np.testing.assert_allclose(differ["['value_out']['b_mu']"], TARGET_AHEAD,
                               rtol=1e-6)


def test_a_step_that_leaves_its_state_unchanged_is_not_correct():
    """The harness's whole run over the timed path broken underneath."""
    from benchmarks.tests.test_correct import _state_unchanged

    broken = _state_unchanged(Driver)
    out = io.StringIO()
    rc = harness.run(CELL, 2**31 + 5, 0.5, False, t0=time.perf_counter(),
                     devices=jax.devices()[:1],
                     make_driver=lambda _f, _t, seed, chips, **kw: broken(
                         tiny_fields(), tiny.traffic("freeway-16lanes"), seed,
                         1, **kw), out=out)
    assert rc == 0
    assert json.loads(out.getvalue().splitlines()[-1])["correct"] is False


def test_large_leaves_are_compared_on_a_fixed_subset_of_their_elements():
    from benchmarks.drivers.fused_r2d2_core import THIN_OVER, THIN_STRIDE, thin

    big = np.arange(2 * THIN_OVER, dtype=np.float32).reshape(2, -1)
    tree = thin({"big": big, "small": np.ones((4, 4)), "dev": jax.numpy.asarray(big)})
    assert tree["small"].shape == (4, 4)
    assert tree["big"].base is None and tree["big"].size == big.size // THIN_STRIDE
    np.testing.assert_array_equal(tree["big"], big.reshape(-1)[::THIN_STRIDE])
    np.testing.assert_array_equal(np.asarray(tree["dev"]), tree["big"])


def test_the_harness_runs_the_cell():
    out = io.StringIO()
    rc = harness.run(CELL, 2**31 + 3, 0.5, False, t0=time.perf_counter(),
                     devices=jax.devices()[:1],
                     make_driver=lambda _f, _t, seed, chips, **kw:
                     tiny_driver(seed, **kw), out=out)
    assert rc == 0
    result = json.loads(out.getvalue().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0


def test_driver_is_the_trainers_program(tmp_path, monkeypatch):
    """As benchmarks/tests/test_same_program.py, with `core_config` set."""
    from rainbow_iqn_apex_tpu import train_anakin_r2d2
    from rainbow_iqn_apex_tpu.replay import device_sequence

    drv = tiny_driver(2**31 + 11)
    ts0, ss0 = jax.tree.map(np.asarray, drv.carry[:2])
    steps, losses = 0, []
    for _ in range(6):
        steps, outs, _k = drv.dispatch()
        loss = np.asarray(outs[1])
        if np.any(np.isfinite(loss)):
            losses.append(float(np.nanmean(loss)))
    monkeypatch.setattr(train_anakin_r2d2, "init_r2d2_state",
                        lambda *a, **k: jax.tree.map(jax.numpy.asarray, ts0))
    monkeypatch.setattr(device_sequence.DeviceSequenceReplay, "init_state",
                        lambda self: jax.tree.map(jax.numpy.asarray, ss0))
    cfg = drv.cfg.replace(
        results_dir=str(tmp_path / "results"),
        checkpoint_dir=str(tmp_path / "checkpoints"),
        metrics_interval=1, eval_episodes=1, eval_interval=0,
        checkpoint_interval=0)
    summary = train_anakin_r2d2.train_anakin_r2d2(
        cfg, max_frames=6 * cfg.anakin_segment_ticks * cfg.num_envs_per_actor)
    rows = [json.loads(line) for line in
            open(tmp_path / "results" / cfg.run_id / "metrics.jsonl")]
    learn = [r for r in rows if r.get("kind") == "learn"]
    assert steps > 0 and summary["learn_steps"] == steps
    assert [r["loss"] for r in learn] == losses
    assert all(r["moe_tokens_dropped"] == 0.0 for r in learn)
    assert learn[-1]["core_state_bytes_per_lane"] > 0


def test_learn_flops_against_a_hand_count():
    cfg = tiny.load("configs", "kimi-linear-r2d2-1chip")
    cc = json.load(open(os.path.join(harness.ROOT, cfg["fields"]["core_config"])))
    kda = 2 * (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32)
    kda += 2 * 3 * 4096 * 4 + 8 * 32 * 128 * 128
    mla = 2 * (2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304)
    mla += 2 * 32 * (192 + 128) * 60.5
    dense = 6 * 2304 * 9216
    moe = 2 * 2304 * 256 + 6 * 2304 * 1024 * (1 + 8 * 8 / 256)
    token = 4 * kda + mla + dense + 4 * moe
    assert flops_kimi_core.core_token_flops(cc, 120) == pytest.approx(token)
    assert token == pytest.approx(595.2e6, rel=0.001)
    step = flops_kimi_core.learn_flops(cfg["fields"], cc, (80, 80), 3)
    # by hand as benchmarks/tests/test_flops.py: trunk 12,763,136 a frame
    # stack, its first layer 5,914,624; noisy dueling heads on 2,304 features
    trunk, conv1 = 12_763_136, 2 * 19 * 19 * 32 * 256
    heads = (2 * 4 * 2304 * 512) + 4 * 512 * 1 + 4 * 512 * 3
    body = trunk + token
    online = 40 * body + 80 * (3 * (body + heads) - conv1)
    target = 120 * body + 80 * heads
    assert step == pytest.approx(64 * (online + target))
    assert step == pytest.approx(15.73e12, rel=0.001)


def test_the_two_copies_of_the_reference_are_the_same_text():
    with open(os.path.join(harness.ROOT, "tests",
                           "reference_kimi_linear_core.py")) as a, open(
            os.path.join(harness.HERE, "references",
                         "kimi_linear_core.py")) as b:
        assert a.read() == b.read()
