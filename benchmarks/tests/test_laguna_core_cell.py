"""The cell `laguna-xs2-r2d2-fused` at sizes a test can hold (a full layer and
two sliding ones, sliding span 8 = the burn-in under a memory of 16 = the
sequence, 8 experts of which 4 are held and 2 a token): the float32 program
passes the cell's own limits; the control (the reference with fp8 matmuls, put
in the program's place) and this architecture's own, the reference with its
sliding layers' span ignored, do not; the harness runs the cell end to end;
the driver runs the trainer's own program, the game's tick cap included; the
FLOP count against a hand count; every new reader on a hand-made attribution,
and on a program without its scopes; the benchmark's entries.  The
shares-add-up test and the reference's two copies held to one text are
tier-1's (tests/test_laguna_core.py, tests/test_core_reference.py)."""

import io
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

from benchmarks import check, flops_laguna_core, harness
from benchmarks.drivers.fused_r2d2_laguna import Driver
from benchmarks.tests import tiny

CELL = "laguna-xs2-r2d2-fused"
CONFIG = "laguna-xs2-r2d2-1chip"
TRAFFIC = "freeway-16lanes-cap3414"
TINY_CORE = os.path.join(harness.ROOT, "tests", "fixtures",
                         "laguna_core_tiny.json")  # the tier-1 tests' own
DEVICE_TIMES = ("laguna_learn_device_ms", "laguna_sliding_attn_device_ms",
                "laguna_full_attn_device_ms", "laguna_attn_proj_device_ms",
                "laguna_moe_device_ms", "laguna_dense_ffn_device_ms",
                "laguna_optimizer_device_ms", "laguna_act_device_ms",
                "laguna_act_attn_device_ms")
COUNTERS = ("laguna_band_key_share", "laguna_sliding_live_key_share",
            "laguna_held_assign_share")
METRICS = DEVICE_TIMES + ("laguna_device_idle_share", "laguna_learn_mfu"
                          ) + COUNTERS


def tiny_fields() -> dict:
    f = tiny.load("configs", CONFIG)["fields"]
    f.update(compute_dtype="float32", hidden_size=32, core_config=TINY_CORE,
             r2d2_burn_in=8, r2d2_seq_len=8, r2d2_overlap=8, batch_size=4,
             multi_step=2, learn_start=16 * 64, memory_capacity=16 * 64)
    return f


def tiny_driver(seed, **kw):
    return Driver(tiny_fields(), tiny.traffic(TRAFFIC), seed, 1, **kw)


def test_program_passes_and_the_two_controls_fail():
    cell = tiny.load("workloads", CELL)
    limits, read_only = cell["limits"], cell.get("read_not_compared", ())
    exact = {"window_steps_missing": 0.0, "first_steps_missing": 0.0}
    drv = tiny_driver(5)
    assert drv.cfg.device_game_tick_cap == 3414
    drv.warm_up()
    assert drv.first_learning["steps"] == 1
    assert sorted(drv.counters) == sorted(drv.core.stat_names)
    assert drv.counters["moe_tokens_dropped"] == 0.0
    # the seeded selection bias deals the held experts their even share: of
    # 2 layers x 2 chosen, round(4 x 4/8) = 2 are held, one a layer
    assert drv.counters["moe_held_assign_share"] == pytest.approx(1 / 2)
    # burn-in = slice = the sliding span: every trained step of a sliding
    # layer sees a full span, half of the slots held, and one block of 8
    # queries computes 15 of the 16 (the oldest lies behind the first
    # query's band); a full layer its causal part of them
    assert drv.counters["attn_live_key_share_sliding"] == pytest.approx(0.5)
    assert drv.counters["attn_band_key_share"] == 15 / 16
    assert drv.counters["attn_live_key_share_full"] == pytest.approx(
        (64 + 36) / 128)
    prog = drv.program_side()
    ref = drv.reference_side(None, prog["priority_after"] != drv.priority0())
    sound, rows = check.verdict(
        {**check.compare(prog, ref, drv.params0), **exact}, limits, read_only)
    assert sound, rows
    # the precision control, and the control of this architecture's own: the
    # sliding layers attending over the whole sequence.  Each is failed by
    # the first gradient's angle
    for mode in ("fp8", "ignore_span"):
        numbers = check.compare(
            drv.reference_side(mode, None), ref, drv.params0)
        ok, rows = check.verdict({**numbers, **exact}, limits, read_only)
        assert not ok, (mode, rows)
        assert numbers["grad1_median_angle"] > limits["grad1_median_angle"]
    # the fault of the online network in the target's place shifts every
    # target value alike: the first loss shows it (printed, not compared, in
    # this cell: the workload file says why), and the parameters' change,
    # which is compared
    assert read_only == ["loss1_rel"]
    numbers = check.compare(
        drv.reference_side("online_target", None), ref, drv.params0)
    assert not check.verdict({**numbers, **exact}, limits, read_only)[0]
    assert numbers["loss1_rel"] > 0.2
    assert numbers["dparam_median_gap"] > limits["dparam_median_gap"]


def test_the_seeded_weights_fill_the_cores_leaves_as_they_stand():
    """`weights_core` goes by leaf name and reads the held count off the
    stacked kernels: kernels normal(0, 1/fan_in), the gates' among them,
    every norm's scale 1; each expert layer has 2 chosen experts of which one
    is held, and a shared expert."""
    core = tiny_driver(2**31 + 7).carry[0].params["core"]
    assert sorted(core) == ["final_norm", "in_proj", "layer_1", "layer_2",
                            "layer_3"]
    kernel = np.asarray(core["in_proj"]["kernel"])
    assert kernel.shape == (2304, 32)
    assert float(kernel.std()) == pytest.approx(1 / np.sqrt(2304), rel=0.05)
    for i, heads in ((1, 6), (2, 8), (3, 8)):
        gqa = core[f"layer_{i}"]["gqa"]
        assert sorted(gqa) == ["g_proj", "k_proj", "o_proj", "q_proj",
                               "v_proj"]
        assert gqa["g_proj"]["kernel"].shape == (32, heads)
        assert float(np.asarray(gqa["q_proj"]["kernel"]).std()
                     ) == pytest.approx(1 / np.sqrt(32), rel=0.1)
    for i in (2, 3):
        moe = core[f"layer_{i}"]["moe"]
        assert sorted(moe) == ["experts", "router", "shared"]
        bias = np.asarray(moe["router"]["select_bias"])
        assert sorted(bias) == [0.0] * 6 + [2.0] * 2
        assert int((bias[:4] > 0).sum()) == 1
        assert moe["experts"]["gate"].shape == (4, 32, 16)
    assert "ffn" in core["layer_1"] and "moe" not in core["layer_1"]


def test_a_step_that_leaves_its_state_unchanged_is_not_correct():
    """The harness's whole run over the timed path broken underneath."""
    from benchmarks.tests.test_correct import _state_unchanged

    broken = _state_unchanged(Driver)
    out = io.StringIO()
    rc = harness.run(CELL, 2**31 + 5, 0.5, False, t0=time.perf_counter(),
                     devices=jax.devices()[:1],
                     make_driver=lambda _f, _t, seed, chips, **kw: broken(
                         tiny_fields(), tiny.traffic(TRAFFIC), seed, 1, **kw),
                     out=out)
    assert rc == 0
    assert json.loads(out.getvalue().splitlines()[-1])["correct"] is False


def test_the_harness_runs_the_cell():
    out = io.StringIO()
    rc = harness.run(CELL, 2**31 + 3, 0.5, False, t0=time.perf_counter(),
                     devices=jax.devices()[:1],
                     make_driver=lambda _f, _t, seed, chips, **kw:
                     tiny_driver(seed, **kw), out=out)
    assert rc == 0
    result = json.loads(out.getvalue().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {
        "setup_s", "learn_steps_per_s", "env_frames_per_s", "peak_hbm_gb"}


def test_driver_is_the_trainers_program(tmp_path, monkeypatch):
    """As benchmarks/tests/test_same_program.py, with the new core and with
    the game's tick cap, which this driver's own `build` hands on: at a cap
    of 21 ticks the lanes' episodes are cut inside the run, and the losses
    are the trainer's bit for bit only if the driver's game is cut there
    too."""
    from rainbow_iqn_apex_tpu import train_anakin_r2d2
    from rainbow_iqn_apex_tpu.replay import device_sequence

    traffic = tiny.traffic(TRAFFIC)
    traffic["fields"]["device_game_tick_cap"] = 21
    drv = Driver(tiny_fields(), traffic, 2**31 + 11, 1)
    ts0, ss0 = jax.tree.map(np.asarray, drv.carry[:2])
    steps, losses, ended = 0, [], 0
    for _ in range(6):
        steps, outs, _k = drv.dispatch()
        loss = np.asarray(outs[1])
        ended += int(np.isfinite(np.asarray(outs[0])).sum())
        if np.any(np.isfinite(loss)):
            losses.append(float(np.nanmean(loss)))
    assert ended == 4 * (48 // 21)  # every lane's episodes, cut at 21 and 42
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
    for name in ("moe_held_assign_share", "attn_band_key_share",
                 "attn_live_key_share_sliding", "attn_live_key_share_full"):
        assert learn[-1][name] == pytest.approx(drv.counters[name])


def test_learn_flops_against_a_hand_count():
    cfg = tiny.load("configs", CONFIG)
    cc = json.load(open(os.path.join(harness.ROOT, cfg["fields"]["core_config"])))
    full = 2 * (2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48)
    sliding = 2 * (2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64)
    dense = 2 * 3 * 2048 * 8192
    # router over 256, the shared expert, and 8 x 16/256 = half a held
    # expert a token
    moe = 2 * 2048 * 256 + 2 * 3 * 2048 * 512 * 1.5
    token = 2 * 2304 * 2048 + 2 * full + 3 * sliding + dense + 4 * moe
    # the keys the queries of the burn-in and of the slice may see: a full
    # layer's all before them, a sliding layer's the last 512
    full_burn, full_slice = 512 * 513 // 2, sum(range(513, 1025))
    sliding_burn, sliding_slice = 512 * 513 // 2, 512 * 512
    assert flops_laguna_core.keys_seen(512, 512, 1024) == sliding_slice
    assert flops_laguna_core.keys_seen(1024, 512, 1024) == full_slice
    scores = lambda f, s: 2 * 256 * (2 * 48 * f + 3 * 64 * s)  # noqa: E731
    burned = 512 * token + scores(full_burn, sliding_burn)
    trained = 512 * token + scores(full_slice, sliding_slice)
    assert flops_laguna_core.core_flops(cc, 0, 512, 2304) == pytest.approx(
        burned)
    assert flops_laguna_core.core_flops(cc, 512, 1024, 2304) == pytest.approx(
        trained)
    assert token == pytest.approx(2 * 248.6e6, rel=0.001)
    step = flops_laguna_core.learn_flops(cfg["fields"], cc, (80, 80), 3)
    # by hand as benchmarks/tests/test_flops.py: trunk 12,763,136 a frame
    # stack, its first layer 5,914,624; noisy dueling heads on 2,048 features
    trunk, conv1 = 12_763_136, 2 * 19 * 19 * 32 * 256
    heads = (2 * 4 * 2048 * 512) + 4 * 512 * 1 + 4 * 512 * 3
    online = (512 * trunk + burned) + 3 * (
        512 * trunk + trained + 512 * heads) - 512 * conv1
    target = 1024 * trunk + burned + trained + 512 * heads
    assert step == pytest.approx(8 * (online + target))
    assert step == pytest.approx(14.40e12, rel=0.001)
    # the two kinds of attention layer are most of a token's work: their
    # projections, and their scores and values over the keys seen
    attention = 8 * (2 + 3 + 1) * 512 * (2 * full + 3 * sliding) / step
    assert attention == pytest.approx(0.59, abs=0.01)
    # a block's size cannot move the count: it goes by the mask
    assert "ATTN_BLOCK" not in open(flops_laguna_core.__file__).read()


# ------------------------------------------------------------ the readers
_BODY = "jit(segment)/jit(main)/while/body/"
_LEARN = _BODY + "tick_learn/cond/branch_1_fun/while/body/learn_step/"
_L1 = "core_layer/checkpoint/layer_1/gqa/attn_full/"
_L2 = "core_layer/checkpoint/layer_2/gqa/attn_sliding/"
_F1 = "core_layer/checkpoint/layer_1/"
_F2 = "core_layer/checkpoint/layer_2/"


def _line(inst, path):
    return f'  %{inst} = f32[] fusion(%a), metadata={{op_name="{path}"}}'


MODULE = "\n".join([
    "HloModule jit_segment, entry_computation_layout={()->f32[]}",
    "ENTRY %main (ring: u8[9]) -> f32[] {",
    _line("fusion.1", _BODY + "tick_act/net_trunk/conv"),
    _line("fusion.2", _BODY + "tick_act/" + _L1 + "mha_proj/q_proj/dot"),
    _line("fusion.3", _BODY + "tick_act/" + _L1 + "mha_attn/mha_rope/mul"),
    _line("fusion.4", _BODY + "tick_act/" + _L2 + "mha_attn/dot"),
    _line("fusion.5", _BODY + "tick_env/add"),
    _line("fusion.6", _LEARN + "jvp(core_embed)/dot"),
    _line("fusion.7", _LEARN + "jvp(" + _L1 + "mha_proj)/q_proj/dot"),
    _line("fusion.8", _LEARN + "transpose(jvp(" + _L2 + "mha_proj))/dot"),
    _line("fusion.9", _LEARN + "jvp(" + _F1 + "dense_ffn)/dot"),
    _line("fusion.10", _LEARN + "transpose(jvp(" + _F1 + "dense_ffn))/dot"),
    _line("fusion.11", _LEARN + "jvp(" + _L1 + "mha_attn/mha_rope)/mul"),
    _line("fusion.12", _LEARN + "jvp(" + _L1 + "mha_attn)/checkpoint/dot"),
    _line("fusion.13", _LEARN + "transpose(jvp(" + _L2 + "mha_attn))/dot"),
    _line("fusion.14", _LEARN + "jvp(" + _L2 + "mha_attn/mha_rope)/mul"),
    _line("fusion.15", _LEARN + "jvp(" + _F2 + "moe/moe_route)/sort"),
    _line("fusion.16", _LEARN + "jvp(" + _F2 + "moe/moe_experts)/ragged_dot"),
    _line("fusion.17", _LEARN + "jvp(" + _F2 + "moe/moe_shared)/dot"),
    _line("fusion.18", _LEARN + "net_trunk/conv"),
    _line("fusion.19", _LEARN + "optimizer/mul"),
    '  %while.20 = f32[] while(%a), body=%b, metadata={op_name="jit(segment)/'
    'jit(main)/while"}',
    "}",
])
# self seconds over 2 traced dispatches of 4 ticks, holding 5 learn steps
_T = {1: 0.0008, 2: 0.0016, 3: 0.0001, 4: 0.0002, 5: 0.0002, 6: 0.001,
      7: 0.010, 8: 0.020, 9: 0.030, 10: 0.015, 11: 0.004, 12: 0.002,
      13: 0.006, 14: 0.008, 15: 0.012, 16: 0.009, 17: 0.004, 18: 0.005,
      19: 0.0025}
OPS = [[f"%fusion.{i} = f32[] fusion(f32[] %a), kind=kLoop", t]
       for i, t in _T.items()] + [["%while.20 = f32[] while(f32[] %a)", 0.05]]
FLOPS = 14.4e12
WANT = {
    "laguna_learn_device_ms": 1e3 * sum(_T[i] for i in range(6, 20)) / 5,
    "laguna_sliding_attn_device_ms": 1e3 * (0.006 + 0.008) / 5,
    "laguna_full_attn_device_ms": 1e3 * (0.004 + 0.002) / 5,
    "laguna_attn_proj_device_ms": 1e3 * (0.010 + 0.020) / 5,
    "laguna_moe_device_ms": 1e3 * (0.012 + 0.009 + 0.004) / 5,
    "laguna_dense_ffn_device_ms": 1e3 * (0.030 + 0.015) / 5,
    "laguna_optimizer_device_ms": 1e3 * 0.0025 / 5,
    "laguna_act_device_ms": 1e3 * sum(_T[i] for i in range(1, 5)) / (2 * 4),
    "laguna_act_attn_device_ms": 1e3 * (0.0001 + 0.0002) / (2 * 4),
    "laguna_device_idle_share": 100 * (1 - 0.3 / 0.4),
    "laguna_learn_mfu": 100 * FLOPS * (5 / 0.3) / 197e12,
    "laguna_band_key_share": 62.4,
    "laguna_sliding_live_key_share": 48.5,
    "laguna_held_assign_share": 6.25,
}


class _Compiled:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


class _Segment:
    def __init__(self, text):
        self.text = text

    def lower(self, carry, key):
        return self

    def compile(self):
        return _Compiled(self.text)


class _Driver:
    ticks, carry, key = 4, "carry", "key"
    counters = {"moe_held_assign_share": 0.0625,
                "attn_band_key_share": 0.624,
                "attn_live_key_share_sliding": 0.485}

    def __init__(self, text=MODULE):
        self.segment = _Segment(text)

    def learn_flops(self):
        return FLOPS


def _ctx(traced=True, driver=None):
    window = {"traced": {"seconds": 0.3, "steps": 5, "segments": 2}
              if traced else None}
    return harness.Context(
        driver=driver or _Driver(), window=window,
        trace={"device_ops": OPS, "window_s": 0.4, "busy_s": 0.3},
        chips=1, peaks={"bf16_flops_per_s": 197e12})


@pytest.mark.parametrize("metric", METRICS)
def test_reader_by_hand(metric):
    assert harness.load_reader(metric).read(_ctx()) == pytest.approx(
        WANT[metric], rel=1e-9)


@pytest.mark.parametrize("metric", DEVICE_TIMES + (
    "laguna_learn_mfu", "laguna_device_idle_share"))
def test_reader_is_none_on_an_untraced_window(metric):
    assert harness.load_reader(metric).read(_ctx(traced=False)) is None


@pytest.mark.parametrize("metric", (
    "laguna_sliding_attn_device_ms", "laguna_full_attn_device_ms",
    "laguna_attn_proj_device_ms", "laguna_moe_device_ms",
    "laguna_dense_ffn_device_ms", "laguna_act_attn_device_ms"))
def test_reader_is_none_where_its_scope_is_absent(metric):
    """A program whose core has no `attn_*`, `mha_*`, `moe_*` or `dense_ffn`
    (the module text of test_scope_readers.py: the LSTM cell's)."""
    from benchmarks.tests.test_scope_readers import MODULE as lstm_module
    from benchmarks.tests.test_scope_readers import OPS as lstm_ops

    ctx = _ctx(driver=_Driver(lstm_module))
    ctx.trace = {"device_ops": lstm_ops}
    assert harness.load_reader(metric).read(ctx) is None


def test_a_program_without_scopes_or_counters_reports_nothing(monkeypatch):
    """Laid over a checkout from before the scopes, the readers find no
    `obs/device_scopes.py`, and a driver without counters has no share: they
    return None and do not raise."""
    import rainbow_iqn_apex_tpu.obs as obs
    from rainbow_iqn_apex_tpu.obs import device_scopes  # noqa: F401

    monkeypatch.delattr(obs, "device_scopes")
    monkeypatch.setitem(
        sys.modules, "rainbow_iqn_apex_tpu.obs.device_scopes", None)
    for metric in DEVICE_TIMES:
        assert harness.load_reader(metric).read(_ctx()) is None
    monkeypatch.setattr(_Driver, "counters", {})
    for metric in COUNTERS:
        assert harness.load_reader(metric).read(_ctx()) is None


@pytest.mark.parametrize("metric", METRICS)
def test_entry_has_a_reader_and_lists_the_cell_alone(metric):
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {m["name"]: m for m in bench["per_layer"]}[metric]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "learn_steps_per_s"
    assert os.path.isfile(
        os.path.join(harness.HERE, "readers", metric + ".py"))
    # no other cell reports it, and this cell reports no other cell's metric
    for other in (w["name"] for w in bench["workloads"] if w["name"] != CELL):
        assert metric not in [m["name"] for m in
                              harness.metric_specs(other, "per_layer")]
    assert {m["name"] for m in harness.metric_specs(CELL, "per_layer")} == set(
        METRICS)


def test_the_benchmark_gained_one_configuration_and_one_cell():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, TRAFFIC, 1)]
    assert [c["name"] for c in bench["configs"]].count(CONFIG) == 1
    assert [w["config"] for w in bench["workloads"]
            if w["traffic"] == TRAFFIC] == [CONFIG]
    assert [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]] == list(METRICS[:9]) + [
        "laguna_device_idle_share", "laguna_learn_mfu", *COUNTERS]
    traffic = tiny.load("traffic", TRAFFIC)["fields"]
    assert traffic == {
        "env_id": "jaxgame:freeway", "num_envs_per_actor": 16,
        "anakin_segment_ticks": 64, "fused_env": True,
        "device_game_tick_cap": 3414}
    assert 3414 == 5 * 512 + (1024 - 1024 // 6)  # ringfill's cut row
    wl = tiny.load("workloads", CELL)
    assert set(wl["limits_why"]) >= {
        "readings", "grad1_median_angle", "dparam_median_gap", "loss1_rel"}


def test_the_configuration_holds_every_published_number():
    """The catalog's `config` for Laguna-XS.2 is what
    configs/cores/laguna_xs_2.json holds verbatim; the benchmark's file holds
    the same but for the keys it lists as `reduced`."""
    core = json.load(open(os.path.join(
        harness.ROOT, "configs", "cores", "laguna_xs_2.json")))
    cfg = tiny.load("configs", CONFIG)
    reduced = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 0}
    assert cfg["reduced"] == [*reduced, "memory_capacity"]
    assert cfg["published"] == {k: core[k] for k in reduced} == {
        "num_hidden_layers": 40, "num_experts": 256, "vocab_size": 100352}
    own = ("source", "what", "layers_here", "first_layer_here", "experts_here",
           "first_expert_here", "chips_per_layer", "published", "assumed")
    for key, value in core.items():
        if key not in own:
            assert cfg[key] == reduced.get(key, value), key
    assert (core["hidden_size"], core["num_attention_heads"],
            core["num_key_value_heads"], core["head_dim"],
            core["intermediate_size"], core["moe_intermediate_size"],
            core["shared_expert_intermediate_size"],
            core["num_experts_per_tok"], core["sliding_window"],
            core["moe_routed_scaling_factor"], core["gating"],
            core["rms_norm_eps"], core["model_type"]) == (
        2048, 48, 8, 128, 8192, 512, 512, 8, 512, 2.5, True, 1e-6, "laguna")
    assert core["rope_parameters"]["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
    assert core["layer_types"][:5] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert core["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    for key in ("layers_here", "first_layer_here", "experts_here",
                "first_expert_here", "chips_per_layer"):
        assert cfg[key] == core[key], key
    assert (core["layers_here"], core["first_layer_here"],
            core["experts_here"], core["chips_per_layer"]) == (5, 0, 16, 16)
    # the agent's fields are the accepted core cells' but for the core's file
    # and the seven this family's shapes force
    lfm2 = tiny.load("configs", "lfm2-r2d2-1chip")["fields"]
    forced = {"r2d2_burn_in": 512, "r2d2_seq_len": 512, "r2d2_overlap": 512,
              "batch_size": 8, "frames_per_learn": 1,
              "memory_capacity": 196608, "learn_start": 196608}
    assert {k: v for k, v in cfg["fields"].items() if k != "core_config"} == {
        **{k: v for k, v in lfm2.items() if k != "core_config"}, **forced}
    assert set(forced) <= set(cfg["assumed"]) | {"r2d2_overlap"}
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(entry) == 1 and entry[0]["reduced"] == cfg["reduced"]
    assert entry[0]["source"] == core["source"]
