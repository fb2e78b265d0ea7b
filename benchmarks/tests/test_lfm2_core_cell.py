"""The cell `lfm2-r2d2-fused` at sizes a test can hold (the cut's five layers,
8 experts of which 2 are held and 2 a token): the float32 program passes the
cell's own limits, the control (the reference with fp8 matmuls, put in the
program's place) and the half-batch fault do not; the harness runs the cell
end to end; the driver runs the trainer's own program; the FLOP count against
a hand count; every new reader on a hand-made attribution, and on a program
without its scopes; the reference's two copies are one text; the benchmark's
entries.  The shares-add-up test is tier-1's (tests/test_lfm2_core.py)."""

import io
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

from benchmarks import check, flops_lfm2_core, harness
from benchmarks.drivers.fused_r2d2_lfm2 import Driver
from benchmarks.tests import tiny

CELL = "lfm2-r2d2-fused"
CONFIG = "lfm2-r2d2-1chip"
TINY_CORE = os.path.join(harness.ROOT, "tests", "fixtures",
                         "lfm2_core_tiny.json")  # the tier-1 tests' own
DEVICE_TIMES = ("lfm2_learn_device_ms", "lfm2_sconv_device_ms",
                "lfm2_attn_device_ms", "lfm2_moe_device_ms",
                "lfm2_moe_route_device_ms", "lfm2_dense_ffn_device_ms",
                "lfm2_optimizer_device_ms", "lfm2_act_device_ms",
                "lfm2_outside_tick_ms", "lfm2_tick_learn_own_device_ms",
                "lfm2_compiler_made_device_ms")
METRICS = DEVICE_TIMES + ("lfm2_device_idle_share", "lfm2_learn_mfu",
                          "lfm2_held_assign_share", "lfm2_row_fill_share")


def tiny_fields() -> dict:
    f = tiny.load("configs", CONFIG)["fields"]
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
    assert sorted(drv.counters) == sorted(drv.core.stat_names)
    assert drv.counters["moe_tokens_dropped"] == 0.0
    # the seeded selection bias deals the held experts their even share: of
    # 4 layers x 2 chosen, round(8 x 2/8) = 2 are held, one a layer in two
    assert drv.counters["moe_held_assign_share"] == pytest.approx(2 / 8)
    # 48 tokens: one 96-row buffer, half full in those two layers
    assert drv.counters["moe_row_fill_share"] == pytest.approx(0.25)
    # the trained slice's 8 queries see 4 burn-in keys and their causal half
    assert drv.counters["attn_live_key_share"] == pytest.approx(68 / 96)
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
    # the fault of the online network in the target's place shifts every
    # target value alike: the first loss shows it, many times over its limit
    numbers = check.compare(
        drv.reference_side("online_target", None), ref, drv.params0)
    assert not check.verdict({**numbers, **exact}, limits, read_only)[0]
    assert numbers["loss1_rel"] > 10 * limits["loss1_rel"]


def test_the_seeded_weights_fill_the_cores_leaves_as_they_stand():
    """`weights_core` goes by leaf name and reads the held count off the
    stacked kernels: kernels normal(0, 1/fan_in), every norm's scale 1 (the
    q/k norms' too), taps uniform(+-1/sqrt(3)); each expert layer has 2
    chosen experts, and the held ones among them are dealt one a layer from
    the first expert layer on; no layer has a `shared` leaf to fill."""
    core = tiny_driver(2**31 + 7).carry[0].params["core"]
    assert sorted(core) == ["final_norm", "in_proj"] + [
        f"layer_{i}" for i in range(1, 6)]
    kernel = np.asarray(core["in_proj"]["kernel"])
    assert kernel.shape == (2304, 32)
    assert float(kernel.std()) == pytest.approx(1 / np.sqrt(2304), rel=0.05)
    held_chosen = []
    for i in (2, 3, 4, 5):
        moe = core[f"layer_{i}"]["moe"]
        assert sorted(moe) == ["experts", "router"]
        bias = np.asarray(moe["router"]["select_bias"])
        assert sorted(bias) == [0.0] * 6 + [2.0] * 2
        held_chosen.append(int((bias[:2] > 0).sum()))
        assert moe["experts"]["gate"].shape == (2, 32, 16)
    assert held_chosen == [1, 1, 0, 0]
    for i in (1, 3, 4, 5):
        sconv = core[f"layer_{i}"]["sconv"]
        taps = np.asarray(sconv["conv"]["taps"])
        assert taps.shape == (3, 32) and np.abs(taps).max() <= 1 / np.sqrt(3)
        assert sconv["in_proj"]["kernel"].shape == (32, 96)
        assert float(np.asarray(sconv["in_proj"]["kernel"]).std()
                     ) == pytest.approx(1 / np.sqrt(32), rel=0.1)
    mha = core["layer_2"]["mha"]
    assert np.all(np.asarray(mha["q_norm"]["scale"]) == 1)
    assert np.all(np.asarray(mha["k_norm"]["scale"]) == 1)
    assert mha["k_proj"]["kernel"].shape == (32, 16)
    assert "ffn" in core["layer_1"] and "moe" not in core["layer_1"]


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
    """As benchmarks/tests/test_same_program.py, with the new core."""
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
    for name in ("moe_held_assign_share", "moe_row_fill_share",
                 "attn_live_key_share"):
        assert learn[-1][name] == pytest.approx(drv.counters[name])


def test_learn_flops_against_a_hand_count():
    cfg = tiny.load("configs", CONFIG)
    cc = json.load(open(os.path.join(harness.ROOT, cfg["fields"]["core_config"])))
    conv = 2 * (2048 * 6144 + 2048 * 2048 + 3 * 2048)
    attn = 2 * (2 * 2048 * 2048 + 2 * 2048 * 512)  # q, o; k, v of 8 heads
    attn += 2 * 32 * (64 + 64) * 60.5  # scores and values, the causal half
    dense = 2 * 3 * 2048 * 7168
    # router over 32, and 4 x 8/32 = one held expert a token
    moe = 2 * 2048 * 32 + 2 * 3 * 2048 * 1792 * 1
    token = (2 * 2304 * 2048 + (conv + dense) + (attn + moe)
             + 3 * (conv + moe))
    assert flops_lfm2_core.core_token_flops(
        cc, 120, 2304) == pytest.approx(token)
    assert token == pytest.approx(2 * 170.93e6, rel=0.001)
    # the whole model's first five layers: both leading layers dense, then
    # attention, conv, conv with experts
    assert flops_lfm2_core.core_token_flops(
        {**cc, "first_layer_here": 0}, 120, 2304) == pytest.approx(
            2 * 2304 * 2048 + 2 * (conv + dense) + (attn + moe)
            + 2 * (conv + moe))
    step = flops_lfm2_core.learn_flops(cfg["fields"], cc, (80, 80), 3)
    # by hand as benchmarks/tests/test_flops.py: trunk 12,763,136 a frame
    # stack, its first layer 5,914,624; noisy dueling heads on 2,048 features
    trunk, conv1 = 12_763_136, 2 * 19 * 19 * 32 * 256
    heads = (2 * 4 * 2048 * 512) + 4 * 512 * 1 + 4 * 512 * 3
    body = trunk + token
    online = 40 * body + 80 * (3 * (body + heads) - conv1)
    target = 120 * body + 80 * heads
    assert step == pytest.approx(64 * (online + target))
    assert step == pytest.approx(9.22e12, rel=0.001)
    # the four expert layers' grouped products: a quarter of the step
    experts = 4 * 2 * 3 * 2048 * 1792 * 64 * (40 + 3 * 80 + 120)
    assert experts / step == pytest.approx(0.245, abs=0.005)


# ------------------------------------------------------------ the readers
_BODY = "jit(segment)/jit(main)/while/body/"
_LEARN = _BODY + "tick_learn/cond/branch_1_fun/while/body/learn_step/"
_L1 = "core_layer/checkpoint/layer_1/"
_L2 = "core_layer/checkpoint/layer_2/"
_L3 = "core_layer/checkpoint/layer_3/"


def _line(inst, path):
    return f'  %{inst} = f32[] fusion(%a), metadata={{op_name="{path}"}}'


MODULE = "\n".join([
    "HloModule jit_segment, entry_computation_layout={()->f32[]}",
    "ENTRY %main (ring: u8[9]) -> f32[] {",
    _line("fusion.1", _BODY + "tick_act/net_trunk/conv"),
    _line("fusion.2", _BODY + "tick_act/" + _L1 + "sconv/sconv_mix/in_proj/dot"),
    _line("fusion.3", _BODY + "tick_act/" + _L2 + "mha/mha_attn/mha_rope/mul"),
    _line("fusion.4", _BODY + "tick_act/" + _L2 + "moe/moe_experts/ragged_dot"),
    _line("fusion.5", _BODY + "tick_env/add"),
    _line("fusion.6", _LEARN + "jvp(core_embed)/dot"),
    _line("fusion.7", _LEARN + "jvp(" + _L1 + "sconv/sconv_mix)/in_proj/dot"),
    _line("fusion.8", _LEARN + "transpose(jvp(" + _L3 + "sconv/sconv_mix))/mul"),
    _line("fusion.9", _LEARN + "jvp(" + _L1 + "dense_ffn)/dot"),
    _line("fusion.10", _LEARN + "transpose(jvp(" + _L1 + "dense_ffn))/dot"),
    _line("fusion.11", _LEARN + "jvp(" + _L2 + "mha/mha_proj)/dot"),
    _line("fusion.12", _LEARN + "jvp(" + _L2 + "mha/mha_attn/mha_rope)/mul"),
    _line("fusion.13", _LEARN + "transpose(jvp(" + _L2 + "mha/mha_attn))/dot"),
    _line("fusion.14", _LEARN + "jvp(" + _L2 + "moe/moe_route)/sort"),
    _line("fusion.15", _LEARN + "jvp(" + _L3 + "moe/moe_experts)/ragged_dot"),
    _line("fusion.16", _LEARN + "transpose(jvp(" + _L3 + "moe/moe_experts))/add"),
    _line("fusion.17", _LEARN + "jvp(" + _L1 + "core_norm)/rsqrt"),
    _line("fusion.18", _LEARN + "net_trunk/conv"),
    _line("fusion.19", _LEARN + "optimizer/mul"),
    '  %while.20 = f32[] while(%a), body=%b, metadata={op_name="jit(segment)/'
    'jit(main)/while"}',
    _line("fusion.21", _BODY + "tick_learn/cond/branch_1_fun/while/body/copy"),
    "  %copy.22 = f32[] copy(%a)",  # the compiler's: no metadata
    # a grouped product as the TPU compiler leaves it: a custom call under
    # its own label, fed by `moe_experts`' rows
    '  %ragged-dot-none.23 = f32[] custom-call(%fusion.15), '
    'custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}',
    "}",
])
# self seconds over 2 traced dispatches of 4 ticks, holding 5 learn steps
_T = {1: 0.0008, 2: 0.0016, 3: 0.0001, 4: 0.0002, 5: 0.0002, 6: 0.001,
      7: 0.010, 8: 0.020, 9: 0.030, 10: 0.015, 11: 0.004, 12: 0.002,
      13: 0.006, 14: 0.008, 15: 0.012, 16: 0.009, 17: 0.004, 18: 0.005,
      19: 0.0025}
OPS = [[f"%fusion.{i} = f32[] fusion(f32[] %a), kind=kLoop", t]
       for i, t in _T.items()] + [
    ["%while.20 = f32[] while(f32[] %a)", 0.05],
    ["%fusion.21 = f32[] fusion(f32[] %a), kind=kLoop", 0.003],
    ["%copy.22 = f32[] copy(f32[] %a)", 0.002],
    ["%ragged-dot-none.23 = f32[] custom-call(f32[] %fusion.15)", 0.007]]
FLOPS = 9.22e12
WANT = {
    "lfm2_learn_device_ms": 1e3 * (sum(_T[i] for i in range(6, 20)) + 0.007) / 5,
    "lfm2_sconv_device_ms": 1e3 * (0.010 + 0.020) / 5,
    "lfm2_attn_device_ms": 1e3 * (0.004 + 0.002 + 0.006) / 5,
    "lfm2_moe_device_ms": 1e3 * (0.008 + 0.012 + 0.009 + 0.007) / 5,
    "lfm2_moe_route_device_ms": 1e3 * 0.008 / 5,
    "lfm2_dense_ffn_device_ms": 1e3 * (0.030 + 0.015) / 5,
    "lfm2_optimizer_device_ms": 1e3 * 0.0025 / 5,
    "lfm2_act_device_ms": 1e3 * sum(_T[i] for i in range(1, 5)) / (2 * 4),
    "lfm2_outside_tick_ms": 1e3 * (0.05 + 0.002) / 2,
    "lfm2_tick_learn_own_device_ms": 1e3 * 0.003 / 5,
    "lfm2_compiler_made_device_ms": 1e3 * 0.002 / 2,
    "lfm2_device_idle_share": 100 * (1 - 0.3 / 0.4),
    "lfm2_learn_mfu": 100 * FLOPS * (5 / 0.3) / 197e12,
    "lfm2_held_assign_share": 25.0,
    "lfm2_row_fill_share": 50.0,
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
    counters = {"moe_held_assign_share": 0.25, "moe_row_fill_share": 0.5,
                "attn_live_key_share": 0.6708}

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
    "lfm2_learn_mfu", "lfm2_device_idle_share"))
def test_reader_is_none_on_an_untraced_window(metric):
    assert harness.load_reader(metric).read(_ctx(traced=False)) is None


@pytest.mark.parametrize("metric", (
    "lfm2_sconv_device_ms", "lfm2_attn_device_ms", "lfm2_moe_device_ms",
    "lfm2_moe_route_device_ms", "lfm2_dense_ffn_device_ms"))
def test_reader_is_none_where_its_scope_is_absent(metric):
    """A program whose core has no `sconv_mix`, `mha_*`, `moe_*` or
    `dense_ffn` (the module text of test_scope_readers.py: the LSTM cell's)."""
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
    for metric in ("lfm2_held_assign_share", "lfm2_row_fill_share"):
        assert harness.load_reader(metric).read(_ctx()) is None


@pytest.mark.parametrize("metric", METRICS)
def test_entry_has_a_reader_and_lists_the_cell_alone(metric):
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    entry = entries[metric]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "learn_steps_per_s"
    assert os.path.isfile(
        os.path.join(harness.HERE, "readers", metric + ".py"))
    # no accepted cell reports it, and this cell reports no accepted metric
    for other in ("r2d2-fused", "kimi-linear-r2d2-fused",
                  "kanana-2-r2d2-fused", "qwen3-next-r2d2-fused",
                  "ouro-r2d2-fused"):
        assert metric not in [m["name"] for m in
                              harness.metric_specs(other, "per_layer")]
    assert {m["name"] for m in harness.metric_specs(CELL, "per_layer")} == set(
        METRICS)


def test_the_benchmark_gained_one_configuration_and_one_cell():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "freeway-16lanes", 1)]
    assert [c["name"] for c in bench["configs"]].count(CONFIG) == 1
    # by name and not by place: a later cell stands after this one
    assert not [w for w in cells if w["chips"] != 1]
    assert [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]] == [
        "lfm2_learn_device_ms", "lfm2_sconv_device_ms", "lfm2_attn_device_ms",
        "lfm2_moe_device_ms", "lfm2_moe_route_device_ms",
        "lfm2_dense_ffn_device_ms", "lfm2_optimizer_device_ms",
        "lfm2_act_device_ms", "lfm2_device_idle_share", "lfm2_learn_mfu",
        "lfm2_held_assign_share", "lfm2_row_fill_share",
        "lfm2_outside_tick_ms", "lfm2_tick_learn_own_device_ms",
        "lfm2_compiler_made_device_ms"]
    wl = tiny.load("workloads", CELL)
    assert set(wl["limits_why"]) >= {
        "readings", "grad1_median_angle", "dparam_median_gap", "loss1_rel"}


def test_the_configuration_holds_every_published_number():
    """The catalog's `config` for LFM2-8B-A1B is what
    configs/cores/lfm2_8b_a1b.json holds verbatim; the benchmark's file holds
    the same but for the keys it lists as `reduced`."""
    core = json.load(open(os.path.join(
        harness.ROOT, "configs", "cores", "lfm2_8b_a1b.json")))
    cfg = tiny.load("configs", CONFIG)
    reduced = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
               "vocab_size": 0}
    assert cfg["reduced"] == [*reduced, "memory_capacity"]
    assert cfg["published"] == {k: core[k] for k in reduced} == {
        "num_hidden_layers": 24, "num_dense_layers": 2, "num_experts": 32,
        "vocab_size": 65536}
    own = ("source", "what", "layers_here", "first_layer_here", "experts_here",
           "first_expert_here", "chips_per_layer", "assumed")
    for key, value in core.items():
        if key not in own:
            assert cfg[key] == reduced.get(key, value), key
    assert (core["hidden_size"], core["num_attention_heads"],
            core["num_key_value_heads"], core["intermediate_size"],
            core["moe_intermediate_size"], core["num_experts_per_tok"],
            core["conv_L_cache"], core["conv_bias"], core["rope_theta"],
            core["norm_eps"], core["routed_scaling_factor"],
            core["use_expert_bias"], core["norm_topk_prob"],
            core["model_type"]) == (
        2048, 32, 8, 7168, 1792, 4, 3, False, 1000000, 1e-5, 1, True, True,
        "lfm2_moe")
    assert core["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    for key in ("layers_here", "first_layer_here", "experts_here",
                "first_expert_here", "chips_per_layer"):
        assert cfg[key] == core[key], key
    assert (core["layers_here"], core["first_layer_here"],
            core["experts_here"], core["chips_per_layer"]) == (5, 1, 8, 4)
    kanana = tiny.load("configs", "kanana-2-r2d2-1chip")["fields"]
    assert {k: v for k, v in cfg["fields"].items() if k != "core_config"} == {
        k: v for k, v in kanana.items() if k != "core_config"}
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(entry) == 1 and entry[0]["reduced"] == cfg["reduced"]
    assert entry[0]["source"] == core["source"]


def test_the_two_copies_of_the_reference_are_the_same_text():
    with open(os.path.join(harness.ROOT, "tests",
                           "reference_lfm2_core.py")) as a, open(
            os.path.join(harness.HERE, "references", "lfm2_core.py")) as b:
        assert a.read() == b.read()
