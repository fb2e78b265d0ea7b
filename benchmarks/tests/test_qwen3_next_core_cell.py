"""The cell `qwen3-next-r2d2-fused` at sizes a test can hold: the float32
program passes the cell's own limits, the control (the reference with fp8
matmuls, put in the program's place) and the half-batch fault do not; the
harness runs the cell end to end; the driver runs the trainer's own program;
the FLOP count against a hand count; every new reader on a hand-made
attribution, and on a program without its scopes; the reference's two copies
are one text; the benchmark's entries."""

import io
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

from benchmarks import check, flops_qwen3_next_core, harness
from benchmarks.drivers.fused_r2d2_qwen3_next import Driver
from benchmarks.tests import tiny

CELL = "qwen3-next-r2d2-fused"
CONFIG = "qwen3-next-r2d2-1chip"
TINY_CORE = os.path.join(harness.ROOT, "tests", "fixtures",
                         "qwen3_next_core_tiny.json")  # the tier-1 tests' own
DEVICE_TIMES = ("qwen3next_learn_device_ms", "qwen3next_delta_scan_device_ms",
                "qwen3next_gdn_mix_device_ms", "qwen3next_gattn_device_ms",
                "qwen3next_moe_device_ms", "qwen3next_moe_route_device_ms",
                "qwen3next_act_device_ms")
METRICS = DEVICE_TIMES + ("qwen3next_learn_mfu", "qwen3next_held_assign_share")


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
    assert drv.counters["moe_tokens_dropped"] == 0.0
    # the seeded selection bias deals the held experts their even share: of
    # 4 layers x 3 chosen, round(12 x 4/16) = 3 are held, one a layer
    assert drv.counters["moe_held_assign_share"] == pytest.approx(3 / 12)
    # the trained slice's 8 queries see 4 burn-in keys and their causal half
    assert drv.counters["gattn_live_key_share"] == pytest.approx(68 / 160)
    assert drv.counters["kda_fused_tile_share"] == 0.0  # the CPU's plain path
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


def test_the_seeded_weights_fill_the_cores_leaves_as_they_stand():
    """`weights_core` goes by leaf name and reads the held count off the
    stacked kernels: the input projection and the shared expert's gate are
    `kernel`s, the decay's two leaves are a value head wide, each expert
    layer has 3 chosen experts, and the held ones among them are dealt one a
    layer from the first layer on."""
    core = tiny_driver(2**31 + 7).carry[0].params["core"]
    kernel = np.asarray(core["in_proj"]["kernel"])
    assert kernel.shape == (2304, 32)
    assert float(kernel.std()) == pytest.approx(1 / np.sqrt(2304), rel=0.05)
    gdn = core["layer_1"]["gdn"]
    assert gdn["A_log"].shape == gdn["dt_bias"].shape == (4,)
    assert 0.0 <= float(np.min(gdn["A_log"])) and float(
        np.max(gdn["A_log"])) <= np.log(16.0)
    assert np.abs(np.asarray(gdn["conv"]["taps"])).max() <= 0.5
    assert np.all(np.asarray(core["layer_4"]["gattn"]["q_norm"]["scale"]) == 1)
    assert core["layer_3"]["moe"]["shared_gate"]["kernel"].shape == (32, 1)
    held_chosen = []
    for i in (1, 2, 3, 4):
        bias = np.asarray(core[f"layer_{i}"]["moe"]["router"]["select_bias"])
        assert bias.shape == (16,) and (bias > 0).sum() == 3
        held_chosen.append(int((bias[:4] > 0).sum()))
    assert held_chosen == [1, 1, 1, 0]


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
    assert learn[-1]["gattn_live_key_share"] == pytest.approx(
        drv.counters["gattn_live_key_share"])


def test_learn_flops_against_a_hand_count():
    cfg = tiny.load("configs", CONFIG)
    cc = json.load(open(os.path.join(harness.ROOT, cfg["fields"]["core_config"])))
    gdn = 2 * (2048 * 12288 + 2048 * 64 + 4096 * 2048)  # qkvz, ba, o
    gdn += 2 * 8192 * 4 + 8 * 32 * 128 * 128  # the taps, the recurrence
    attn = 2 * (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048)
    attn += 2 * 16 * (256 + 256) * 60.5
    moe = 2 * 2048 * 512 + 6 * 2048 * 512 * (1 + 10 * 32 / 512) + 2 * 2048
    token = 2 * 2304 * 2048 + 3 * gdn + attn + 4 * moe
    assert flops_qwen3_next_core.core_token_flops(
        cc, 120, 2304) == pytest.approx(token)
    assert token == pytest.approx(329.1e6, rel=0.001)
    step = flops_qwen3_next_core.learn_flops(cfg["fields"], cc, (80, 80), 3)
    # by hand as benchmarks/tests/test_flops.py: trunk 12,763,136 a frame
    # stack, its first layer 5,914,624; noisy dueling heads on 2,048 features
    trunk, conv1 = 12_763_136, 2 * 19 * 19 * 32 * 256
    heads = (2 * 4 * 2048 * 512) + 4 * 512 * 1 + 4 * 512 * 3
    body = trunk + token
    online = 40 * body + 80 * (3 * (body + heads) - conv1)
    target = 120 * body + 80 * heads
    assert step == pytest.approx(64 * (online + target))
    assert step == pytest.approx(8.895e12, rel=0.001)


# ------------------------------------------------------------ the readers
_BODY = "jit(segment)/jit(main)/while/body/"
_LEARN = _BODY + "tick_learn/cond/branch_1_fun/while/body/learn_step/"
_GDN = "core_layer/checkpoint/layer_2/gdn/"
_ATT = "core_layer/checkpoint/layer_4/gattn/"
_MOE = "core_layer/checkpoint/layer_2/moe/"


def _line(inst, path):
    return f'  %{inst} = f32[] fusion(%a), metadata={{op_name="{path}"}}'


MODULE = "\n".join([
    "HloModule jit_segment, entry_computation_layout={()->f32[]}",
    "ENTRY %main (ring: u8[9]) -> f32[] {",
    _line("fusion.1", _BODY + "tick_act/net_trunk/conv"),
    _line("fusion.2", _BODY + "tick_act/" + _GDN + "gdn_mix/dot"),
    _line("fusion.3", _BODY + "tick_act/" + _GDN + "core_step/mul"),
    _line("fusion.4", _BODY + "tick_act/" + _ATT + "gattn_attn/gattn_rope/mul"),
    _line("fusion.5", _BODY + "tick_act/" + _ATT + "gattn_proj/dot"),
    _line("fusion.6", _BODY + "tick_env/add"),
    _line("fusion.7", _LEARN + "jvp(" + _GDN + "gdn_mix)/dot"),
    _line("fusion.8", _LEARN + "transpose(jvp(" + _GDN + "gdn_mix))/dot"),
    _line("fusion.9", _LEARN + "jvp(" + _GDN + "kda_scan/kda_prep)/custom"),
    _line("fusion.10", _LEARN + "transpose(jvp(" + _GDN + "kda_scan))/while"),
    _line("fusion.11", _LEARN + "jvp(" + _ATT + "gattn_proj)/dot"),
    _line("fusion.12", _LEARN + "jvp(" + _ATT + "gattn_attn/gattn_rope)/mul"),
    _line("fusion.13", _LEARN + "transpose(jvp(" + _ATT + "gattn_attn))/dot"),
    _line("fusion.14", _LEARN + "jvp(" + _MOE + "moe_route)/sort"),
    _line("fusion.15", _LEARN + "jvp(" + _MOE + "moe_experts)/ragged_dot"),
    _line("fusion.16", _LEARN + "jvp(" + _MOE + "moe_shared)/dot"),
    _line("fusion.17", _LEARN + "optimizer/mul"),
    "}",
])
# self seconds over 2 traced dispatches of 4 ticks, holding 5 learn steps
_T = {1: 0.0008, 2: 0.0016, 3: 0.0001, 4: 0.0002, 5: 0.0004, 6: 0.0002,
      7: 0.010, 8: 0.020, 9: 0.030, 10: 0.015, 11: 0.004, 12: 0.002,
      13: 0.006, 14: 0.003, 15: 0.004, 16: 0.005, 17: 0.0025}
OPS = [[f"%fusion.{i} = f32[] fusion(f32[] %a), kind=kLoop", t]
       for i, t in _T.items()]
FLOPS = 8.895e12
WANT = {
    "qwen3next_learn_device_ms": 1e3 * sum(_T[i] for i in range(7, 18)) / 5,
    "qwen3next_delta_scan_device_ms": 1e3 * (0.030 + 0.015) / 5,
    "qwen3next_gdn_mix_device_ms": 1e3 * (0.010 + 0.020) / 5,
    "qwen3next_gattn_device_ms": 1e3 * (0.004 + 0.002 + 0.006) / 5,
    "qwen3next_moe_device_ms": 1e3 * (0.003 + 0.004 + 0.005) / 5,
    "qwen3next_moe_route_device_ms": 1e3 * 0.003 / 5,
    "qwen3next_act_device_ms": 1e3 * sum(_T[i] for i in range(1, 6)) / (2 * 4),
    "qwen3next_learn_mfu": 100 * FLOPS * (5 / 0.3) / 197e12,
    "qwen3next_held_assign_share": 5.0,
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
    counters = {"moe_held_assign_share": 0.05}

    def __init__(self, text=MODULE):
        self.segment = _Segment(text)

    def learn_flops(self):
        return FLOPS


def _ctx(traced=True, driver=None):
    window = {"traced": {"seconds": 0.3, "steps": 5, "segments": 2}
              if traced else None}
    return harness.Context(
        driver=driver or _Driver(), trace={"device_ops": OPS}, window=window,
        chips=1, peaks={"bf16_flops_per_s": 197e12})


@pytest.mark.parametrize("metric", METRICS)
def test_reader_by_hand(metric):
    assert harness.load_reader(metric).read(_ctx()) == pytest.approx(
        WANT[metric], rel=1e-9)


@pytest.mark.parametrize("metric", DEVICE_TIMES + ("qwen3next_learn_mfu",))
def test_reader_is_none_on_an_untraced_window(metric):
    assert harness.load_reader(metric).read(_ctx(traced=False)) is None


@pytest.mark.parametrize("metric", DEVICE_TIMES[1:6])
def test_reader_is_none_where_its_scope_is_absent(metric):
    """A program whose core has no delta-rule scan, no `gdn_mix` / `gattn_*`
    and no expert scopes (the module text of test_scope_readers.py: the LSTM
    cell's), and the Kanana cell's, which has the expert scopes alone."""
    from benchmarks.tests.test_kanana_core_cell import MODULE as kanana_module
    from benchmarks.tests.test_kanana_core_cell import OPS as kanana_ops
    from benchmarks.tests.test_scope_readers import MODULE as lstm_module
    from benchmarks.tests.test_scope_readers import OPS as lstm_ops

    ctx = _ctx(driver=_Driver(lstm_module))
    ctx.trace = {"device_ops": lstm_ops}
    assert harness.load_reader(metric).read(ctx) is None
    ctx = _ctx(driver=_Driver(kanana_module))
    ctx.trace = {"device_ops": kanana_ops}
    value = harness.load_reader(metric).read(ctx)
    assert (value is None) == ("moe" not in metric)


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
    assert harness.load_reader("qwen3next_held_assign_share").read(_ctx()) is None


@pytest.mark.parametrize("metric", METRICS)
def test_entry_has_a_reader_and_lists_the_cell_alone(metric):
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    entry = entries[metric]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "learn_steps_per_s"
    assert os.path.isfile(
        os.path.join(harness.HERE, "readers", metric + ".py"))
    assert metric in [m["name"] for m in harness.metric_specs(CELL, "per_layer")]
    # no accepted cell reports it, and this cell reports no accepted metric
    for other in ("r2d2-fused", "kimi-linear-r2d2-fused",
                  "kanana-2-r2d2-fused"):
        assert metric not in [m["name"] for m in
                              harness.metric_specs(other, "per_layer")]
    assert {m["name"] for m in harness.metric_specs(CELL, "per_layer")} == set(
        METRICS)


def test_the_configuration_holds_every_published_number():
    """The catalog's `config` for Qwen3-Next-80B-A3B-Instruct is what
    configs/cores/qwen3_next_80b_a3b.json holds verbatim; the benchmark's
    file holds the same but for the keys it lists as `reduced`."""
    core = json.load(open(os.path.join(
        harness.ROOT, "configs", "cores", "qwen3_next_80b_a3b.json")))
    cfg = tiny.load("configs", CONFIG)
    reduced = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 0}
    assert cfg["reduced"] == [*reduced, "memory_capacity"]
    assert cfg["published"] == {k: core[k] for k in reduced} == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    own = ("source", "what", "layers_here", "experts_here",
           "first_expert_here", "chips_per_layer", "assumed")
    for key, value in core.items():
        if key not in own:
            assert cfg[key] == reduced.get(key, value), key
    assert (core["hidden_size"], core["linear_num_key_heads"],
            core["linear_num_value_heads"], core["linear_key_head_dim"],
            core["linear_value_head_dim"], core["linear_conv_kernel_dim"],
            core["num_attention_heads"], core["num_key_value_heads"],
            core["head_dim"], core["partial_rotary_factor"],
            core["moe_intermediate_size"], core["num_experts_per_tok"],
            core["shared_expert_intermediate_size"],
            core["full_attention_interval"]) == (
        2048, 16, 32, 128, 128, 4, 16, 2, 256, 0.25, 512, 10, 512, 4)
    assert (cfg["layers_here"], cfg["experts_here"], cfg["chips_per_layer"]) == (
        core["layers_here"], core["experts_here"], core["chips_per_layer"]) == (
        4, 32, 16)
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
                           "reference_qwen3_next_core.py")) as a, open(
            os.path.join(harness.HERE, "references",
                         "qwen3_next_core.py")) as b:
        assert a.read() == b.read()
