"""The readers that close the device-time account (PR 37), and their helper
`benchmarks/idle.py`.

The op-time readers on a hand-made context, as `test_scope_readers.py` does
it.  The idle readers on `tiny.py`'s driver: the helper's second capture runs
for real (two dispatches of the tiny segment through the program's own
`TraceWindow`), and since a CPU capture has no device plane the events it
reduces are made up here, from instructions of the segment's own text."""

import json
import os

import jax
import pytest

from benchmarks import harness, idle, scopes
from benchmarks.tests import tiny
from rainbow_iqn_apex_tpu.obs import device_scopes as ds

OP_TIME = ("tick_learn_own_device_ms", "compiler_made_device_ms",
           "net_trunk_device_ms", "optimizer_device_ms",
           "core_norm_device_ms", "dense_ffn_device_ms", "kda_mix_device_ms",
           "core_unnamed_device_ms")
IDLE = ("idle_learn_ms", "idle_act_ms", "idle_outside_ms",
        "idle_lstm_scan_ms")
CELLS = ("r2d2-fused", "kimi-linear-r2d2-fused", "kanana-2-r2d2-fused",
         "qwen3-next-r2d2-fused")

_LEARN = "jit(segment)/jit(main)/while/body/tick_learn/cond/branch_1_fun/while/body/"
_LAYER = _LEARN + "learn_step/jvp(core)/core_layer/checkpoint/"


def _line(inst, opcode, operand, op_name=None):
    meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
    return f"  %{inst} = f32[4]{{0}} {opcode}(%{operand}){meta}"


MODULE = "\n".join([
    "HloModule jit_segment, entry_computation_layout={()->f32[]}",
    "%learn_body (p: f32[4]) -> f32[4] {",
    _line("copy.20", "copy", "p"),  # the compiler's: read by the KDA mixer
    _line("fusion.21", "fusion", "copy.20", _LAYER + "kda_mix/q_proj/dot"),
    _line("fusion.22", "fusion", "fusion.21", _LAYER + "core_norm/mix_norm/mul"),
    _line("fusion.23", "fusion", "fusion.22", _LAYER + "dense_ffn/ffn/dot"),
    _line("fusion.24", "fusion", "fusion.23", _LAYER + "add"),  # the residual
    _line("fusion.25", "fusion", "fusion.24", _LAYER + "kda_scan/while/body/dot"),
    _line("fusion.26", "fusion", "fusion.25", _LEARN + "learn_step/jvp(net_trunk)/conv"),
    _line("fusion.27", "fusion", "fusion.26", _LEARN + "learn_step/optimizer/mul"),
    _line("fusion.28", "fusion", "fusion.27", _LEARN + "replay_draw/cumsum"),
    _line("copy.29", "copy", "fusion.28"),  # read by nothing with a name
    "}",
    "ENTRY %main (ring: f32[4]) -> f32[] {",
    _line("copy.1", "copy", "ring"),
    _line("while.2", "while", "copy.1") + ", body=%learn_body, metadata={"
    'op_name="jit(segment)/jit(main)/while/body/tick_learn/cond"}',
    "}",
])
# self seconds over 2 traced dispatches holding 5 learn steps
OPS = [[f"%{inst} = f32[4]{{0}} {inst.split('.')[0]}(f32[4]{{0}} %x)", t]
       for inst, t in [
           ("copy.1", 0.040), ("while.2", 0.002), ("copy.20", 0.010),
           ("fusion.21", 0.100), ("fusion.22", 0.020), ("fusion.23", 0.050),
           ("fusion.24", 0.005), ("fusion.25", 0.200), ("fusion.26", 0.008),
           ("fusion.27", 0.025), ("fusion.28", 0.001), ("copy.29", 0.004)]]
WANT = {
    # the `while`, and the two copies that inherit its path
    "tick_learn_own_device_ms": 1e3 * (0.002 + 0.010 + 0.004) / 5,
    "compiler_made_device_ms": 1e3 * (0.040 + 0.010 + 0.004) / 2,
    "net_trunk_device_ms": 1e3 * 0.008 / 5,
    "optimizer_device_ms": 1e3 * 0.025 / 5,
    "core_norm_device_ms": 1e3 * 0.020 / 5,
    "dense_ffn_device_ms": 1e3 * 0.050 / 5,
    "kda_mix_device_ms": 1e3 * 0.100 / 5,
    "core_unnamed_device_ms": 1e3 * 0.005 / 5,
}


class _Compiled:
    text = MODULE

    def as_text(self):
        return self.text


class _Segment:
    lowered = 0

    def lower(self, carry, key):
        _Segment.lowered += 1
        return type("Lowered", (), {"compile": lambda self: _Compiled()})()

    def __call__(self, *a):
        raise AssertionError("an op-time reader never dispatches the segment")


class _Driver:
    ticks, carry, key, segment = 4, "carry", "key", _Segment()


def _ctx(traced=True):
    window = {"traced": {"seconds": 0.3, "steps": 5, "segments": 2}
              if traced else None}
    return harness.Context(driver=_Driver(), trace={"device_ops": OPS},
                           window=window, chips=1)


@pytest.mark.parametrize("metric", OP_TIME)
def test_op_time_reader_by_hand(metric):
    assert harness.load_reader(metric).read(_ctx()) == pytest.approx(
        WANT[metric], rel=1e-9)


@pytest.mark.parametrize("metric", OP_TIME + IDLE)
def test_reader_is_none_on_an_untraced_window(metric):
    assert harness.load_reader(metric).read(_ctx(traced=False)) is None


def test_the_compiler_made_are_filed_under_the_op_that_reads_them():
    attr = idle.made(_ctx())
    assert attr["compiler_made_by_consumer_path"] == pytest.approx({
        "tick_learn/learn_step/core_layer/kda_mix": 0.010,
        "tick_learn": 0.040 + 0.004})
    assert attr["compiler_made"][0][:3] == ("copy.1", 0.040, "copy")
    # and the classes of the accepted attribution are the same numbers
    plain = scopes.attribution(_ctx())
    assert {k: attr[k] for k in ("tick_s", "outside_tick_s", "by_path")} == {
        k: plain[k] for k in ("tick_s", "outside_tick_s", "by_path")}


def test_a_text_cached_before_the_scopes_is_compiled_again(monkeypatch, capsys):
    """The accepted attribution was made from an executable that an older
    tree cached: the text names `core_layer` and none of PR 37's scopes.  The
    first reader that needs one compiles past the cache, once; every reader
    after it reads the new text."""
    import re

    stale = re.sub(r"(kda_mix|core_norm|dense_ffn)/", "", MODULE)
    monkeypatch.setattr(_Compiled, "text", stale)
    asked = []
    monkeypatch.setattr(scopes, "compile_past_cache",
                        lambda drv: asked.append(drv) or MODULE)
    ctx = _ctx()
    assert harness.load_reader("net_trunk_device_ms").read(ctx) \
        == pytest.approx(WANT["net_trunk_device_ms"])
    assert scopes.ms_per(ctx, "steps", "learn_step", "core_norm") == 0.0
    for metric in ("core_norm_device_ms", "kda_mix_device_ms",
                   "core_unnamed_device_ms", "dense_ffn_device_ms"):
        assert harness.load_reader(metric).read(ctx) == pytest.approx(
            WANT[metric]), metric
    assert len(asked) == 1
    assert "compiling this program's module past the cache" \
        in capsys.readouterr().err


def test_a_scope_the_program_does_not_use_reads_none_not_zero(
        monkeypatch, capsys):
    """`r2d2-fused` has no core: asked all the same, the reader compiles
    past the cache once, finds no `kda_mix`, says so and returns None."""
    lstm = MODULE.replace("kda_mix/", "").replace("core_norm/", "")
    monkeypatch.setattr(_Compiled, "text", lstm)
    monkeypatch.setattr(scopes, "compile_past_cache", lambda drv: lstm)
    ctx = _ctx()
    assert harness.load_reader("kda_mix_device_ms").read(ctx) is None
    assert harness.load_reader("core_unnamed_device_ms").read(ctx) is None
    assert harness.load_reader("dense_ffn_device_ms").read(ctx) \
        == pytest.approx(WANT["dense_ffn_device_ms"])
    assert "nothing to read" in capsys.readouterr().err


def test_a_program_from_before_the_reduction_reports_nothing(monkeypatch):
    """Laid over the parent's checkout: `obs/device_scopes.py` has neither
    `instruction_origins` nor the new constants.  The readers that need them
    return None, nothing is dispatched, and the readers of scopes the parent
    has read them there too."""
    monkeypatch.delattr(ds, "instruction_origins")
    monkeypatch.setattr(ds, "ALL_SCOPES", tuple(
        s for s in ds.ALL_SCOPES
        if s not in ("core_norm", "dense_ffn", "kda_mix")))
    ctx = _ctx()
    for metric in IDLE + ("compiler_made_device_ms", "core_norm_device_ms",
                          "dense_ffn_device_ms", "kda_mix_device_ms",
                          "core_unnamed_device_ms"):
        assert harness.load_reader(metric).read(ctx) is None, metric
    for metric in ("tick_learn_own_device_ms", "net_trunk_device_ms",
                   "optimizer_device_ms"):
        assert harness.load_reader(metric).read(ctx) == pytest.approx(
            WANT[metric])


@pytest.mark.parametrize("metric", OP_TIME + IDLE)
def test_entry_has_a_reader_and_lists_its_cells(metric):
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    names = [m["name"] for m in per_layer]
    # new entries stand last, so every accepted reader has run before them
    assert set(names[-12:]) == set(OP_TIME + IDLE)
    entry = per_layer[names.index(metric)]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"]) \
        == ("ms", "lower", "device_trace", "learn_steps_per_s")
    assert entry["workloads"] and set(entry["workloads"]) <= set(CELLS)
    assert os.path.isfile(
        os.path.join(harness.HERE, "readers", metric + ".py"))
    for cell in entry["workloads"]:
        assert metric in [m["name"] for m in
                          harness.metric_specs(cell, "per_layer")]


# ------------------------------------------- the second capture, for real

DEV, HOST, US = "/device:TPU:0", "/host:CPU", 1e3  # ns


def _pick(inst_scopes, origins, want, own=True):
    """An instruction of the tiny segment whose path is `want`."""
    return next(i for i, p in inst_scopes.items()
                if p == want and origins[i].own == own)


@pytest.fixture(scope="module")
def captured():
    """The tiny driver after its warm-up and a short window, a context over
    it, and the helper's second capture with made-up device events: two runs
    of the segment, each a tick `while` holding an act op, a learn op, an
    LSTM-scan op and a draw op with gaps of 2, 3, 5 and 7 us before them,
    a compiler-made op outside the tick first; 50 us between the runs."""
    from benchmarks.drivers.fused_r2d2 import Driver

    drv = Driver(tiny.r2d2_fields(), tiny.traffic("freeway-16lanes"),
                 2**31 + 7, 1)
    drv.warm_up()
    win = harness.measure(drv, 0.2)
    win["traced"] = {"seconds": 0.2, "steps": win["steps"],
                     "segments": win["segments"]}
    text = drv.segment.lower(drv.carry, drv.key).compile().as_text()
    inst, orig = ds.instruction_scopes(text), ds.instruction_origins(text)
    ops = {
        "outside": _pick(inst, orig, (), own=False),
        "act": next(i for i, p in inst.items() if p[:1] == ("tick_act",)),
        "learn": _pick(inst, orig, ("tick_learn", "learn_step")),
        "scan": _pick(inst, orig, ("tick_learn", "learn_step", "lstm_scan")),
        "draw": _pick(inst, orig, ("tick_learn", "replay_draw")),
    }
    name = ds.module_name(text)
    events = []
    for r, t0 in enumerate((0.0, 1050 * US)):
        at = lambda us: t0 + us * US  # noqa: E731
        events += [
            (DEV, ds.MODULES_LINE, f"{name}({r})", at(0), 1000 * US),
            (DEV, ds.OPS_LINE, f"%{ops['outside']} = f32[] copy()", at(0), 100 * US),
            (DEV, ds.OPS_LINE, "%while.9999 = () while()", at(100), 900 * US),
            (DEV, ds.OPS_LINE, f"%{ops['act']} = f32[] fusion()", at(102), 98 * US),
            (DEV, ds.OPS_LINE, f"%{ops['learn']} = f32[] fusion()", at(203), 97 * US),
            (DEV, ds.OPS_LINE, f"%{ops['scan']} = f32[] fusion()", at(305), 95 * US),
            (DEV, ds.OPS_LINE, f"%{ops['draw']} = f32[] fusion()", at(407), 593 * US),
            (HOST, "python3", "segment", at(-10), 1020 * US),
        ]
    before = (drv.segments, list(drv.spans), drv.key, int(drv.carry[0].step))
    ctx = harness.Context(driver=drv, spans=list(drv.spans), window=win,
                          trace={"device_ops": [[f"%{i} = f32[] x()", 1e-3]
                                                for i in ops.values()]},
                          chips=1)
    real = ds.load_capture
    ds.load_capture = lambda logdir: events
    try:
        row = idle.device_time(ctx)
    finally:
        ds.load_capture = real
    return drv, ctx, row, before


def test_the_second_capture_leaves_the_harness_s_state_alone(captured):
    drv, ctx, row, (segments, spans, key, step) = captured
    assert row is not None
    assert (drv.segments, drv.spans) == (segments, spans)
    assert drv.key is key
    # the carry went through two dispatches and came back: the cadence's
    # learn steps are in it, and the driver can dispatch on
    owed = drv.expected_steps(segments, segments + idle.CAPTURE_DISPATCHES)
    assert int(drv.carry[0].step) == step + owed == step + row["steps"]
    new_step, _outs, _k = drv.dispatch()
    assert new_step >= step + owed
    assert not os.path.exists(os.path.join(
        harness.OUT_DIR, f"idle_capture_{os.getpid()}"))
    assert idle.device_time(ctx) is row  # one capture, kept on the context


def test_the_capture_s_idle_closes_by_path(captured):
    _drv, _ctx_, row, _before = captured
    assert row["dispatches"] == 2 and row["ticks"] == 8
    idle_s = row["window_s"] - row["busy_s"]
    assert idle_s == pytest.approx((2 * (2 + 3 + 5 + 7) + 50) * 1e-6)
    assert idle.idle_seconds_of(row, lambda path: True) \
        + row["idle_between_dispatches_s"] == pytest.approx(idle_s, abs=1e-8)
    assert row["idle_between_dispatches_s"] == pytest.approx(50e-6)
    # the longest first: the one between the runs (under 1 ms: no host span
    # is looked up), then the 7 us before the draw, inside the tick's `while`
    assert row["idle_gaps"][0] == {"ms": pytest.approx(0.05)}
    assert row["idle_gaps"][1]["container"] == "while.9999"
    assert row["idle_gaps"][1]["path"] == "tick_learn/replay_draw"


@pytest.mark.parametrize("metric,total_us,count", [
    ("idle_learn_ms", 2 * (3 + 5 + 7), "steps"),
    ("idle_act_ms", 2 * 2, "ticks"),
    ("idle_outside_ms", 50, "dispatches"),
    ("idle_lstm_scan_ms", 2 * 5, "steps"),
])
def test_idle_reader_on_the_tiny_driver(captured, metric, total_us, count):
    _drv, ctx, row, _before = captured
    per = {"steps": row["steps"], "dispatches": 2, "ticks": 2 * 8}[count]
    assert harness.load_reader(metric).read(ctx) == pytest.approx(
        1e-3 * total_us / per, rel=1e-4)


def test_the_three_idle_classes_add_to_the_capture_s_idle(captured):
    _drv, ctx, row, _before = captured
    read = lambda m: harness.load_reader(m).read(ctx)  # noqa: E731
    total = (read("idle_learn_ms") * row["steps"]
             + read("idle_act_ms") * row["dispatches"] * row["ticks"]
             + read("idle_outside_ms") * row["dispatches"]) / 1e3
    assert total == pytest.approx(row["window_s"] - row["busy_s"], abs=1e-8)
