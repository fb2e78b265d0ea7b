"""The seven readers of device time by scope, on a hand-made context: a
`device_ops` list as the trace reduction gives it and a driver whose
`segment.lower(...).compile().as_text()` returns a short module text."""

import json
import os
import sys

import pytest

from benchmarks import harness, scopes

NEW = ("outside_tick_ms", "act_tick_device_ms", "replay_sample_device_ms",
       "priority_writeback_device_ms", "learn_device_ms",
       "lstm_scan_device_ms", "unattributed_share")

_BODY = "jit(segment)/jit(main)/while/body/"
MODULE = "\n".join([
    "HloModule jit_segment, entry_computation_layout={()->f32[]}",
    "ENTRY %main (ring: u8[9]) -> f32[] {",
    "  %copy.1 = u8[9]{0} copy(%ring)",  # the compiler's own, no metadata
    f'  %while.2 = () while(%t), metadata={{op_name="jit(segment)/jit(main)/while"}}',
    f'  %fusion.3 = f32[] fusion(%a), metadata={{op_name="{_BODY}tick_act/net_trunk/conv"}}',
    f'  %fusion.4 = f32[] fusion(%a), metadata={{op_name="{_BODY}tick_act/lstm_scan/while/body/dot"}}',
    f'  %fusion.5 = f32[] fusion(%a), metadata={{op_name="{_BODY}tick_env/add"}}',
    f'  %scatter.6 = f32[] scatter(%a), metadata={{op_name="{_BODY}tick_append/scatter"}}',
    f'  %fusion.7 = f32[] fusion(%a), metadata={{op_name="{_BODY}tick_learn/cond/branch_1_fun/while/body/replay_draw/cumsum"}}',
    f'  %gather.8 = f32[] gather(%a), metadata={{op_name="{_BODY}tick_learn/cond/branch_1_fun/while/body/replay_gather/gather"}}',
    f'  %fusion.9 = f32[] fusion(%a), metadata={{op_name="{_BODY}tick_learn/cond/branch_1_fun/while/body/learn_step/jvp(net_trunk)/conv"}}',
    f'  %fusion.10 = f32[] fusion(%a), metadata={{op_name="{_BODY}tick_learn/cond/branch_1_fun/while/body/learn_step/transpose(jvp(lstm_scan))/while/body/dot"}}',
    f'  %fusion.11 = f32[] fusion(%a), metadata={{op_name="{_BODY}tick_learn/cond/branch_1_fun/while/body/learn_step/optimizer/mul"}}',
    f'  %scatter.12 = f32[] scatter(%a), metadata={{op_name="{_BODY}tick_learn/cond/branch_1_fun/while/body/replay_writeback/scatter"}}',
    "}",
])
# self seconds over 2 traced dispatches of 4 ticks, holding 5 learn steps
OPS = [
    ["%copy.1 = u8[9]{0:T(8,128)(4,1)} copy(u8[9]{0} %ring)", 0.040],
    ["%while.2 = () while(() %t), condition=%c, body=%b", 0.002],
    ["%fusion.3 = f32[] fusion(f32[] %a), kind=kLoop", 0.0008],
    ["%fusion.4 = f32[] fusion(f32[] %a), kind=kLoop", 0.0004],
    ["%fusion.5 = f32[] fusion(f32[] %a), kind=kLoop", 0.0002],
    ["%scatter.6 = f32[] scatter(f32[] %a)", 0.0010],
    ["%fusion.7 = f32[] fusion(f32[] %a), kind=kLoop", 0.0005],
    ["%gather.8 = f32[] gather(f32[] %a)", 0.0015],
    ["%fusion.9 = f32[] fusion(f32[] %a), kind=kLoop", 0.030],
    ["%fusion.10 = f32[] fusion(f32[] %a), kind=kLoop", 0.020],
    ["%fusion.11 = f32[] fusion(f32[] %a), kind=kLoop", 0.005],
    ["%scatter.12 = f32[] scatter(f32[] %a)", 0.00025],
    ["%fusion.99 = f32[] fusion()", 0.001],  # not in the module's text
]
TOTAL = sum(t for _n, t in OPS)
WANT = {
    "outside_tick_ms": 1e3 * (0.040 + 0.002) / 2,
    "act_tick_device_ms": 1e3 * (0.0008 + 0.0004 + 0.0002 + 0.0010) / (2 * 4),
    "replay_sample_device_ms": 1e3 * (0.0005 + 0.0015) / 5,
    "priority_writeback_device_ms": 1e3 * 0.00025 / 5,
    "learn_device_ms": 1e3 * (0.030 + 0.020 + 0.005) / 5,
    "lstm_scan_device_ms": 1e3 * 0.020 / 5,  # not the act tick's 0.0004
    "unattributed_share": 100 * 0.001 / TOTAL,
}


class _Compiled:
    def as_text(self):
        return MODULE


class _Lowered:
    def compile(self):
        return _Compiled()


class _Segment:
    lowered = 0

    def lower(self, carry, key):
        _Segment.lowered += 1
        assert (carry, key) == ("carry", "key")
        return _Lowered()

    def __call__(self, *a):
        raise AssertionError("a reader never dispatches the segment")


class _Driver:
    ticks, carry, key, segment = 4, "carry", "key", _Segment()


def _ctx(traced=True, ops=OPS):
    window = {"traced": {"seconds": 0.3, "steps": 5, "segments": 2}
              if traced else None}
    return harness.Context(driver=_Driver(), trace={"device_ops": ops},
                           window=window, chips=1)


@pytest.mark.parametrize("metric", NEW)
def test_reader_by_hand(metric):
    assert harness.load_reader(metric).read(_ctx()) == pytest.approx(
        WANT[metric], rel=1e-9)


@pytest.mark.parametrize("metric", NEW)
def test_reader_is_none_on_an_untraced_window(metric):
    assert harness.load_reader(metric).read(_ctx(traced=False)) is None


def test_module_text_is_read_once_and_classes_add_up():
    ctx, before = _ctx(), _Segment.lowered
    for metric in NEW:
        harness.load_reader(metric).read(ctx)
    assert _Segment.lowered == before + 1
    attr = scopes.attribution(ctx)
    assert attr["tick_s"] + attr["outside_tick_s"] + attr["unresolved_s"] \
        == pytest.approx(TOTAL, rel=1e-12)


def test_a_cached_executable_without_scopes_is_compiled_again(monkeypatch):
    """The compile cache's key leaves metadata out: where the executable's
    text names no scope, the program's own module is compiled past the
    cache and its text is read instead."""
    import re

    bare = re.sub(r", metadata=\{[^}]*\}", "", MODULE)
    monkeypatch.setattr(_Compiled, "as_text", lambda self: bare)
    asked = []
    monkeypatch.setattr(scopes, "compile_past_cache",
                        lambda drv: asked.append(drv) or MODULE)
    ctx = _ctx()
    assert harness.load_reader("learn_device_ms").read(ctx) == pytest.approx(
        WANT["learn_device_ms"])
    assert harness.load_reader("outside_tick_ms").read(ctx) == pytest.approx(
        WANT["outside_tick_ms"])
    assert len(asked) == 1


def test_compile_past_cache_reads_the_program_s_own_scopes(tmp_path):
    """On the CPU: an executable cached under one scope name is what a
    program with another name loads; compiled past the cache, the text
    names the program's own scope, and the cache is in use again after."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}

    def make(name):
        def segment(carry, key):
            with jax.named_scope(name):
                return (carry * 3.25 + key).sum()
        return jax.jit(segment, donate_argnums=(0,))

    class Drv:
        carry, key = jnp.arange(7.0), jnp.float32(1.5)

    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cc.reset_cache()
        old = make("tick_before").lower(Drv.carry, Drv.key).compile()
        assert "tick_before" in old.as_text()
        Drv.segment = make("tick_learn")
        loaded = Drv.segment.lower(Drv.carry, Drv.key).compile().as_text()
        assert "tick_before" in loaded and "tick_learn" not in loaded
        anew = scopes.compile_past_cache(Drv)
        assert "tick_learn" in anew and "tick_before" not in anew
        assert jax.config.jax_enable_compilation_cache
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_no_learn_step_in_the_traced_window():
    ctx = _ctx()
    ctx.window["traced"]["steps"] = 0
    assert harness.load_reader("learn_device_ms").read(ctx) is None
    assert harness.load_reader("outside_tick_ms").read(ctx) is not None


def test_a_program_without_scopes_reports_nothing(monkeypatch):
    """Laid over a checkout from before the scopes, the readers find no
    `obs/device_scopes.py`: they return None and do not raise."""
    import rainbow_iqn_apex_tpu.obs as obs
    from rainbow_iqn_apex_tpu.obs import device_scopes  # noqa: F401

    monkeypatch.delattr(obs, "device_scopes")
    monkeypatch.setitem(
        sys.modules, "rainbow_iqn_apex_tpu.obs.device_scopes", None)
    for metric in NEW:
        assert harness.load_reader(metric).read(_ctx()) is None


@pytest.mark.parametrize("metric", NEW)
def test_entry_has_a_reader_and_lists_the_cell(metric):
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    entry = entries[metric]
    assert entry["workloads"] == ["r2d2-fused"]
    assert entry["source"] == "device_trace"
    assert entry["moves"] == "learn_steps_per_s"
    assert os.path.isfile(
        os.path.join(harness.HERE, "readers", metric + ".py"))
    assert metric in [m["name"] for m in
                      harness.metric_specs("r2d2-fused", "per_layer")]
