"""obs/device_scopes: the names the fused programs wrap their work in, read
back out of the compiled module, and device time put down to them.

The two segment fixtures compile the tiny fused R2D2 and IQN programs once;
every scope of the table in docs/OBSERVABILITY.md that a program uses has to
name at least one instruction of its compiled text.
"""

import re

import jax
import pytest

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.obs import device_scopes as ds

R2D2_SCOPES = (
    ds.TICK_ACT, ds.TICK_ENV, ds.TICK_APPEND, ds.TICK_LEARN, ds.REPLAY_DRAW,
    ds.REPLAY_GATHER, ds.REPLAY_WRITEBACK, ds.LEARN_STEP, ds.NET_TRUNK,
    ds.LSTM_SCAN, ds.OPTIMIZER, ds.LSTM_INPUT,
)
IQN_SCOPES = (
    ds.TICK_ACT, ds.TICK_ENV, ds.TICK_APPEND, ds.TICK_LEARN, ds.REPLAY_DRAW,
    ds.REPLAY_GATHER, ds.REPLAY_WRITEBACK, ds.LEARN_STEP, ds.NET_TRUNK,
    ds.IQN_HEAD, ds.OPTIMIZER,
)
COMMON = dict(
    env_id="jaxgame:catch", compute_dtype="float32", history_length=2,
    hidden_size=32, batch_size=8, multi_step=2, gamma=0.9,
    num_envs_per_actor=4, anakin_segment_ticks=4, learner_devices=1, seed=3,
)


def _r2d2_text() -> str:
    from rainbow_iqn_apex_tpu import train_anakin_r2d2 as prog
    from rainbow_iqn_apex_tpu.envs.device_games import make_device_game
    from rainbow_iqn_apex_tpu.ops.r2d2 import init_r2d2_state
    from rainbow_iqn_apex_tpu.replay import device_sequence as dseq

    cfg = Config(architecture="r2d2", lstm_size=16, r2d2_burn_in=2,
                 r2d2_seq_len=6, r2d2_overlap=2, memory_capacity=8 * 64,
                 learn_start=64, frames_per_learn=2, **COMMON)
    game = make_device_game("catch")
    seq_total, stride, capacity, _ = prog._seq_geometry(cfg)
    replay = dseq.DeviceSequenceReplay(
        capacity=capacity, seq_len=seq_total, frame_shape=game.frame_shape,
        lstm_size=cfg.lstm_size, lanes=cfg.num_envs_per_actor, stride=stride)
    segment = prog.build_fused_r2d2_segment(
        cfg, game, replay,
        dseq.build_device_r2d2_learn(cfg, game.num_actions, replay))
    key = jax.random.PRNGKey(0)
    carry = jax.eval_shape(
        lambda k: prog.init_fused_r2d2_carry(
            cfg, game,
            init_r2d2_state(cfg, game.num_actions, k, game.frame_shape),
            replay.init_state(), k), key)
    return segment.lower(carry, key).compile().as_text()


def _iqn_text() -> str:
    from rainbow_iqn_apex_tpu import train_anakin as prog
    from rainbow_iqn_apex_tpu.envs.device_games import make_device_game
    from rainbow_iqn_apex_tpu.ops.learn import init_train_state
    from rainbow_iqn_apex_tpu.replay.device import (
        DeviceReplay,
        build_device_learn,
    )

    cfg = Config(num_cosines=8, num_tau_samples=4, num_tau_prime_samples=4,
                 num_quantile_samples=4, memory_capacity=256, learn_start=32,
                 frames_per_learn=4, **COMMON)
    game = make_device_game("catch")
    lanes = cfg.num_envs_per_actor
    replay = DeviceReplay(
        lanes=lanes, seg=cfg.memory_capacity // lanes,
        frame_shape=game.frame_shape, history=cfg.history_length,
        n_step=cfg.multi_step, gamma=cfg.gamma)
    segment = prog.build_fused_segment(
        cfg, game, replay, build_device_learn(cfg, game.num_actions, replay))
    key = jax.random.PRNGKey(0)
    carry = jax.eval_shape(
        lambda k: prog.init_fused_carry(
            cfg, game, replay,
            init_train_state(cfg, game.num_actions, k,
                             (*game.frame_shape, cfg.history_length)),
            replay.init_state(), k), key)
    return segment.lower(carry, key).compile().as_text()


@pytest.fixture(scope="module")
def texts():
    return {"r2d2": _r2d2_text(), "iqn": _iqn_text()}


@pytest.mark.parametrize(
    "program,scope",
    [("r2d2", s) for s in R2D2_SCOPES] + [("iqn", s) for s in IQN_SCOPES])
def test_compiled_segment_names_every_scope(texts, program, scope):
    paths = ds.instruction_scopes(texts[program]).values()
    assert any(scope in p for p in paths), (
        f"no instruction of the compiled {program} segment is in {scope!r}")


@pytest.mark.parametrize("program,scope", [
    ("r2d2", ds.LSTM_SCAN), ("r2d2", ds.NET_TRUNK), ("iqn", ds.IQN_HEAD)])
def test_backward_pass_resolves_to_its_scope(texts, program, scope):
    """An op of the backward pass carries its scope wrapped by autodiff
    (`transpose(jvp(lstm_scan))`); the path still names the scope, inside
    the learn step inside the learning tick."""
    ops = [n for n in re.findall(r'op_name="([^"]*)"', texts[program])
           if "transpose(jvp(" in n and scope in ds.scope_path(n)]
    assert ops
    path = ds.scope_path(ops[0])
    assert path[0] == ds.TICK_LEARN and ds.LEARN_STEP in path
    assert path.index(ds.LEARN_STEP) < path.index(scope)


@pytest.mark.parametrize("side,shapes", [
    # the learn step's four scans: online and target, burn-in 2 and slice 6,
    # batch 8, lstm 16 (4m = 64), 2,304 features
    ("jvp(", {"f32[16,64]", "f32[48,64]"}),  # z_x = x W_x, before each loop
    ("transpose(jvp(", {"f32[2304,64]", "f32[48,2304]"}),  # x^T dz, dx
])
def test_the_lstms_input_products_stand_outside_its_loop_under_lstm_input(
        texts, side, shapes):
    """PR 39 took the LSTM's input products out of the time loop: forward
    and backward they resolve to `tick_learn/learn_step/lstm_scan/lstm_input`
    (so `lstm_scan_device_ms` still reads them), none is in a `while` body,
    and no `dot` the loops keep has a 2,304-wide operand."""
    rows, _caller = ds._parse(texts["r2d2"])
    shape_of = {inst: shape.split("{")[0] for inst, _c, shape, *_ in rows}
    # (result shape, every shape the dot touches, op_name) of the core's dots
    dots = [(shape_of[inst], [shape_of[inst]] + [
                shape_of.get(a, "") for a in operands], op_name)
            for inst, _c, _s, opcode, op_name, operands in rows
            if opcode == "dot" and op_name
            and ds.LSTM_SCAN in ds.scope_path(op_name)]
    products = {result: op_name for result, _touched, op_name in dots
                if ds.LSTM_INPUT in ds.scope_path(op_name)
                and "/learn_step/" + side in op_name}
    assert set(products) == shapes
    for op_name in products.values():
        assert ds.scope_path(op_name) == (
            ds.TICK_LEARN, ds.LEARN_STEP, ds.LSTM_SCAN, ds.LSTM_INPUT)
        assert "while" not in op_name.split(ds.LSTM_SCAN)[1]
    in_loop = [touched for _r, touched, op_name in dots
               if "while" in op_name.split(ds.LSTM_SCAN)[-1]]
    assert in_loop and not any("2304" in s for t in in_loop for s in t)
    assert all("f32[16,64]" in t for t in in_loop)  # they read W_h [m, 4m]


@pytest.mark.parametrize("op_name,want", [
    ("jit(segment)/while/body/tick_learn/cond/branch_1_fun/while/body/"
     "learn_step/transpose(jvp(lstm_scan))/mul",
     ("tick_learn", "learn_step", "lstm_scan")),
    ("jit(f)/while/body/closed_call/tick_learn/transpose(jvp(learn_step))/"
     "lstm_scan/while/body/add", ("tick_learn", "learn_step", "lstm_scan")),
    ("jit(segment)/while/body/tick_act/R2D2Net/net_trunk/conv_general_dilated",
     ("tick_act", "net_trunk")),
    ("jit(learn_step)/jit(main)/mul", ()),  # a jitted function is no scope
    ("jit(segment)/while/body/vmap(transpose(jvp(replay_gather/x)))/gather",
     ("replay_gather",)),
    ("", ()),
])
def test_scope_path(op_name, want):
    assert ds.scope_path(op_name) == want


HLO = """HloModule jit_segment, entry_computation_layout={()->f32[]}

%fused_computation (p: f32[4]) -> f32[4] {
  %inner.1 = f32[4]{0} add(%p, %p), metadata={op_name="jit(segment)/while/body/tick_learn/learn_step/jvp(lstm_scan)/add"}
}

%wide.while_body.sunk (arg: (s32[], u8[9])) -> (s32[], u8[9]) {
  %copy.415 = u8[9]{0} copy(%gte.1)
  %while.9 = (s32[]) while(%t), condition=%cond.9, body=%nested_body
}

%nested_body (arg: (s32[])) -> (s32[]) {
  ROOT %copy-done.11 = u8[9]{0} copy-done(%cs)
}

ENTRY %main (arg0: u8[9]) -> (f32[4]) {
  %copy.284 = u8[6554,120,80,80]{1,3,2,0} copy(%arg0)
  %copy.300 = f32[4]{0} copy(%x), metadata={op_name="jit(segment)/jit(main)/while"}
  %fusion.403 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(segment)/while/body/tick_learn/learn_step/transpose(jvp(lstm_scan))/mul" source_file="x.py" source_line=3}
  %fusion.7 = f32[4]{0} fusion(%x), kind=kLoop, metadata={op_name="jit(segment)/while/body/tick_learn/replay_draw/cumsum"}
  %gather.2 = f32[4]{0} gather(%x), metadata={op_name="jit(segment)/while/body/tick_act/net_trunk/conv"}
  %while.134 = (s32[], u8[9]) while(%t), condition=%cond.1, body=%wide.while_body.sunk, metadata={op_name="jit(segment)/while/body/tick_learn/replay_gather/gather"}
  ROOT %tuple.9 = (f32[4]{0}) tuple(%fusion.403)
}
"""


def test_instruction_scopes_by_hand():
    got = ds.instruction_scopes(HLO)
    assert got["copy.284"] == ()  # known to the module, named by nobody
    assert got["copy.300"] == ()
    assert got["fusion.403"] == ("tick_learn", "learn_step", "lstm_scan")
    assert got["inner.1"] == ("tick_learn", "learn_step", "lstm_scan")
    assert got["gather.2"] == ("tick_act", "net_trunk")
    assert got["tuple.9"] == ()
    # made by the compiler inside the loop a gather became: the gather's,
    # through a loop of the compiler's own too
    assert got["copy.415"] == got["while.134"] == ("tick_learn", "replay_gather")
    assert got["while.9"] == got["copy-done.11"] == ("tick_learn", "replay_gather")
    assert not {"fused_computation", "main", "nested_body"} & set(got)


def test_attribute_is_exact_by_hand():
    ops = [
        ["%copy.284 = u8[6554,120,80,80]{1,3,2,0:T(8,128)(4,1)} copy(u8[...", 0.5],
        ["%fusion.403 = f32[4]{0} fusion(f32[4]{0} %x), kind=kLoop", 0.25],
        ["%fusion.7 = f32[4]{0} fusion(...)", 0.125],
        ["%gather.2 = f32[4]{0} gather(...)", 0.0625],
        ["%fusion.999 = f32[] fusion()", 0.03125],  # not in the module
        ["%copy.300", 0.015625],
    ]
    a = ds.attribute(ops, ds.instruction_scopes(HLO))
    assert a["total_s"] == sum(t for _n, t in ops)
    # three disjoint classes that add up to the input
    assert a["tick_s"] + a["outside_tick_s"] + a["unresolved_s"] == a["total_s"]
    assert a["outside_tick_s"] == 0.5 + 0.015625
    assert a["unresolved_s"] == 0.03125
    assert a["unresolved"] == [("fusion.999", 0.03125)]
    assert a["outside"][0] == ("copy.284", 0.5)
    # a nested path counts in every scope it names
    assert a["by_scope"] == {
        "tick_learn": 0.25 + 0.125, "learn_step": 0.25, "lstm_scan": 0.25,
        "replay_draw": 0.125, "tick_act": 0.0625, "net_trunk": 0.0625}


def test_every_named_scope_in_the_program_comes_from_device_scopes():
    """`grep named_scope rainbow_iqn_apex_tpu/`: every use names a constant
    of obs/device_scopes.py, and every constant is used."""
    import pathlib

    root = pathlib.Path(ds.__file__).resolve().parents[1]
    used = set()
    for path in root.rglob("*.py"):
        if path.name == "device_scopes.py":
            continue
        for arg in re.findall(r"named_scope\(([^)]*)\)", path.read_text()):
            assert arg.startswith("device_scopes."), (path, arg)
            used.add(getattr(ds, arg.split(".", 1)[1]))
    assert used == set(ds.ALL_SCOPES)


# ------------------------------------------ a capture reduced: 'device_time'

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6  # ns


def _capture():
    """One run of `jit_segment` on one chip: a 10 ms ring copy outside the
    tick, then the scan's `while` 30 ms long holding a 4 ms act op, a 2 ms
    wait, and an 8 ms LSTM op; then 3 ms idle and a run of a program nobody
    registered.  The host's `segment` span ends at 30 ms."""
    return [
        (DEV, ds.MODULES_LINE, "jit_segment(123)", 0.0, 40 * MS),
        (DEV, ds.OPS_LINE, "%copy.284 = u8[9]{0} copy(...)", 0.0, 10 * MS),
        (DEV, ds.OPS_LINE, "%while.1 = () while(...)", 10 * MS, 30 * MS),
        (DEV, ds.OPS_LINE, "%gather.2 = f32[4]{0} gather(...)", 10 * MS, 4 * MS),
        (DEV, ds.OPS_LINE, "%fusion.403 = f32[4]{0} fusion(...)", 16 * MS, 8 * MS),
        (DEV, ds.MODULES_LINE, "jit_other(7)", 43 * MS, 1 * MS),
        (DEV, ds.OPS_LINE, "%fusion.403 = f32[] fusion()", 43 * MS, 1 * MS),
        (DEV, "Steps", "0", 0.0, 44 * MS),
        (HOST, "python3", "segment", 0.0, 30 * MS),
        (HOST, "python3", "PjitFunction(segment)", 0.0, 1 * MS),
    ]


HLO_RUN = HLO.replace(
    "ROOT %tuple.9",
    '%while.1 = () while(%x), metadata={op_name="jit(segment)/while"}\n'
    "  ROOT %tuple.9")


def test_reduce_events_by_hand():
    r = ds.reduce_events(_capture(), [HLO_RUN], {"segment", "learn_step"})
    assert r["chips"] == 1 and r["dispatches"] == 1
    assert r["window_s"] == pytest.approx(0.044)
    # busy: copy 10 + gather 4 + fusion 8 + the other program's op 1
    assert r["busy_s"] == pytest.approx(0.023)
    assert r["idle_share"] == pytest.approx(100 * (1 - 23 / 44))
    assert r["programs"]["jit_segment"] == {"runs": 1, "device_ms": 40.0}
    # the while's self time (30 - 4 - 8) is outside the tick with the copy
    assert r["outside_tick_s"] == pytest.approx(0.010 + 0.018)
    assert r["tick_s"] == pytest.approx(0.012)
    # the same instruction name in a program nobody registered is unresolved
    assert r["unresolved_s"] == pytest.approx(0.001)
    assert r["total_s"] == pytest.approx(
        r["tick_s"] + r["outside_tick_s"] + r["unresolved_s"])
    assert r["by_scope"]["lstm_scan"] == pytest.approx(0.008)
    assert r["by_path"]["tick_act/net_trunk"] == pytest.approx(0.004)
    assert ds.seconds(r, ds.TICK_LEARN, ds.LSTM_SCAN) == pytest.approx(0.008)
    assert ds.seconds(r, ds.TICK_ACT, ds.LSTM_SCAN) == 0.0
    # gaps over 1 ms between innermost ops, longest first, by the innermost
    # span that covers their middle: 24-43 ms (the middle is after `segment`
    # ended) and 14-16 ms
    assert [(round(g["ms"], 6), g["span"]) for g in r["idle_gaps"]] == [
        (19.0, "no span"), (2.0, "segment")]
    assert r["idle_gap_ms_by_span"] == pytest.approx(
        {"segment": 2.0, "no span": 19.0})


# ---------------- the other half of the account: idle by scope path, and
# the instructions the compiler made

HLO_MADE = """HloModule jit_segment, entry_computation_layout={()->f32[]}

%body (arg: (s32[], u8[9])) -> (s32[], u8[9]) {
  %gte.1 = u8[9]{0} get-tuple-element(%arg), index=1
  %copy.415 = u8[9]{0:T(8,128)} copy(%gte.1), backend_config={"estimated_cycles":"100"}
  %bitcast.3 = u8[3,3]{1,0} bitcast(%copy.415)
  %fusion.20 = f32[3]{0} fusion(%bitcast.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(segment)/while/body/tick_learn/cond/branch_1_fun/learn_step/core_layer/kda_mix/dot_general" stack_frame_id=3}
  %copy.416 = f32[3]{0} copy(%fusion.20)
  ROOT %tuple.5 = (s32[], f32[3]) tuple(%gte.0, %copy.416)
}

ENTRY %main (arg0: u8[9]) -> (f32[4]) {
  %copy.284 = u8[9]{0:T(8,128)(4,1)} copy(%arg0)
  %while.134 = (s32[], u8[9]{0:T(8,128)}) while(%copy.284), condition=%cond.1, body=%body, metadata={op_name="jit(segment)/while/body/tick_learn/cond"}
  %copy.9 = f32[4]{0} copy(%gte.9)
  ROOT %tuple.9 = (f32[4]{0}) tuple(%copy.9)
}
"""
KDA_MIX_PATH = ("tick_learn", "learn_step", "core_layer", "kda_mix")


@pytest.mark.parametrize("inst,own,opcode,shape,consumer", [
    # in a called computation: read by a bitcast read by a named fusion
    ("copy.415", False, "copy", "u8[9]{0:T(8,128)}", KDA_MIX_PATH),
    ("bitcast.3", False, "bitcast", "u8[3,3]{1,0}", KDA_MIX_PATH),
    # read only by the root, which has no name: the caller's path stays
    ("copy.416", False, "copy", "f32[3]{0}", ("tick_learn",)),
    # in the entry: no scope of its own, and the named `while` reads it
    ("copy.284", False, "copy", "u8[9]{0:T(8,128)(4,1)}", ("tick_learn",)),
    ("copy.9", False, "copy", "f32[4]{0}", ()),
    ("fusion.20", True, "fusion", "f32[3]{0}", KDA_MIX_PATH),
    ("while.134", True, "while", "(s32[], u8[9]{0:T(8,128)})",
     ("tick_learn",)),
])
def test_instruction_origins_by_hand(inst, own, opcode, shape, consumer):
    assert ds.instruction_origins(HLO_MADE)[inst] == ds.Origin(
        own, opcode, shape, consumer)


def test_origins_leave_the_scopes_as_they_were():
    """`instruction_scopes` keeps its answer (a compiler-made instruction
    inherits its caller's path, an entry copy has none), on the module of
    the tests above too, whose operands carry no `%`-less surprises."""
    scopes = ds.instruction_scopes(HLO_MADE)
    assert scopes["copy.415"] == scopes["copy.416"] == ("tick_learn",)
    assert scopes["copy.284"] == scopes["copy.9"] == ()
    assert set(ds.instruction_origins(HLO)) == set(ds.instruction_scopes(HLO))
    assert ds.instruction_origins(HLO)["copy.415"] == ds.Origin(
        False, "copy", "u8[9]{0}", ("tick_learn", "replay_gather"))


_LEARN = "jit(segment)/while/body/tick_learn/cond/branch_1_fun/learn_step/"
HLO_LABELLED = f"""HloModule jit_segment, entry_computation_layout={{()->f32[]}}

%branch.clone (arg: (s32[8], bf16[64,32], bf16[8,32,16])) -> (f32[64,16]) {{
  %gte.sizes = s32[8]{{0}} get-tuple-element(%arg), index=0, metadata={{op_name="{_LEARN}closed_call"}}
  %ragged-dot-metadata.1 = (s32[9]{{0}}, s32[12]{{0}}) custom-call(%gte.sizes), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-metadata"}}
  %gte.m = s32[9]{{0}} get-tuple-element(%ragged-dot-metadata.1), index=0
  %fusion.rows = bf16[64,32]{{1,0}} fusion(%arg), kind=kLoop, metadata={{op_name="{_LEARN}transpose(jvp(core_layer))/moe_experts/cond/branch_0_fun/convert_element_type"}}
  %copy.k = bf16[8,32,16]{{2,1,0}} copy(%fusion.rows)
  %ragged-dot-none.7 = f32[64,16]{{1,0}} custom-call(%gte.m, %fusion.rows, %copy.k), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %fusion.norm = f32[] fusion(%ragged-dot-none.7), kind=kInput, metadata={{op_name="{_LEARN}optimizer/reduce_sum"}}
  %ragged-dot-none.8 = f32[64,16]{{1,0}} custom-call(%arg), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %bitcast.8 = f32[1024]{{0}} bitcast(%ragged-dot-none.8)
  %fusion.after = f32[] fusion(%bitcast.8), kind=kInput, metadata={{op_name="{_LEARN}core_layer/moe_experts/cond/branch_0_fun/mul"}}
  %ragged-dot-none.9 = f32[64,16]{{1,0}} custom-call(%arg), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  ROOT %tuple.5 = (f32[64,16]) tuple(%ragged-dot-none.9)
}}

ENTRY %main (arg0: u8[9]) -> (f32[4]) {{
  %conditional.74 = (f32[64,16]) conditional(%i, %t), branch_computations={{%branch.clone}}
  %conditional.95 = (f32[4]) conditional(%p, %t), branch_computations={{%learn}}, metadata={{op_name="jit(segment)/while/body/tick_learn/cond"}}
  ROOT %tuple.9 = (f32[4]{{0}}) tuple(%conditional.95)
}}
"""
MOE_BACK = ("tick_learn", "learn_step", "core_layer", "moe_experts")


@pytest.mark.parametrize("inst,want", [
    # the gradient of a grouped product in a branch the compiler cloned
    # without a name: fed by the learn step's sizes (through its own
    # metadata call), by rows and, through a copy, kernels of `moe_experts`:
    # the innermost; that the optimizer's norm reads it first says nothing
    ("ragged-dot-none.7", MOE_BACK),
    # nothing with a name feeds it: the first that reads it, through a bitcast
    ("ragged-dot-none.8", MOE_BACK),
    # neither: its caller's path, which here is nobody's
    ("ragged-dot-none.9", ()),
    ("ragged-dot-metadata.1", ("tick_learn", "learn_step")),
    ("fusion.norm", ("tick_learn", "learn_step", "optimizer")),
    ("copy.k", ()),  # no metadata at all: the caller's, as ever
])
def test_an_instruction_under_the_compilers_label_takes_its_feeders_path(
        inst, want):
    """The TPU compiler lowers `jax.lax.ragged_dot` to custom calls whose
    `op_name` is its own (`ragged-dot-none`: no `/`, so no name stack of the
    program): the grouped products of the expert layers stood outside every
    tick scope (PR 43: 19% of `lfm2-r2d2-fused`'s dispatch)."""
    assert ds.instruction_scopes(HLO_LABELLED)[inst] == want
    origin = ds.instruction_origins(HLO_LABELLED)[inst]
    # it bears metadata, so it is no instruction the compiler made
    assert origin.own == (inst != "copy.k")
    if origin.own:
        assert origin.consumer_path == want


def test_a_labelled_product_counts_in_its_layer_and_not_outside():
    ops = [["%ragged-dot-none.7 = f32[64,16]{1,0} custom-call(...)", 0.5],
           ["%ragged-dot-none.9 = f32[64,16]{1,0} custom-call(...)", 0.25]]
    a = ds.attribute(ops, ds.instruction_scopes(HLO_LABELLED),
                     ds.instruction_origins(HLO_LABELLED))
    assert ds.seconds(a, "learn_step", "moe_experts") == 0.5
    assert a["outside_tick_s"] == 0.25 and a["compiler_made_s"] == 0.0


def test_attribute_sums_the_compiler_made_by_consumer():
    ops = [["%copy.415 = u8[9]{0:T(8,128)} copy(u8[9]{0} %gte.1)", 0.5],
           ["%copy.416 = f32[3]{0} copy(f32[3]{0} %fusion.20)", 0.25],
           ["%copy.284 = u8[9]{0:T(8,128)(4,1)} copy(u8[9]{0} %arg0)", 0.125],
           ["%copy.9 = f32[4]{0} copy(...)", 0.0625],
           ["%fusion.20 = f32[3]{0} fusion(...)", 2.0],
           ["%fusion.999 = f32[] fusion()", 0.03125]]
    scopes = ds.instruction_scopes(HLO_MADE)
    a = ds.attribute(ops, scopes, ds.instruction_origins(HLO_MADE))
    assert a["tick_s"] + a["outside_tick_s"] + a["unresolved_s"] \
        == a["total_s"] == sum(t for _n, t in ops)
    assert a["compiler_made_s"] == 0.5 + 0.25 + 0.125 + 0.0625
    assert a["compiler_made_by_consumer_path"] == {
        "tick_learn/learn_step/core_layer/kda_mix": 0.5,
        "tick_learn": 0.25 + 0.125, ds.NO_SCOPE: 0.0625}
    assert a["compiler_made"][0] == (
        "copy.415", 0.5, "copy", "u8[9]{0:T(8,128)}",
        "tick_learn/learn_step/core_layer/kda_mix")
    # the copy in the loop counts under `tick_learn` alone as it did: no
    # accepted reading moves
    assert a["by_path"]["tick_learn"] == 0.5 + 0.25
    plain = ds.attribute(ops, scopes)
    assert plain["compiler_made_s"] is None and plain["compiler_made"] is None
    assert {k: v for k, v in plain.items() if not k.startswith("compiler")} \
        == {k: v for k, v in a.items() if not k.startswith("compiler")}


def _nested_capture():
    """One run of `jit_segment`: a 10 ms entry copy, then the scan's `while`
    (10-40 ms) holding a 4 ms act op and a nested `while` (15-30 ms) with a
    compiler-made copy, an LSTM fusion and, 500 ns after it, a draw fusion;
    3 ms after the run ends another program's op.  Idle: 14-16 ms (ended by
    the copy), 18-20 (by the LSTM fusion), 500 ns (by the draw), and
    29.0005-43 ms with no program's next op to end it."""
    return [
        (DEV, ds.MODULES_LINE, "jit_segment(123)", 0.0, 40 * MS),
        (DEV, ds.OPS_LINE, "%copy.284 = u8[9]{0} copy(...)", 0.0, 10 * MS),
        (DEV, ds.OPS_LINE, "%while.1 = () while(...)", 10 * MS, 30 * MS),
        (DEV, ds.OPS_LINE, "%gather.2 = f32[4]{0} gather(...)", 10 * MS, 4 * MS),
        (DEV, ds.OPS_LINE, "%while.134 = (s32[]) while(...)", 15 * MS, 15 * MS),
        (DEV, ds.OPS_LINE, "%copy.415 = u8[9]{0} copy(...)", 16 * MS, 2 * MS),
        (DEV, ds.OPS_LINE, "%fusion.403 = f32[4]{0} fusion(...)", 20 * MS, 8 * MS),
        (DEV, ds.OPS_LINE, "%fusion.7 = f32[4]{0} fusion(...)", 28 * MS + 500, 1 * MS),
        (DEV, ds.MODULES_LINE, "jit_other(7)", 43 * MS, 1 * MS),
        (DEV, ds.OPS_LINE, "%fusion.403 = f32[] fusion()", 43 * MS, 1 * MS),
        (HOST, "python3", "segment", 0.0, 30 * MS),
    ]


def test_idle_by_path_and_between_dispatches_close_the_window():
    r = ds.reduce_events(_nested_capture(), [HLO_RUN], {"segment"})
    assert r["busy_s"] == pytest.approx(0.026)  # 10 + 4 + 2 + 8 + 1 + 1
    gather = "tick_learn/replay_gather"  # the copy's inherited path
    assert r["idle_s_by_path"] == pytest.approx({
        gather: 0.002, "tick_learn/learn_step/lstm_scan": 0.002,
        "tick_learn/replay_draw": 500e-9})
    assert r["idle_between_dispatches_s"] == pytest.approx(0.014 - 500e-9)
    # the identity: every nanosecond of the window is busy or idle somewhere
    assert sum(r["idle_s_by_path"].values()) + r["idle_between_dispatches_s"] \
        == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-12)
    assert r["tick_s"] + r["outside_tick_s"] + r["unresolved_s"] \
        == pytest.approx(r["total_s"], rel=1e-12)
    gaps = r["idle_gaps"]
    assert gaps[0] == {"ms": pytest.approx(13.9995), "span": "no span"}
    assert sorted((g["op"], g["after"], g["container"], g["path"], g["span"])
                  for g in gaps[1:3]) == [
        ("copy.415", "gather.2", "while.134", gather, "segment"),
        ("fusion.403", "copy.415", "while.134",
         "tick_learn/learn_step/lstm_scan", "segment")]
    # a gap under 1 ms is listed too, without a host span
    assert gaps[3] == {"ms": pytest.approx(0.0005), "op": "fusion.7",
                       "after": "fusion.403", "container": "while.134",
                       "path": "tick_learn/replay_draw"}
    assert r["idle_gap_ms_by_span"] == pytest.approx(
        {"segment": 4.0, "no span": 13.9995})
    # the copy inside the nested loop is the compiler's, and `while.134`
    # reads nothing of it: its caller's path stands in for the consumer
    assert r["compiler_made_s"] == pytest.approx(0.012)
    assert r["compiler_made_by_consumer_path"] == pytest.approx(
        {ds.NO_SCOPE: 0.010, gather: 0.002})


def test_idle_without_a_registered_program_is_unresolved_not_lost():
    """The host-fed loops' capture before they registered their programs:
    the gaps inside a run land on `UNRESOLVED`, the identity still holds."""
    r = ds.reduce_events(_nested_capture(), [], {"segment"})
    assert set(r["idle_s_by_path"]) == {ds.UNRESOLVED}
    assert sum(r["idle_s_by_path"].values()) + r["idle_between_dispatches_s"] \
        == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-12)
    assert r["unresolved_s"] == pytest.approx(r["total_s"])


def test_two_programs_resolve_each_in_its_own_text():
    """The host-fed loops run two programs, and instruction names are unique
    within one module only: `fusion.3` of the act program is its own."""
    act = "\n".join([
        "HloModule jit_act_append, entry_computation_layout={()->f32[]}",
        "ENTRY %main () -> f32[] {",
        '  %fusion.3 = f32[] fusion(%a), metadata={op_name="jit(act_append)/net_trunk/conv"}',
        "}"])
    learn = "\n".join([
        "HloModule jit_learn, entry_computation_layout={()->f32[]}",
        "ENTRY %main () -> f32[] {",
        '  %fusion.3 = f32[] fusion(%a), metadata={op_name="jit(learn)/learn_step/optimizer/mul"}',
        "  %copy.4 = f32[] copy(%fusion.3)",
        "}"])
    events = [
        (DEV, ds.MODULES_LINE, "jit_act_append(1)", 0.0, 2 * MS),
        (DEV, ds.OPS_LINE, "%fusion.3 = f32[] fusion(...)", 0.0, 2 * MS),
        (DEV, ds.MODULES_LINE, "jit_learn(2)", 5 * MS, 9 * MS),
        (DEV, ds.OPS_LINE, "%fusion.3 = f32[] fusion(...)", 5 * MS, 4 * MS),
        (DEV, ds.OPS_LINE, "%copy.4 = f32[] copy(...)", 10 * MS, 4 * MS),
        (HOST, "python3", "act_append", 0.0, 3 * MS),
        (HOST, "python3", "learn_step", 3 * MS, 12 * MS),
    ]
    r = ds.reduce_events(events, [act, learn], {"act_append", "learn_step"})
    # a host-fed loop's programs have no tick: their scopes are outside it
    assert r["by_path"] == {} and r["outside_by_path"] == pytest.approx({
        "net_trunk": 0.002, "learn_step/optimizer": 0.004})
    assert r["unresolved_s"] == 0.0 and r["dispatches"] == 1
    assert r["outside"][-1] == ("jit_act_append:fusion.3", pytest.approx(0.002))
    assert r["outside_tick_s"] == pytest.approx(r["total_s"])
    assert r["idle_s_by_path"] == pytest.approx({ds.NO_SCOPE: 0.001})
    assert r["idle_between_dispatches_s"] == pytest.approx(0.003)
    assert r["idle_gap_ms_by_span"] == pytest.approx({"learn_step": 3.0})
    assert r["scoped_instructions"] == 2


def test_reduce_events_without_a_device_plane_is_none():
    assert ds.reduce_events(
        [(HOST, "python3", "segment", 0.0, 1.0)], [HLO], {"segment"}) is None


def test_device_time_row_is_valid(tmp_path, monkeypatch):
    """The row TraceWindow logs from a capture with a device plane passes the
    schema and the strict-JSON lint, and carries per-step milliseconds."""
    import json
    import sys

    from rainbow_iqn_apex_tpu.obs import MetricRegistry, Tracer, TraceWindow
    from rainbow_iqn_apex_tpu.obs.schema import validate_row
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    sys.path.insert(0, str(
        __import__("pathlib").Path(__file__).resolve().parents[1] / "scripts"))
    from lint_jsonl import lint_file

    monkeypatch.setattr(ds, "load_capture", lambda logdir: _capture())
    path = str(tmp_path / "m.jsonl")
    m = MetricsLogger(path, "r", echo=False)
    tracer = Tracer(MetricRegistry(), None, "learner")
    tw = TraceWindow(str(tmp_path / "trace"), 1, 2, logger=m, tracer=tracer)
    tw.add_program(lambda: HLO_RUN)
    with tracer.span("segment"):
        tw.step(1)
    tw.step(3)
    m.close()
    assert lint_file(path) == []
    rows = [json.loads(line) for line in open(path)]
    assert all(validate_row(r, require_known_kind=True) == [] for r in rows)
    (row,) = [r for r in rows if r["kind"] == "device_time"]
    assert row["step"] == 3 and row["steps"] == 2 and row["dispatches"] == 1
    assert row["scope_ms_per_step"]["lstm_scan"] == pytest.approx(4.0)
    assert row["path_ms_per_step"]["tick_act/net_trunk"] == pytest.approx(2.0)
    assert row["outside_tick_ms_per_dispatch"] == pytest.approx(28.0)
    assert row["unresolved_share"] == pytest.approx(100 * 1 / 41, abs=1e-3)
    assert row["idle_gaps"][0] == {"ms": 19.0, "span": "no span"}
    assert validate_row({k: v for k, v in row.items() if k != "steps"}) != []
    # and scripts/obs_report.py prints it
    from obs_report import aggregate, find_jsonl, load_rows, render

    text = render(aggregate(load_rows(find_jsonl(str(tmp_path)))[0]))
    assert "device_time: 2 learn steps, 1.0 dispatches" in text
    assert "scope lstm_scan: 4.0ms/learn step" in text
    assert "idle gaps over 1ms under no span: 19.0ms" in text


def test_device_time_row_carries_the_idle_and_compiler_made_keys(
        tmp_path, monkeypatch):
    """`TraceWindow.device_time` on the nested capture: idle by path a learn
    step and between dispatches add up to the row's window less its busy
    time; the compiler's instructions are listed with opcode and shape; the
    row is strict JSON and the report prints the new lines."""
    import json
    import sys

    from rainbow_iqn_apex_tpu.obs import MetricRegistry, Tracer, TraceWindow
    from rainbow_iqn_apex_tpu.obs.schema import validate_row
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    sys.path.insert(0, str(
        __import__("pathlib").Path(__file__).resolve().parents[1] / "scripts"))
    from obs_report import _device_time_lines

    monkeypatch.setattr(ds, "load_capture", lambda logdir: _nested_capture())
    tracer = Tracer(MetricRegistry(), None, "learner")
    tw = TraceWindow(str(tmp_path / "trace"), 1, 2, tracer=tracer)
    tw.add_program(lambda: HLO_RUN)
    with tracer.span("segment"):
        pass
    row = tw.device_time(2)
    gather = "tick_learn/replay_gather"
    assert row["idle_ms_by_path_per_step"] == pytest.approx({
        gather: 1.0, "tick_learn/learn_step/lstm_scan": 1.0,
        "tick_learn/replay_draw": 0.00025}, abs=1e-6)
    assert row["idle_between_dispatches_s"] == pytest.approx(0.0139995)
    assert 2e-3 * sum(row["idle_ms_by_path_per_step"].values()) \
        + row["idle_between_dispatches_s"] == pytest.approx(
            row["window_s"] - row["busy_s"], abs=1e-8)
    assert row["compiler_made_ms_per_dispatch"] == pytest.approx(12.0)
    assert row["compiler_made_ms_by_consumer_path_per_dispatch"] == \
        pytest.approx({ds.NO_SCOPE: 10.0, gather: 2.0})
    assert row["compiler_made"][0] == {
        "instruction": "copy.284", "ms": 10.0, "opcode": "copy",
        "shape": "u8[6554,120,80,80]{1,3,2,0}", "consumer": ds.NO_SCOPE}
    assert row["idle_gaps"][1]["container"] == "while.134"
    m = MetricsLogger(str(tmp_path / "m.jsonl"), "r", echo=False)
    m.log("device_time", step=3, **row)
    m.close()
    (logged,) = [json.loads(line) for line in open(tmp_path / "m.jsonl")]
    assert validate_row(logged, require_known_kind=True) == []
    text = "\n".join(_device_time_lines(logged))
    assert f"idle before {gather}: 1.0ms/learn step" in text
    assert "idle between dispatches: 0.0139995s" in text
    assert "compiler-made instructions: 12.0ms/dispatch" in text
    assert "copy.284 copy u8[6554,120,80,80]{1,3,2,0}: 10.0ms" in text


def _core_learn_step_names(fixture: str):
    """The `op_name`s of the R2D2 learn step lowered (not compiled) with the
    tiny core of `tests/fixtures/<fixture>`."""
    import os

    import jax.numpy as jnp

    from rainbow_iqn_apex_tpu.ops.r2d2 import (
        SequenceBatch,
        build_r2d2_learn_step,
        init_r2d2_state,
    )

    cfg = Config(
        env_id="jaxgame:freeway", architecture="r2d2", role="anakin",
        core_config=os.path.join(os.path.dirname(__file__), "fixtures", fixture),
        compute_dtype="float32", history_length=4, hidden_size=32,
        r2d2_burn_in=4, r2d2_seq_len=8, r2d2_overlap=4, batch_size=2,
        multi_step=2, gamma=0.9, learner_devices=1, seed=3)
    b, length, actions = 2, 12, 3
    key = jax.random.PRNGKey(0)
    ts = jax.eval_shape(
        lambda k: init_r2d2_state(cfg, actions, k, (80, 80)), key)
    shaped = jax.ShapeDtypeStruct
    seq = SequenceBatch(
        obs=shaped((b, length, 80, 80, 1), jnp.uint8),
        action=shaped((b, length), jnp.int32),
        reward=shaped((b, length), jnp.float32),
        done=shaped((b, length), bool), valid=shaped((b, length), bool),
        init_c=shaped((b, 0), jnp.float32), init_h=shaped((b, 0), jnp.float32),
        weight=shaped((b,), jnp.float32))
    lowered = jax.jit(build_r2d2_learn_step(cfg, actions)).lower(ts, seq, key)
    return set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))


@pytest.fixture(scope="module")
def core_paths():
    return {fixture: {ds.scope_path(n) for n in _core_learn_step_names(fixture)}
            for fixture in ("kimi_core_tiny.json", "deepseek_v3_core_tiny.json",
                            "qwen3_next_core_tiny.json")}


@pytest.mark.parametrize("fixture,scope", [
    ("kimi_core_tiny.json", ds.CORE_NORM),
    ("kimi_core_tiny.json", ds.DENSE_FFN),
    ("kimi_core_tiny.json", ds.KDA_MIX),
    ("deepseek_v3_core_tiny.json", ds.CORE_NORM),
    ("deepseek_v3_core_tiny.json", ds.DENSE_FFN),
    ("qwen3_next_core_tiny.json", ds.CORE_NORM),
])
def test_the_cores_name_their_norms_dense_ffn_and_kda_mixer(
        core_paths, fixture, scope):
    """The three names of PR 37 are constants of `ALL_SCOPES` and occur in
    the lowered learn step under `learn_step`, forward and backward; inside a
    layer they nest in `core_layer` (the stack's final norm stands after the
    last layer, outside it), and `kda_mix` is KDA but its scan."""
    assert scope in ds.ALL_SCOPES
    holding = [p for p in core_paths[fixture] if scope in p]
    # (a function the lowering outlines names its ops from its own root)
    assert any(ds.LEARN_STEP in p and ds.CORE_LAYER in p for p in holding)
    if scope == ds.CORE_NORM:  # final_norm
        assert any(ds.LEARN_STEP in p and ds.CORE_LAYER not in p
                   for p in holding)
    if scope == ds.KDA_MIX:
        assert not any(ds.KDA_SCAN in p for p in holding)
        assert any(ds.KDA_SCAN in p for p in core_paths[fixture])


@pytest.mark.parametrize("with_program", [False, True])
def test_trace_window_on_cpu_logs_no_device_time(tmp_path, with_program):
    """A real capture on the CPU backend holds no device plane: the window
    closes cleanly, the .xplane.pb is there, and no 'device_time' row is."""
    import json

    import jax.numpy as jnp

    from rainbow_iqn_apex_tpu.obs import MetricRegistry, Tracer, TraceWindow
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((8,))
    m = MetricsLogger(str(tmp_path / "m.jsonl"), "r", echo=False)
    tracer = Tracer(MetricRegistry(), m, "learner")
    tw = TraceWindow(str(tmp_path / "trace"), 1, 1, logger=m, tracer=tracer)
    if with_program:
        tw.add_program(lambda: f.lower(x).compile().as_text())
    tw.step(1)
    with tracer.span("learn_step"):
        f(x).block_until_ready()
    tw.close(2)
    m.close()
    assert not tw.active
    assert list((tmp_path / "trace").rglob("*.xplane.pb"))
    kinds = [json.loads(line)["kind"] for line in open(tmp_path / "m.jsonl")]
    assert "trace" in kinds and "device_time" not in kinds


def test_compile_counter_tells_compiles_from_cache_hits(tmp_path):
    """With a persistent cache: a jitted function called, the in-memory
    caches cleared, called again: one backend compile, one cache hit."""
    import jax.numpy as jnp

    from rainbow_iqn_apex_tpu.obs import MetricRegistry, install_compile_counter

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cc.reset_cache()
        reg = MetricRegistry()
        assert install_compile_counter(reg)
        compiles = reg.counter("jax_compiles_total", "jax")
        hits = reg.counter("jax_compile_cache_hits_total", "jax")
        x = jnp.arange(7, dtype=jnp.float32).block_until_ready()
        c0, h0 = compiles.get(), hits.get()

        def poly_unlike_any_other_in_the_suite(x):
            return (x * 3.25 + 1.5).sum() - x[0]

        jax.jit(poly_unlike_any_other_in_the_suite)(x).block_until_ready()
        assert (compiles.get() - c0, hits.get() - h0) == (1, 0)
        assert reg.histogram("jax_compile_s", "jax").snapshot()["count"] >= 1
        jax.clear_caches()
        jax.jit(poly_unlike_any_other_in_the_suite)(x).block_until_ready()
        assert (compiles.get() - c0, hits.get() - h0) == (1, 1)
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
