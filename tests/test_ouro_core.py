"""The Ouro core (models/ouro.py over models/mla_moe.py) against its plain
float32 reference (tests/reference_ouro_core.py), at tiny widths (2 layers run
3 times, 4 heads of 8), float32 compute, seeded weights; that the weights are
shared across the passes and the state is not; and the three accepted cores,
which run the same stack once with two norms a block."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.models import mla_moe
from rainbow_iqn_apex_tpu.models import ouro
from rainbow_iqn_apex_tpu.models.cores import (
    CORE_STATS,
    FAMILIES,
    reduce_stats,
    state_bytes_per_lane,
    zero_lanes,
)

import reference_ouro_core as ref
from ring_windows import aged

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TINY = os.path.join(HERE, "fixtures", "ouro_core_tiny.json")
FEATURES = 24  # what the trunk would feed; the input projection takes any


def tiny_cc(window=32, **over):
    """The reference attends over the whole sequence, so the window is as
    long as the sequences compared with it unless a test says otherwise."""
    with open(TINY) as f:
        cc = json.load(f)
    cc["assumed"]["attn_window"] = window
    cc.update(over)
    return cc


@functools.lru_cache(maxsize=None)
def _built(cc_json, seed):
    """(core, stack, params) of a configuration, every leaf random (the
    norms' scales too): one compiled init serves every test of it."""
    cc = json.loads(cc_json)
    core = ouro.OuroCore(ouro.OuroConfig.from_dict(cc), jnp.float32)
    k2, k3 = jax.random.split(jax.random.PRNGKey(seed))
    stack = mla_moe._Stack(core.kc, jnp.float32)
    params = jax.jit(stack.init)(
        k2, jnp.zeros((1, 2, FEATURES)), core.initial_state(1),
        jnp.zeros((1, 2), bool))["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(k3, len(leaves))
    leaves = [p + 0.1 * jax.random.normal(k, p.shape) if p.ndim == 1 else p
              for p, k in zip(leaves, keys)]
    return core, stack, jax.tree.unflatten(tree, leaves)


def make(cc, batch=3, steps=20, seed=0, reset_at=((0, 5), (1, 9), (1, 10))):
    core, stack, params = _built(json.dumps(cc, sort_keys=True), seed)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (batch, steps, FEATURES))
    resets = np.zeros((batch, steps), bool)
    for b, t in reset_at:
        if b < batch and t < steps:
            resets[b, t] = True
    return core, stack, params, x, jnp.asarray(resets), core.initial_state(batch)


def jitted(cc, stack):
    """(program, reference) as compiled functions of (params, x, state,
    resets) and (params, x, resets, burn=, window=)."""
    run = jax.jit(lambda p, x, st, r: stack.apply({"params": p}, x, st, r))
    plain = jax.jit(
        lambda p, x, r, burn=0, window=None: ref.core_forward(
            p, cc, x, r, burn=burn, window=window),
        static_argnames=("burn", "window"))
    return run, plain


def close(a, b, tol=2e-4):
    """Float32 on both sides, sums in another order (keys in window slots
    against keys in sequence order, 3 x 2 blocks deep): 2e-4 of the largest
    value is some thousand roundings of room."""
    a, b = np.asarray(a), np.asarray(b)
    scale = max(float(np.abs(b).max()), 1e-6)
    assert float(np.abs(a - b).max()) <= tol * scale, (
        float(np.abs(a - b).max()), scale)


def grads_close(g1, g2, tol=2e-3):
    """A gradient sums over every step, token and pass: ten times the
    values'."""
    assert jax.tree.structure(g1) == jax.tree.structure(g2)
    for a, c in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        close(a, c, tol)


def test_the_stack_is_two_dense_attention_layers_run_three_times():
    core, _, params, _, _, state = make(tiny_cc())
    kc = core.kc
    assert [m.layer_name for m in kc.mixers] == ["mha", "mha"]
    assert (kc.layers, kc.passes, kc.out_norms, kc.first_dense) == (2, 3, True, 2)
    assert (kc.experts, kc.top_k, kc.experts_here) == (0, 0, 0)
    # each layer's leaves stand once, whatever the number of passes
    assert sorted(params) == ["final_norm", "in_proj", "layer_1", "layer_2"]
    assert sorted(params["layer_1"]) == [
        "ffn", "ffn_norm", "ffn_out_norm", "mha", "mix_norm", "mix_out_norm"]
    assert sorted(params["layer_2"]["mha"]) == [
        "k_proj", "o_proj", "q_proj", "v_proj"]
    # the state has one entry a (pass, layer), every leaf led by the lanes
    assert sorted(state) == [f"pass_{r}_layer_{i}"
                             for r in (1, 2, 3) for i in (1, 2)]
    assert sorted(state["pass_2_layer_1"]) == ["head", "k", "v", "valid"]
    assert state["pass_3_layer_2"]["k"].shape == (3, 32, 4, 8)
    assert all(leaf.shape[0] == 3 for leaf in jax.tree.leaves(state))
    assert core.stat_names == ("attn_live_key_share", "loop_passes")


@pytest.mark.parametrize("passes", [1, 3, 4])
def test_the_parameter_tree_does_not_go_by_the_number_of_passes(passes):
    cc = tiny_cc(total_ut_steps=passes)
    core, _, params = _built(json.dumps(cc, sort_keys=True), 0)
    _, _, three = _built(json.dumps(tiny_cc(), sort_keys=True), 0)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(jnp.shape, three)
    assert len(core.initial_state(1)) == passes * 2


def test_sequence_pass_matches_the_reference_values_and_gradients():
    cc = tiny_cc()
    core, stack, params, x, resets, state = make(cc)
    w = jax.random.normal(jax.random.PRNGKey(4), (*x.shape[:2], core.kc.hidden))
    run, plain = jitted(cc, stack)
    prog = jax.jit(jax.value_and_grad(
        lambda p: jnp.sum(run(p, x, state, resets)[0] * w)))
    want = jax.jit(jax.value_and_grad(
        lambda p: jnp.sum(plain(p, x, resets) * w)))
    y = run(params, x, state, resets)[0]
    assert y.shape == (*x.shape[:2], cc["hidden_size"])
    close(y, plain(params, x, resets))
    grads_close(prog(params)[1], want(params)[1])


def test_a_shared_leafs_gradient_is_the_sum_over_its_untied_copies():
    """The reference with a copy of every layer's leaves a pass, the copies
    set equal: the program's gradient of a (shared) leaf is the sum of the
    gradients of its three copies, and no copy's gradient is another's."""
    cc = tiny_cc()
    core, stack, params, x, resets, state = make(cc)
    w = jax.random.normal(jax.random.PRNGKey(6), (*x.shape[:2], core.kc.hidden))
    layers = {n: v for n, v in params.items() if n.startswith("layer_")}
    untied = {r: jax.tree.map(jnp.copy, layers) for r in (1, 2, 3)}

    def loss(untied):
        y = ref.core_forward(
            params, cc, x, resets,
            layer_of=lambda r, l: untied[r][f"layer_{l}"])
        return jnp.sum(y * w)

    copies = jax.jit(jax.grad(loss))(untied)
    shared = jax.jit(jax.grad(lambda p: jnp.sum(
        stack.apply({"params": p}, x, state, resets)[0] * w)))(params)
    summed = jax.tree.map(lambda a, b, c: a + b + c, *copies.values())
    grads_close({n: shared[n] for n in layers}, summed)
    first, last = (np.asarray(copies[r]["layer_1"]["mha"]["q_proj"]["kernel"])
                   for r in (1, 3))
    assert np.abs(first - last).max() > 1e-3 * np.abs(first).max()


def test_burn_in_then_trained_slice_match_one_full_pass():
    """The learn step's two passes (burn-in, its final state stop-gradiented,
    then the trained slice from it) against the reference's one pass with its
    stop-gradient boundary: values, and the gradient of the trained slice.
    The slice's keys sit in every (pass, layer) window at slots that are not
    their absolute positions."""
    cc = tiny_cc()
    burn, steps = 6, 14
    core, stack, params, x, resets, state = make(
        cc, steps=steps, reset_at=((0, 2), (1, 9)))
    w = jax.random.normal(
        jax.random.PRNGKey(5), (x.shape[0], steps - burn, core.kc.hidden))
    run, plain = jitted(cc, stack)

    def prog(p):
        _, st = run(p, x[:, :burn], state, resets[:, :burn])
        st = jax.lax.stop_gradient(st)
        y = run(p, x[:, burn:], st, resets[:, burn:])[0]
        return jnp.sum(y * w), y

    def want(p):
        y = plain(p, x, resets, burn=burn)[:, burn:]
        return jnp.sum(y * w), y

    (_, y), grads = jax.jit(jax.value_and_grad(prog, has_aux=True))(params)
    (_, y_ref), grads_ref = jax.jit(jax.value_and_grad(want, has_aux=True))(
        params)
    close(y, y_ref)
    grads_close(grads, grads_ref)


@pytest.mark.parametrize("window,steps", [(12, 30), (40, 40)])
def test_act_ticks_match_the_sequence_pass_and_absolute_positions(
        window, steps):
    """Ticks of one step each from the empty state (one row of scores a
    (pass, layer)) against the program's own pass over the sequence and
    against the reference's absolute positions 0..T-1.  T = 2.5 W: each of the
    six windows rolls over twice, every key is rotated by the slot it sits in
    when it is used, a slot that changes with every tick.  T = W: from the
    empty window that is full causal attention exactly, so the reference is
    not told of a window."""
    cc = tiny_cc(window=window)
    core, stack, params, x, resets, state = make(
        cc, batch=2, steps=steps, reset_at=((0, 7), (1, 19), (1, 20)))
    run, plain = jitted(cc, stack)
    st, ys = state, []
    for t in range(steps):
        y, st = run(params, x[:, t:t + 1], st, resets[:, t:t + 1])
        ys.append(y)
    ticks = jnp.concatenate(ys, axis=1)
    rolled = steps > window
    close(ticks, plain(params, x, resets, window=window if rolled else None))
    seq, seq_state = run(params, x, state, resets)
    close(ticks, seq)
    for a, c in zip(jax.tree.leaves(aged(st)),
                    jax.tree.leaves(aged(seq_state))):
        close(a, c)
    if rolled:  # the window matters there: another window's pass differs
        assert float(jnp.abs(ticks - plain(
            params, x, resets, window=2 * window)).max()) > 1e-3


def test_a_cut_inside_a_sequence_equals_two_passes():
    """After a reset before step `cut` the outputs are those of two
    sequences, one that ends there and one that starts there: nothing of any
    (pass, layer) window crosses it."""
    cc = tiny_cc()
    core, stack, params, x, _, state = make(cc, batch=2, reset_at=())
    run, plain = jitted(cc, stack)
    none = jnp.zeros(x.shape[:2], bool)
    for cut in (1, 10, 19):  # one step from either end, and the middle
        resets = none.at[:, cut].set(True)
        whole = run(params, x, state, resets)[0]
        close(whole, plain(params, x, resets))
        close(whole[:, :cut], run(params, x[:, :cut], state, none[:, :cut])[0])
        close(whole[:, cut:], run(params, x[:, cut:], state, none[:, cut:])[0])


def test_zero_lanes_returns_a_lane_to_the_initial_state_in_every_window():
    cc = tiny_cc(window=12)
    core, stack, params, x, _, state = make(cc, batch=2, reset_at=())
    run, _ = jitted(cc, stack)
    none = jnp.zeros(x.shape[:2], bool)
    y0, warm = run(params, x, state, none)
    assert all(float(jnp.abs(leaf[1]).max()) > 0
               for leaf in jax.tree.leaves(warm))
    cut = zero_lanes(warm, jnp.asarray([1, 0], jnp.uint8))
    for a, z in zip(jax.tree.leaves(cut), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(z[1]))
    y1 = run(params, x, cut, none)[0]
    close(y1[1], y0[1], 1e-6)  # lane 1 starts over
    assert float(jnp.abs(y1[0] - y0[0]).max()) > 1e-3  # lane 0 remembers


@pytest.mark.parametrize("shift", [0, 7, 100000])
def test_whole_head_rotation_against_the_published_form(shift):
    """The program's `rotate_halves` over a whole head against the
    reference's `u cos + rotate_half(u) sin`; and a score depends on the
    difference of the two positions alone (a common shift changes nothing,
    which is what lets the windows carry un-rotated keys)."""
    theta, d = 1e6, 128
    kq, kk = jax.random.split(jax.random.PRNGKey(shift))
    q = jax.random.normal(kq, (2, 9, 3, d))
    k = jax.random.normal(kk, (2, 9, 3, d))
    pos = jnp.arange(9)
    # float32 angles at position 1e5 are good to about 0.01 rad: the reason
    # the program keeps every position under W + T
    far = shift >= 1000
    prog = lambda u, s: mla_moe.rotate_halves(u, pos + s, theta)  # noqa: E731
    close(prog(q, shift), ref.rope(q, pos + shift, theta),
          1e-2 if far else 1e-6)
    score = lambda a, b: jnp.einsum(  # noqa: E731
        "bthd,bshd->bhts", a, b, precision=jax.lax.Precision.HIGHEST)
    close(score(prog(q, shift), prog(k, shift)),
          score(ref.rope(q, pos, theta), ref.rope(k, pos, theta)),
          3e-2 if far else 1e-5)


@pytest.mark.parametrize("steps,filled,lane,share", [
    (40, 0, False, 820 / (40 * 40)),  # the burn-in from a sequence's start
    (80, 40, False, (80 * 40 + 3240) / (80 * 120)),  # the trained slice after
    (1, 120, True, 1.0),  # a warmed actor's tick: written first, its ring whole
])
def test_live_key_share_and_loop_passes_of_the_learn_steps_two_passes(
        steps, filled, lane, share):
    """`attn_live_key_share`: the share of score columns the mask leaves, the
    mean over the six (pass, layer) uses, at the published window and
    sequence lengths (tiny widths); `loop_passes`: how often the weights
    were used."""
    cc = tiny_cc(window=120)
    core, stack, params, _, _, state = make(cc, batch=1, steps=2, reset_at=())
    x = jax.random.normal(jax.random.PRNGKey(1), (1, filled + steps, FEATURES))
    none = jnp.zeros((1, filled + steps), bool)
    if not lane:  # the learner's passes: from a sequence's zero-slot start
        state = core.from_stored(jnp.zeros((1, 0)), jnp.zeros((1, 0)))
    if filled:
        _, state = jitted(cc, stack)[0](
            params, x[:, :filled], state, none[:, :filled])
    _, sown = jax.jit(lambda p, x, st, r: stack.apply(
        {"params": p}, x, st, r, mutable=[CORE_STATS]))(
            params, x[:, filled:], state, none[:, filled:])
    assert len(sown[CORE_STATS]["layer_2"]["mha"]["attn_live_key_share"]) == 3
    stats = reduce_stats(sown)
    assert float(stats["attn_live_key_share"]) == pytest.approx(share, rel=1e-6)
    assert float(stats["loop_passes"]) == 3.0
    assert not [n for n in stats if n.startswith("moe_")]


def test_the_published_file_reads_the_published_sizes():
    with open(os.path.join(ROOT, "configs", "cores", "ouro_2_6b.json")) as f:
        cc = json.load(f)
    kc = ouro.OuroConfig.from_dict(cc)
    assert [m.layer_name for m in kc.mixers] == ["mha"] * 4
    assert (kc.hidden, kc.passes, kc.out_norms, kc.first_dense, kc.dense_width,
            kc.eps, kc.in_proj) == (2048, 4, True, 4, 5632, 1e-6, True)
    assert (kc.attn_heads, kc.attn_kv_heads, kc.attn_head_dim, kc.window,
            kc.rope_theta) == (16, 16, 128, 120, 1e6)
    # 16 (pass, layer) windows of 120 keys and values [16, 128], their
    # validity and the ring's head, float32: 31.5 MB a lane
    assert state_bytes_per_lane(ouro.OuroCore(kc)) == 16 * (
        2 * 120 * 16 * 128 + 120 + 1) * 4 == 31_465_024
    for key, bad in (("use_sliding_window", True),
                     ("rope_scaling", {"type": "yarn"}),
                     ("early_exit_threshold", 0.5),
                     ("layer_types", ["sliding_attention"] * 48)):
        with pytest.raises(ValueError, match="not written"):
            ouro.OuroConfig.from_dict({**cc, key: bad})


def _family_core(family, fixture):
    import importlib

    with open(os.path.join(HERE, "fixtures", fixture)) as f:
        cc = json.load(f)
    module, reader, core_cls = FAMILIES[family]
    mod = importlib.import_module("rainbow_iqn_apex_tpu.models." + module)
    return cc, getattr(mod, core_cls)(getattr(mod, reader).from_dict(cc),
                                      jnp.float32)


@pytest.mark.parametrize("family,fixture", [
    ("kimi_linear", "kimi_core_tiny.json"),
    ("deepseek_v3", "deepseek_v3_core_tiny.json"),
    ("qwen3_next", "qwen3_next_core_tiny.json"),
])
def test_the_accepted_cores_run_the_stack_once_with_two_norms_a_block(
        family, fixture):
    """`passes` 1 and no output norms are `CoreConfig`'s defaults, which the
    three accepted readers leave alone: their state is keyed by the layer,
    their blocks hold the two pre-norms, and they list no `loop_passes` (the
    pinned trees of tests/test_qwen3_next_core.py hold them leaf for leaf)."""
    cc, core = _family_core(family, fixture)
    assert (core.kc.passes, core.kc.out_norms) == (1, False)
    state = jax.eval_shape(lambda: core.initial_state(2))
    assert sorted(state) == [f"layer_{i}" for i in range(1, core.kc.layers + 1)]
    width = cc["hidden_size"] if core.kc.in_proj else 2304
    shapes = jax.eval_shape(
        lambda k, x, s, r: mla_moe._Stack(core.kc, jnp.float32).init(
            k, x, s, r)["params"],
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((2, 3, width), jnp.float32),
        state, jax.ShapeDtypeStruct((2, 3), jnp.bool_))
    norms = {n for i in range(1, core.kc.layers + 1)
             for n in shapes[f"layer_{i}"] if n.endswith("norm")}
    assert norms == {"mix_norm", "ffn_norm"}
    assert "loop_passes" not in core.stat_names
    assert set(core.moe_stat_names) <= set(core.stat_names)


@pytest.mark.parametrize("family,fixture,passes", [
    ("kimi_linear", "kimi_core_tiny.json", 1),
    ("deepseek_v3", "deepseek_v3_core_tiny.json", 1),
    ("qwen3_next", "qwen3_next_core_tiny.json", 1),
    ("ouro", "ouro_core_tiny.json", 3),
])
def test_only_a_stack_run_several_times_wears_loop_pass(
        family, fixture, passes):
    """`loop_pass` stands round a pass only where there is more than one: a
    stack run once keeps the scope paths it had (`.../learn_step/core_layer`,
    which the accepted readers match whole: `core_unnamed_device_ms` sums
    the paths that hold `core_layer` and no scope it does not know)."""
    from rainbow_iqn_apex_tpu.obs import device_scopes as ds

    cc, core = _family_core(family, fixture)
    assert core.kc.passes == passes
    stack = mla_moe._Stack(core.kc, jnp.float32)
    width = cc["hidden_size"] if core.kc.in_proj else 2304
    args = (jax.ShapeDtypeStruct((2, 3, width), jnp.float32),
            jax.eval_shape(lambda: core.initial_state(2)),
            jax.ShapeDtypeStruct((2, 3), jnp.bool_))
    params = jax.eval_shape(
        lambda k, *a: stack.init(k, *a)["params"], jax.random.PRNGKey(0),
        *args)
    text = jax.jit(lambda p, *a: stack.apply({"params": p}, *a)).lower(
        params, *args).as_text(debug_info=True)
    assert ds.CORE_LAYER in text and ds.CORE_NORM in text
    # (this test's own name is in the text's locations: hence the "/")
    assert (f"{ds.LOOP_PASS}/{ds.CORE_LAYER}" in text) == (passes > 1)
    assert (f"{ds.LOOP_PASS}/" in text) == (passes > 1)


def test_one_pass_and_no_output_norms_is_the_plain_pre_norm_stack():
    """The Ouro mixer in the accepted cores' block: `passes` 1 and
    `out_norms` False give the pre-norm residual stack with the state keyed
    by the layer, written out here from the reference's pieces."""
    cc = tiny_cc(total_ut_steps=1)
    core, _, params, x, resets, _ = make(cc)
    kc = dataclasses.replace(core.kc, out_norms=False)
    plain_core = ouro.OuroCore(kc, jnp.float32)
    stack = mla_moe._Stack(kc, jnp.float32)
    state = plain_core.initial_state(x.shape[0])
    assert sorted(state) == ["layer_1", "layer_2"]
    pre = jax.tree.map(lambda a: a, params)
    for i in (1, 2):
        for name in ("mix_out_norm", "ffn_out_norm"):
            del pre[f"layer_{i}"][name]
    y = stack.apply({"params": pre}, x, state, resets)[0]
    eps = cc["rms_norm_eps"]
    h = ref.plain_dot(x, pre["in_proj"]["kernel"])
    for i in (1, 2):
        lp = pre[f"layer_{i}"]
        h = h + ref.attention(lp["mha"], cc, ref.norm(h, lp["mix_norm"], eps),
                              resets, 0, ref.plain_dot)
        h = h + ref.swiglu(lp["ffn"], ref.norm(h, lp["ffn_norm"], eps),
                           ref.plain_dot)
    close(y, ref.norm(h, pre["final_norm"], eps))


def test_a_core_file_imports_its_own_family_alone():
    """`cores._load` reads `model_type` first: a process that runs the Ouro
    core imports neither the delta-rule scan nor its kernels (a child
    process, so that this one's imports do not count)."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from rainbow_iqn_apex_tpu.config import Config\n"
        "from rainbow_iqn_apex_tpu.models.cores import make_core\n"
        "make_core(Config(architecture='r2d2', core_config="
        "'configs/cores/ouro_2_6b.json'))\n"
        "print(sorted(m.rsplit('.', 1)[1] for m in sys.modules if m.startswith("
        "'rainbow_iqn_apex_tpu.models.') and m.rsplit('.', 1)[1] in "
        "('kimi_linear', 'kda_tile', 'deepseek_v3', 'qwen3_next', 'ouro')))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "['ouro']"


def test_the_two_copies_of_the_reference_are_the_same_text():
    with open(os.path.join(HERE, "reference_ouro_core.py")) as a, open(
            os.path.join(ROOT, "benchmarks", "references",
                         "ouro_core.py")) as b:
        assert a.read() == b.read()
