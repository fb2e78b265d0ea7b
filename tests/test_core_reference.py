"""Every core family (tests/core_families.py's table) against its plain
float32 reference, at tiny widths, float32 compute, seeded weights: the cases
that read the same for each.  What is a family's own (its mixer against the
published form, its rotation, its shares of the expert layer) is its
`test_<family>_core.py`'s."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.models.cores import zero_lanes

import core_families as cf
from core_families import close, grads_close
from ring_windows import aged

families = pytest.mark.parametrize("family", sorted(cf.FAMILIES))


@families
def test_sequence_pass_matches_the_reference_values_and_gradients(family):
    cc = cf.tiny_cc(family)
    core, stack, params, x, resets, state = cf.make(family, cc)
    w = jax.random.normal(
        jax.random.PRNGKey(4), (*x.shape[:2], core.kc.hidden))
    run, plain = cf.jitted(family, cc)
    prog = jax.jit(jax.value_and_grad(
        lambda p: jnp.sum(run(p, x, state, resets)[0] * w)))
    want = jax.jit(jax.value_and_grad(
        lambda p: jnp.sum(plain(p, x, resets) * w)))
    y = run(params, x, state, resets)[0]
    assert y.shape == (*x.shape[:2], cc["hidden_size"])
    close(y, plain(params, x, resets))
    grads_close(prog(params)[1], want(params)[1])


@pytest.mark.parametrize(
    "family", ["deepseek_v3", "lfm2_moe", "ouro", "qwen3_next"])
def test_burn_in_then_trained_slice_match_one_full_pass(family):
    """The learn step's two passes (burn-in, its final state stop-gradiented,
    then the trained slice from it) against the reference's one pass with its
    stop-gradient boundary: values, and the gradient of the trained slice.
    The slice's keys sit in the windows at slots that are not their absolute
    positions; the boundary falls inside a chunk of a delta-rule scan, and a
    short convolution's first steps read the burn-in's last from the tails.
    (Kimi-Linear's case takes the slice step by step, in its own file.)"""
    cc = cf.tiny_cc(family)
    burn, steps = 6, 14
    # (each file's own cuts: one in the burn-in, one in the slice)
    reset_at = ((0, 2), (1, 7 if family == "lfm2_moe" else 9))
    core, stack, params, x, resets, state = cf.make(
        family, cc, steps=steps, reset_at=reset_at)
    w = jax.random.normal(
        jax.random.PRNGKey(5), (x.shape[0], steps - burn, core.kc.hidden))
    run, plain = cf.jitted(family, cc)

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


@pytest.mark.parametrize("family,window,steps", [
    ("lfm2_moe", 12, 30), ("lfm2_moe", 40, 40), ("ouro", 12, 30),
    ("ouro", 40, 40), ("qwen3_next", 12, 30), ("qwen3_next", 120, 120)])
def test_act_ticks_match_the_sequence_pass_and_absolute_positions(
        family, window, steps):
    """Ticks of one step each from the empty state (one row of scores an
    attention layer, `kda_step` a delta-rule layer, one 3-tap sum a
    convolution layer with its tail carried tick to tick) against the
    program's own pass over the sequence and against the reference's
    absolute positions 0..T-1.  T = 2.5 W: every window rolls over twice,
    every key is rotated by the slot it sits in when it is used, a slot that
    changes with every tick.  T = W (120, the cells' sequence length, for
    Qwen3-Next): from the empty window that is full causal attention exactly,
    so the reference is not told of a window.  (DeepSeek-V3's and
    Kimi-Linear's ticks: their own files.)"""
    # (that case at one layer of each kind: 120 steps compile long)
    over = ({} if steps < 120
            else dict(full_attention_interval=2, layers_here=2))
    cc = cf.tiny_cc(family, window=window, **over)
    core, stack, params, x, resets, state = cf.make(
        family, cc, batch=2, steps=steps, reset_at=((0, 7), (1, 19), (1, 20)))
    run, plain = cf.jitted(family, cc)
    ticks, st = cf.ticks_from(run, params, x, resets, state)
    rolled = steps > window
    close(ticks, plain(params, x, resets, window=window if rolled else None))
    seq, seq_state = run(params, x, state, resets)
    close(ticks, seq)
    cf.states_close(st, seq_state, aged)
    if rolled:  # the window matters there: another window's pass differs
        assert float(jnp.abs(ticks - plain(
            params, x, resets, window=2 * window)).max()) > 1e-3


@pytest.mark.parametrize("family", ["lfm2_moe", "ouro", "qwen3_next"])
def test_a_cut_inside_a_sequence_equals_two_passes(family):
    """After a reset before step `cut` (one step from either end, where a
    pass is the actor's single step, and between; Qwen3-Next's chunks are 8
    steps, so each of its five cuts falls inside one) the outputs are those
    of two sequences, one that ends there and one that starts there: nothing
    of a window, a recurrent state or a convolution's tail crosses it."""
    cc = cf.tiny_cc(family)
    core, stack, params, x, _, state = cf.make(
        family, cc, batch=2, reset_at=())
    run, plain = cf.jitted(family, cc)
    none = jnp.zeros(x.shape[:2], bool)
    cuts = (1, 6, 10, 14, 19) if family == "qwen3_next" else (1, 10, 19)
    for cut in cuts:
        resets = none.at[:, cut].set(True)
        whole = run(params, x, state, resets)[0]
        close(whole, plain(params, x, resets))
        close(whole[:, :cut], run(params, x[:, :cut], state, none[:, :cut])[0])
        close(whole[:, cut:], run(params, x[:, cut:], state, none[:, cut:])[0])


@pytest.mark.parametrize("family", ["lfm2_moe", "ouro"])
def test_zero_lanes_returns_a_lane_to_the_initial_state(family):
    cc = cf.tiny_cc(family, window=12)
    core, stack, params, x, _, state = cf.make(
        family, cc, batch=2, reset_at=())
    run, _ = cf.jitted(family, cc)
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


@families
def test_a_core_file_imports_its_own_family_alone(family):
    """`cores._load` reads `model_type` first: a process that runs one core
    pays for that family's modules alone (the LFM2 core for its attention's
    home, models/ouro.py, Qwen3-Next for the delta-rule scan's), in a child
    process, so that this one's imports do not count."""
    fam = cf.FAMILIES[family]
    code = (
        "import sys\n"
        "from rainbow_iqn_apex_tpu.config import Config\n"
        "from rainbow_iqn_apex_tpu.models.cores import make_core\n"
        "make_core(Config(architecture='r2d2', core_config="
        f"'configs/cores/{fam.published}'))\n"
        "print(sorted(m.rsplit('.', 1)[1] for m in sys.modules if m.startswith("
        "'rainbow_iqn_apex_tpu.models.') and m.rsplit('.', 1)[1] in "
        "('kimi_linear', 'kda_tile', 'deepseek_v3', 'qwen3_next', 'ouro', "
        "'lfm2', 'laguna')))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=cf.ROOT, capture_output=True,
        text=True, timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == str(sorted(fam.imports))


@families
def test_the_two_copies_of_the_reference_are_the_same_text(family):
    fam = cf.FAMILIES[family]
    other = fam.reference.removeprefix("reference_") + ".py"
    with open(os.path.join(cf.HERE, fam.reference + ".py")) as a, open(
            os.path.join(cf.ROOT, "benchmarks", "references", other)) as b:
        assert a.read() == b.read()
