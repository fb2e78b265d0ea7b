"""The tile kernel of the KDA chunk's preparation (models/kda_tile.py) against
the plain `kda_chunked`, which is its definition: under `interpret=True` on
the CPU at small batch and head counts that keep d 128, chunk 40 and block 8;
the scalar form a one-wide gate takes (`_prep_scalar`) against the same
definition; the choice between the paths; where a device trace files each;
and the kernels and the scalar form compiled at the published widths for a
described v5e."""

import functools
import json
import math
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, SingleDeviceSharding

from rainbow_iqn_apex_tpu.models import kda_tile
from rainbow_iqn_apex_tpu.models import kimi_linear as kl
from rainbow_iqn_apex_tpu.models.cores import CORE_STATS, reduce_stats
from rainbow_iqn_apex_tpu.obs import device_scopes

B, H, D, CHUNK, BLOCK = 2, 2, 128, 40, 8


def inputs(steps, cuts, s0_scale, d=D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + steps), 8)
    unit = lambda z: z / jnp.linalg.norm(z, axis=-1, keepdims=True)  # noqa: E731
    shape = (B, steps, H, d)
    q, k = unit(jax.random.normal(ks[0], shape)), unit(
        jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    # a head's decay rate spans A_log's range; some steps' sums tie in float32
    g = -jnp.exp(jax.random.uniform(ks[3], (B, steps, H, 1), minval=-6.0,
                                    maxval=0.5)) * jax.random.uniform(ks[4], shape)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, steps, H)))
    resets = np.zeros((B, steps), bool)
    if cuts == "one":
        resets[np.arange(B), [steps // 3, steps - 7]] = True
    elif cuts == "random":
        resets = np.asarray(jax.random.uniform(ks[6], (B, steps)) < 0.08)
    seg = jnp.cumsum(jnp.asarray(resets, jnp.int32), axis=1)
    s0 = s0_scale * jax.random.normal(ks[7], (B, H, d, d))
    return q, k, v, g, beta, s0, seg


def _loss(q, k, v, g, beta, s0, seg, chunk, block):
    o, s = kl.kda_chunked(q, k, v, g, beta, seg, s0, chunk, block, jnp.float32)
    w = jnp.cos(0.37 * jnp.arange(o.size, dtype=jnp.float32)).reshape(o.shape)
    return (o * w).sum() + jnp.sin(s).sum(), (o, s)


def _value_and_grads(*args, chunk=CHUNK, block=BLOCK):
    return jax.value_and_grad(
        functools.partial(_loss, chunk=chunk, block=block),
        argnums=(0, 1, 2, 3, 4, 5), has_aux=True)(*args)


plain = jax.jit(_value_and_grads)


@jax.jit
def fused(*args):
    with mock.patch.object(kl, "kda_prep_fused", lambda *a: True):
        return _value_and_grads(*args)


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(float(np.abs(b).max()), 1e-6)
    assert float(np.abs(a - b).max()) <= tol * scale, (
        float(np.abs(a - b).max()), scale)


@pytest.mark.parametrize("s0_scale", [0.0, 0.3], ids=["zero_s0", "s0"])
@pytest.mark.parametrize("cuts", ["lockstep", "one", "random"])
@pytest.mark.parametrize("steps", [40, 80, 120, 100])
def test_kernel_matches_the_plain_path(steps, cuts, s0_scale):
    """Outputs, final state and the gradients with respect to q, k, v, g,
    beta and s0; 100 steps are padded to 120."""
    args = inputs(steps, cuts, s0_scale)
    (_, (o, s)), grads = plain(*args)
    with pltpu.force_tpu_interpret_mode():
        (_, (o_k, s_k)), grads_k = fused(*args)
    close(o_k, o)
    close(s_k, s)
    for got, want in zip(grads_k, grads):
        close(got, want)


@pytest.mark.parametrize("d,chunk,block", [(64, 40, 8), (128, 12, 4)])
def test_shapes_the_kernel_does_not_take_fall_back(d, chunk, block):
    """On a TPU at these shapes `kda_chunked` still runs the plain path (a
    kernel call would not lower here) and gives the plain path's numbers."""
    assert not kda_tile.takes(d, d, chunk, block)
    args = inputs(24, "random", 0.3, d=d)
    want = _value_and_grads(*args, chunk=chunk, block=block)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert kl.kda_prep_fused(D, D, CHUNK, BLOCK)
        assert not kl.kda_prep_fused(d, d, chunk, block)
        got = _value_and_grads(*args, chunk=chunk, block=block)
    jax.tree.map(functools.partial(close, tol=1e-6), got, want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("cuts", ["lockstep", "one", "random"])
@pytest.mark.parametrize("steps", [40, 80])
def test_scalar_form_gives_the_plain_preparations_six_outputs(
        steps, cuts, dtype):
    """`_prep_scalar` on a gate one channel wide against `_prep_plain` on
    the same gate broadcast over the channels, leaf by leaf, compared in
    float32: u, wk, qg, a_qk, k_end, s_keep (one wide, the value of every
    channel), in their dtypes.  In float32 only the order of rounding
    differs; on bfloat16 operands the decay multiplies the float32
    accumulator where the plain path rounds decayed operands, so those two
    leaves agree to bfloat16's last place."""
    q, k, v, g, beta, _, seg = inputs(steps, cuts, 0.0)
    g = g[..., :1]
    got = kl._prep_scalar(q, k, v, g, beta, seg, CHUNK, dtype)
    want = kl._prep_plain(q, k, v, jnp.broadcast_to(g, q.shape), beta, seg,
                          CHUNK, BLOCK, dtype)
    tol = 2e-5 if dtype == jnp.float32 else 2 ** -7
    for name, a, b in zip(("u", "wk", "qg", "a_qk", "k_end", "s_keep"),
                          got, want):
        assert a.dtype == b.dtype, name
        assert a.shape == (b.shape[:-1] + (1,) if name == "s_keep"
                           else b.shape), name
        close(jnp.broadcast_to(a.astype(jnp.float32), b.shape),
              b.astype(jnp.float32), tol)


def test_a_one_wide_gate_takes_the_scalar_form_on_every_platform():
    """The gate's width alone picks the scalar form: on the CPU, on a TPU
    where a channel-wide gate takes the kernel, and under a mesh.  The
    Qwen3-Next core counts it (and no tile); the Kimi-Linear core, whose
    gate is dk wide, has no such counter."""
    from rainbow_iqn_apex_tpu.models import qwen3_next as qn
    from rainbow_iqn_apex_tpu.parallel.mesh import traced_under

    path = lambda w: kl.kda_prep_path(w, D, D, CHUNK, BLOCK)  # noqa: E731
    assert (path(1), path(D)) == ("scalar", "plain")
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert (path(1), path(D)) == ("scalar", "tile")
        several = Mesh(np.array(jax.devices()[:4]), ("dp",))
        assert traced_under(several, lambda: (path(1), path(D)))() == (
            "scalar", "plain")
        # at shapes the kernel does not take, too
        assert kl.kda_prep_path(1, 64, 64, 12, 4) == "scalar"

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "fixtures", "qwen3_next_core_tiny.json")) as f:
        kc = qn.Qwen3NextConfig.from_dict(json.load(f))
    core = qn.Qwen3NextCore(kc, jnp.float32)
    x, resets = jnp.ones((2, 16, 24)), jnp.zeros((2, 16), bool)
    stack = kl._Stack(kc, jnp.float32)
    state = core.initial_state(2)
    params = stack.init(jax.random.PRNGKey(0), x, state, resets)["params"]
    _, sown = stack.apply({"params": params}, x, state, resets,
                          mutable=[CORE_STATS])
    stats = reduce_stats(sown)
    assert float(stats["kda_scalar_gate_share"]) == 1.0
    assert float(stats["kda_fused_tile_share"]) == 0.0
    assert core.stat_names[-1] == "kda_scalar_gate_share"
    assert "kda_scalar_gate_share" not in kl.KimiLinearCore.stat_names


def test_the_path_is_chosen_by_platform_mesh_and_shape():
    """CPU: plain, and the core counts no fused tile.  TPU: the kernel,
    unless the function is traced under a mesh of several devices (the
    `_sharded` builders and the apex learner trace their learn step so)."""
    from rainbow_iqn_apex_tpu.parallel.mesh import traced_under

    assert not kl.kda_prep_fused(D, D, CHUNK, BLOCK)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "fixtures", "kimi_core_tiny.json")) as f:
        kc = kl.KimiLinearConfig.from_dict({**json.load(f), "hidden_size": 32})
    core = kl.KimiLinearCore(kc, jnp.float32)
    x, resets = jnp.ones((2, 16, 32)), jnp.zeros((2, 16), bool)
    stack = kl._Stack(kc, jnp.float32)
    state = core.initial_state(2)
    params = stack.init(jax.random.PRNGKey(0), x, state, resets)["params"]
    _, sown = stack.apply({"params": params}, x, state, resets,
                          mutable=[CORE_STATS])
    assert float(reduce_stats(sown)["kda_fused_tile_share"]) == 0.0
    assert "kda_scalar_gate_share" not in reduce_stats(sown)
    assert "kda_fused_tile_share" in core.stat_names

    chosen = lambda: kl.kda_prep_fused(D, D, CHUNK, BLOCK)  # noqa: E731
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert chosen()
        several = Mesh(np.array(jax.devices()[:4]), ("dp",))
        assert not traced_under(several, chosen)()
        assert traced_under(Mesh(np.array(jax.devices()[:1]), ("dp",)),
                            chosen)()


def scan_in_scope(q, k, v, g, beta, s0, seg):
    with jax.named_scope(device_scopes.KDA_SCAN):
        return kl.kda_chunked(q, k, v, g, beta, seg, s0, CHUNK, BLOCK,
                              jnp.bfloat16)


def scan_grads(*args):
    """Gradients of every float input through `scan_in_scope`."""
    return jax.grad(lambda *z: sum(
        (y.astype(jnp.float32) ** 2).sum() for y in scan_in_scope(*z)),
        argnums=(0, 1, 2, 3, 4, 5))(*args)


def paths_of(text, opcode):
    scopes = device_scopes.instruction_scopes(text)
    return {scopes[line.split("=")[0].strip().lstrip("%").split()[-1]]
            for line in text.splitlines() if f" {opcode}(" in line}


def test_the_plain_preparation_is_filed_under_kda_prep():
    text = jax.jit(scan_in_scope).lower(
        *inputs(80, "one", 0.3)).compile().as_text()
    scopes = device_scopes.instruction_scopes(text)
    assert ("kda_scan", "kda_prep") in set(scopes.values())
    assert paths_of(text, "while") == {("kda_scan",)}


# ---------------------------------------------------------------------------
# compiled for a described TPU v5e, no chip attached (nothing runs)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("steps", [80, 120])
def test_kernels_compile_at_the_published_widths_and_carry_the_scope(
        one_chip, steps):
    """Forward and backward kernels lower for the chip at H 32, d 128, chunk
    40 (Mosaic refuses what interpret mode lets through: a slice of a mask, a
    misaligned block), and `instruction_scopes` files both custom calls under
    kda_scan/kda_prep, where `kda_scan_device_ms` and the `device_time` row
    find them."""
    b, h = 2, 32
    shaped = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, dt, sharding=one_chip)
    args = (*(shaped(b, steps, h, D) for _ in range(4)), shaped(b, steps, h),
            shaped(b, h, D, D), shaped(b, steps, dt=jnp.int32))

    with mock.patch.object(kl, "kda_prep_fused", lambda *a: True):
        text = jax.jit(scan_grads).lower(*args).compile().as_text()
    scopes = device_scopes.instruction_scopes(text)
    kernels = {name: path for name, path in scopes.items()
               if name.startswith("kda_tile")}
    assert {n.split(".")[0] for n in kernels} == {"kda_tile", "kda_tile_vjp"}
    assert set(kernels.values()) == {("kda_scan", "kda_prep")}, kernels


@pytest.mark.parametrize("steps,backward", [(80, True), (40, False)],
                         ids=["T80_grad", "T40_forward"])
def test_scalar_form_compiles_at_the_published_shapes_with_no_tile_kernel(
        one_chip, steps, backward):
    """The Qwen3-Next cell's scans (B 64, H 32, d 128, chunk 40; a learn
    step differentiates the 80-step slice and runs the 40-step burn-in
    forward) with a one-wide gate, compiled for the chip where a channel-wide
    gate would take the kernel: no `kda_tile` custom call, the preparation's
    ops under kda_scan/kda_prep, and no array the size of the per-channel
    plain path's pair products (`[.., C, C, d]`) or decayed copies
    (`[.., nb, C, d]`): the largest is the scan's stacked states."""
    import re

    b, h = 64, 32
    n, nb = steps // CHUNK, CHUNK // BLOCK
    shaped = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, dt, sharding=one_chip)
    args = (*(shaped(b, steps, h, D) for _ in range(3)),
            shaped(b, steps, h, 1), shaped(b, steps, h),
            shaped(b, h, D, D), shaped(b, steps, dt=jnp.int32))

    run = scan_grads if backward else scan_in_scope
    with mock.patch.object(kl, "kda_prep_fused", lambda *a: True):
        text = jax.jit(run).lower(*args).compile().as_text()
    assert "kda_tile" not in text
    scopes = device_scopes.instruction_scopes(text)
    prep = [name for name, path in scopes.items()
            if path[-2:] == ("kda_scan", "kda_prep")]
    assert len(prep) > 20, len(prep)
    results = re.findall(
        r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = \(?\w+\[([\d,]*)\]", text, re.M)
    assert len(results) > 200
    largest = max(math.prod(int(x) for x in dims.split(",") if x)
                  for dims in results)
    assert largest < b * n * h * nb * CHUNK * D, largest
    assert largest < b * n * h * CHUNK * CHUNK * D


def test_the_sequence_rings_loop_needs_no_ring_sized_temporary(one_chip):
    """`r2d2-fused`'s ring at its real size (6,553 sequences of 120 80x80
    frames, 5.03 GB; 16 lanes) under a donated scan of append, draw and
    assemble, compiled for the chip: with frames stored flat the loop runs in
    the ring's own layout.  A ring stored `[C+1, L, h, w]` is copied whole on
    entry to the scan and back on exit (5.4 GB of temporaries), and a flat
    ring gathered `frames[idx]` is cut into column strips on every learn step
    (3.5 GB); row by row the loop holds 0.18 GB.  It lives in this file
    because one file holds the suite's compiles for the described chip."""
    from rainbow_iqn_apex_tpu.replay.device_sequence import DeviceSequenceReplay

    lanes, length, cap, hw, lstm = 16, 120, 6553, (80, 80), 512
    replay = DeviceSequenceReplay(capacity=cap, seq_len=length, frame_shape=hw,
                                  lstm_size=lstm, lanes=lanes, stride=40)

    def loop(state, key):
        def tick(carry, k):
            s, seen = carry
            z = jnp.zeros((lanes,))
            s = replay.append(
                s, jax.random.bits(k, (lanes,) + hw, jnp.uint8),
                z.astype(jnp.int32), z, z > 0, z > 0,
                jnp.zeros((lanes, lstm)), jnp.zeros((lanes, lstm)))
            batch, _ = replay.assemble(s, replay.draw(s, k, 64),
                                       jnp.float32(0.5))
            return (s, seen + batch.obs.astype(jnp.int32).sum()), None

        return jax.lax.scan(tick, (state, jnp.int32(0)),
                            jax.random.split(key, 4))[0]

    shaped = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    state = shaped(jax.eval_shape(replay.init_state))
    ring_bytes = state.frames.size
    assert state.frames.shape == (cap + 1, length, hw[0] * hw[1])
    compiled = jax.jit(loop, donate_argnums=(0,)).lower(
        state, shaped(jax.eval_shape(lambda: jax.random.PRNGKey(0)))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < ring_bytes / 10
    ring_ops = {line.split(" = ")[1].split("(")[0].split(" ")[-1]
                for line in compiled.as_text().splitlines()
                if f" = u8[{cap + 1},{length}," in line}
    assert ring_ops.isdisjoint({"copy", "slice", "transpose"}), ring_ops


def test_the_learn_step_reads_the_first_conv_from_the_stored_frames(one_chip):
    """`r2d2-fused`'s learn step at its shapes (64 sequences of 120 single
    80x80 frames, LSTM 512, history 4) compiled for the chip.  Until the stem
    (`layers.StemConv`) the step wrote the batch's 49 MB of frames four times
    over as `u8[64,120,80,80,4]`, cast that, and turned the cast to the conv's
    layout in two `copy` of `bf16[...,80,80,4]`: 3.7 of its 16.1 ms.  Now no
    array holds the history per pixel, the step's temporaries are no more
    than they were (1,249,761,792 B), and the first conv is found under
    net_trunk/net_stem, where the `device_time` row prices it."""
    import re

    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.ops import r2d2 as ops

    cfg = Config(architecture="r2d2", compute_dtype="bfloat16", lstm_size=512,
                 hidden_size=512, history_length=4, r2d2_burn_in=40,
                 r2d2_seq_len=80, r2d2_overlap=40, batch_size=64, multi_step=5)
    b, length, hw, actions = 64, 120, (80, 80), 3
    shaped = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    sd = jax.ShapeDtypeStruct
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    state = jax.eval_shape(
        lambda k: ops.init_r2d2_state(cfg, actions, k, hw), key)
    assert state.params["ConvTrunk_0"]["Conv_0"]["kernel"].shape == (8, 8, 4, 32)
    batch = ops.SequenceBatch(
        obs=sd((b, length, *hw, 1), jnp.uint8),
        action=sd((b, length), jnp.int32), reward=sd((b, length), jnp.float32),
        done=sd((b, length), jnp.bool_), valid=sd((b, length), jnp.bool_),
        init_c=sd((b, 512), jnp.float32), init_h=sd((b, 512), jnp.float32),
        weight=sd((b,), jnp.float32))
    compiled = jax.jit(ops.build_r2d2_learn_step(cfg, actions)).lower(
        shaped(state), shaped(batch), shaped(key)).compile()
    text = compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes <= 1_249_761_792
    frame_bytes = b * length * hw[0] * hw[1]
    results = re.findall(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \(?(\w+)\[([\d,]*)\]\S* (\S+?)\(",
        text, re.M)
    assert len(results) > 1000
    largest_u8 = max(math.prod(int(d) for d in dims.split(",") if d)
                     for _, dtype, dims, _ in results if dtype == "u8")
    assert largest_u8 < 4 * frame_bytes, largest_u8
    stacked_copies = [name for name, dtype, dims, op in results
                      if op == "copy" and dtype == "bf16"
                      and dims.endswith(f",{hw[0]},{hw[1]},4")]
    assert not stacked_copies, stacked_copies
    scopes = device_scopes.instruction_scopes(text)
    first_convs = [
        device_scopes.instruction_name(line.strip().removeprefix("ROOT "))
        for line in text.splitlines()
        if " convolution(" in line and "/Conv_0/" in line]
    assert len(first_convs) >= 2, first_convs
    for name in first_convs:
        path = scopes[name]
        assert ("net_trunk", "net_stem") in set(zip(path, path[1:])), (
            name, path)
