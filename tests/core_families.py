"""What the core families' tests share (a library beside ring_windows.py, not
a test file): the table of the families, a family's tiny configuration built
once a process with its program and its plain reference as compiled functions,
the two comparisons, and the tiny `Config` and CLI runs of the training cases.

A family costs one row here, one file under tests/fixtures/, one plain
reference, and a `test_<family>_core.py` of what is its own.  The table names
a family's modules as strings and imports them on use: a process that tests
one family loads that family alone."""

import dataclasses
import functools
import importlib
import json
import os

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np

from rainbow_iqn_apex_tpu.models import cores, mla_moe

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRUNK_FEATURES = 2304  # what the trunk feeds a core at 80x80 frames


@dataclasses.dataclass(frozen=True)
class Family:
    """`name` is the files' `model_type`, the key of `cores.FAMILIES`."""

    name: str
    short: str  # tests/fixtures/<short>_core_tiny.json, the CLI cases' id
    reference: str  # the plain reference's module, in tests/
    loss_reference: str  # benchmarks/references/<this>.py holds its `loss_fn`
    # where the file keeps the attention windows' lengths: keys of its
    # `assumed`, or of the file itself after a dot; a family whose layers
    # have spans of their own names each, and the shared cases set them alike
    window_keys: tuple
    published: str  # its file under configs/cores/
    # the width fed to the stack: its input projection takes any; None for
    # the one family without (the trunk feeds its hidden size)
    features: int | None = 24
    # the two oldest families' files split one key three ways (x, the init,
    # the noise), the later ones two and take x from the next seed: kept, so
    # that every case sees the numbers it saw (fixtures/kimi_core_pinned.json
    # holds outputs of the first)
    three_way_keys: bool = False
    # which of models/'s family modules a process that runs the published
    # core imports
    imports: tuple = ()

    @property
    def tiny(self) -> str:
        return os.path.join(HERE, "fixtures", self.short + "_core_tiny.json")

    @property
    def published_path(self) -> str:
        return os.path.join(ROOT, "configs", "cores", self.published)

    def classes(self):
        """(configuration reader, core class), imported here."""
        module, reader, core = cores.FAMILIES[self.name]
        mod = importlib.import_module("rainbow_iqn_apex_tpu.models." + module)
        return getattr(mod, reader), getattr(mod, core)

    def core(self, cc, dtype=jnp.float32):
        reader, core = self.classes()
        return core(reader.from_dict(cc), dtype)

    @property
    def ref(self):
        return importlib.import_module(self.reference)

    @property
    def loss_fn(self):
        return importlib.import_module(
            "benchmarks.references." + self.loss_reference).loss_fn


FAMILIES = {f.name: f for f in (
    Family("kimi_linear", "kimi", "reference_kimi_linear_core", "r2d2_kimi",
           ("mla_window",), "kimi_linear_48b_a3b.json", features=None,
           three_way_keys=True, imports=("kda_tile", "kimi_linear")),
    Family("deepseek_v3", "deepseek_v3", "reference_deepseek_v3_core",
           "r2d2_kanana", ("mla_window",), "kanana_2_30b_a3b.json",
           three_way_keys=True, imports=("deepseek_v3",)),
    Family("qwen3_next", "qwen3_next", "reference_qwen3_next_core",
           "r2d2_qwen3_next", ("attn_window",), "qwen3_next_80b_a3b.json",
           imports=("kda_tile", "kimi_linear", "qwen3_next")),
    Family("ouro", "ouro", "reference_ouro_core", "r2d2_ouro",
           ("attn_window",), "ouro_2_6b.json", imports=("ouro",)),
    Family("lfm2_moe", "lfm2", "reference_lfm2_core", "r2d2_lfm2",
           ("attn_window",), "lfm2_8b_a1b.json", imports=("lfm2", "ouro")),
    Family("laguna", "laguna", "reference_laguna_core", "r2d2_laguna",
           ("attn_window", ".sliding_window"), "laguna_xs_2.json",
           imports=("laguna",)),
)}
# the CLI cases name a core by its fixture
CORES = {f.short: f.tiny for f in FAMILIES.values()}


# ------------------------------------------------- a tiny core, built once
def tiny_cc(family, window=32, **over):
    """The family's tiny file as a dict.  The reference attends over the
    whole sequence, so the window is as long as the sequences compared with
    it unless a test says otherwise."""
    fam = FAMILIES[family]
    with open(fam.tiny) as f:
        cc = json.load(f)
    if fam.features is None:
        cc["hidden_size"] = 32  # no trunk in front of the core here
    set_windows(fam, cc, window)
    cc.update(over)
    return cc


def set_windows(fam, cc, window):
    """Every attention window of `cc` at `window` slots."""
    for key in fam.window_keys:
        where = cc if key.startswith(".") else cc["assumed"]
        where[key.lstrip(".")] = window


def stack_width(family, cc):
    return FAMILIES[family].features or cc["hidden_size"]


def _keys(fam, seed):
    """(x's key, the init's, the noise's)."""
    if fam.three_way_keys:
        return tuple(jax.random.split(jax.random.PRNGKey(seed), 3))
    return (jax.random.PRNGKey(seed + 1),
            *jax.random.split(jax.random.PRNGKey(seed)))


def _as_key(cc):
    return json.dumps(cc, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _params(family, cc_json, seed):
    """The stack's parameters with every leaf random (the norms' scales, the
    decay's parameters and the routers' selection bias too).  They go
    neither by the batch, nor by the sequence's length, nor by the window's:
    one compiled init serves every test of a configuration."""
    fam, cc = FAMILIES[family], json.loads(cc_json)
    core = fam.core(cc)
    _, k_init, k_noise = _keys(fam, seed)
    params = jax.jit(mla_moe._Stack(core.kc, jnp.float32).init)(
        k_init, jnp.zeros((1, 2, stack_width(family, cc))),
        core.initial_state(1), jnp.zeros((1, 2), bool))["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(k_noise, len(leaves))
    leaves = [p + 0.1 * jax.random.normal(k, p.shape) if p.ndim == 1 else p
              for p, k in zip(leaves, keys)]
    return jax.tree.unflatten(tree, leaves)


def built(family, cc, seed=0):
    """(core, stack, params) of a configuration; the tree's dicts are the
    caller's own, the arrays every caller's."""
    core = FAMILIES[family].core(cc)
    # (the init wants some window; the parameters are the same under any)
    any_window = {**cc, "assumed": dict(cc["assumed"])}
    set_windows(FAMILIES[family], any_window, 12)
    params = _params(family, _as_key(any_window), seed)
    return (core, mla_moe._Stack(core.kc, jnp.float32),
            jax.tree.map(lambda a: a, params))


def make(family, cc, batch=3, steps=20, seed=0,
         reset_at=((0, 5), (1, 9), (1, 10))):
    """(core, stack, params, x, resets, zero state)."""
    core, stack, params = built(family, cc, seed)
    x = jax.random.normal(_keys(FAMILIES[family], seed)[0],
                          (batch, steps, stack_width(family, cc)))
    resets = np.zeros((batch, steps), bool)
    for b, t in reset_at:
        if b < batch and t < steps:
            resets[b, t] = True
    return (core, stack, params, x, jnp.asarray(resets),
            core.initial_state(batch))


@functools.lru_cache(maxsize=None)
def _jitted(family, cc_json):
    cc = json.loads(cc_json)
    stack = mla_moe._Stack(FAMILIES[family].core(cc).kc, jnp.float32)
    ref = FAMILIES[family].ref
    run = jax.jit(lambda p, x, st, r: stack.apply({"params": p}, x, st, r))
    sown = jax.jit(lambda p, x, st, r: stack.apply(
        {"params": p}, x, st, r, mutable=[cores.CORE_STATS]))

    def plain(p, x, r, burn=0, window=None):
        # (a reference that knows no window is never told of one)
        told = {} if window is None else {"window": window}
        return ref.core_forward(p, cc, x, r, burn=burn, **told)

    return run, jax.jit(plain, static_argnames=("burn", "window")), sown


def jitted(family, cc):
    """(program, reference) as compiled functions of (params, x, state,
    resets) and (params, x, resets, burn=, window=): XLA:CPU compiles a tiny
    stack in seconds where op-by-op dispatch takes many times that, and an
    eager `lax.cond` (one a held expert in the few-token expert layer)
    compiles by itself.  One pair a configuration and process, so that two
    cases with the same shapes share a compile."""
    return _jitted(family, _as_key(cc))[:2]


def jitted_sown(family, cc):
    """The program as a compiled function that also returns what it sowed:
    ((y, state), sown) of (params, x, state, resets)."""
    return _jitted(family, _as_key(cc))[2]


def ticks_from(run, params, x, resets, state, cut=None):
    """One-step calls over x [B, T, .] from `state`; `cut(state, t)` stands
    between tick t - 1 and tick t, where the trainers reset a lane."""
    ys = []
    for i in range(x.shape[1]):
        if cut is not None:
            state = cut(state, i)
        y, state = run(params, x[:, i:i + 1], state, resets[:, i:i + 1])
        ys.append(y)
    return jnp.concatenate(ys, axis=1), state


def sequence_start(core, batch):
    """The learner's start: windows of zero slots."""
    none = jnp.zeros((batch, 0), jnp.float32)
    return core.from_stored(none, none)


# ------------------------------------------------------------ comparisons
def close(a, b, tol=2e-4):
    """Float32 on both sides, sums in another order (keys in window slots
    against keys in sequence order, the chunked scan against the step-by-step
    one, the experts' rows sorted against a dense mask, five blocks deep):
    2e-4 of the largest value is some thousand roundings of room."""
    a, b = np.asarray(a), np.asarray(b)
    scale = max(float(np.abs(b).max()), 1e-6)
    assert float(np.abs(a - b).max()) <= tol * scale, (
        float(np.abs(a - b).max()), scale)


def grads_close(g1, g2, tol=2e-3):
    """A gradient sums over every step, token and pass: ten times the
    values'."""
    assert jax.tree.structure(g1) == jax.tree.structure(g2)
    for (path, a), c in zip(jax.tree_util.tree_leaves_with_path(g1),
                            jax.tree.leaves(g2)):
        if "select_bias" in jax.tree_util.keystr(path):
            # the bias enters the choice alone: no gradient on either side
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(c))
            continue
        close(a, c, tol)


def states_close(a, b, order):
    """Two states leaf for leaf, their windows turned by `order`
    (ring_windows.aged or .live)."""
    for x, y in zip(jax.tree.leaves(order(a)), jax.tree.leaves(order(b))):
        close(x, y)


# -------------------------------------------------------- the expert layer
def shares_add_up(cfg, cc, ref, p, x, per_share, whole, shared):
    """Every share of `per_share` experts (0.., per_share.., ...) computed by
    the program's layer told which it holds, each against the reference's
    share; what every chip computes alike (`shared`, 0.0 where a family has no
    shared expert) counted once: their sum is `whole`, the uncut reference
    layer, and every assignment fell on one share.  `first_expert` enters
    `_MoE` in one subtraction, so it is an argument of the compiled layer
    here and one compile serves every share."""
    def layer(share_p, x, first):
        kc = dataclasses.replace(
            cfg, experts_here=per_share, first_expert=first)
        y, sown = mla_moe._MoE(kc, jnp.float32).apply(
            {"params": share_p}, x, mutable=[cores.CORE_STATS])
        return y, cores.reduce_stats(sown)

    layer = jax.jit(layer)
    plain = jax.jit(lambda share_p, x, first: ref.moe_ffn(
        share_p, cc, x, (first, per_share), ref.plain_dot))
    total, held = shared, 0.0
    for first in range(0, cfg.experts, per_share):
        share_p = {**p, "experts": {n: w[first:first + per_share]
                                    for n, w in p["experts"].items()}}
        y, stats = layer(share_p, x, first)
        close(y, plain(share_p, x, first))
        total = total + (y - shared)
        assert float(stats["moe_tokens_dropped"]) == 0.0
        held += float(stats["moe_held_assign_share"])
    close(total, whole)
    assert abs(held - 1.0) < 1e-6  # every assignment fell on one share


def expert_layer(cfg, x, dtype=jnp.float32):
    """(the seeded parameters of `_MoE` of `cfg`, the layer compiled: (y,
    what it sowed, reduced) of (params, x))."""
    moe = mla_moe._MoE(cfg, dtype)
    p = jax.jit(moe.init)(jax.random.PRNGKey(1), x)["params"]

    def run(p, x):
        y, sown = moe.apply({"params": p}, x, mutable=[cores.CORE_STATS])
        return y, cores.reduce_stats(sown)

    return p, jax.jit(run)


# ---------------------------------------------------------- jaxprs, shapes
def sub_jaxprs(value):
    if isinstance(value, jax.extend.core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jax.extend.core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from sub_jaxprs(v)


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (remat,
    cond, scan, custom derivatives)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in sub_jaxprs(value):
                yield from equations(sub)


def score_shapes(jaxpr):
    """The shapes of the attention scores: what a softmax takes its largest
    over, [B, heads.., T, slots] (the only `reduce_max` of four axes or
    more)."""
    return {eqn.invars[0].aval.shape for eqn in equations(jaxpr)
            if eqn.primitive.name == "reduce_max"
            and len(eqn.invars[0].aval.shape) >= 4}


def all_shapes(jaxpr):
    return {v.aval.shape for eqn in equations(jaxpr) for v in eqn.outvars
            if hasattr(v.aval, "shape")}


def stack_shapes(kc, width, dtype=jnp.float32, batch=2, steps=3):
    """(parameter shapes, state shapes, the call's argument shapes) of the
    stack of `kc`: nothing is allocated."""
    state = jax.eval_shape(lambda: mla_moe.StackCore()._zero_state(kc, batch))
    args = (jax.ShapeDtypeStruct((batch, steps, width), jnp.float32), state,
            jax.ShapeDtypeStruct((batch, steps), jnp.bool_))
    params = jax.eval_shape(
        lambda k, *a: mla_moe._Stack(kc, dtype).init(k, *a)["params"],
        jax.random.PRNGKey(0), *args)
    return params, state, args


def lowered_text(kc, width):
    """The stack's lowered module with its scopes' names."""
    params, _, args = stack_shapes(kc, width)
    return jax.jit(lambda p, *a: mla_moe._Stack(kc, jnp.float32).apply(
        {"params": p}, *a)).lower(params, *args).as_text(debug_info=True)


def tiny_core(family):
    """(the tiny file as it stands, its core, the width its stack takes)."""
    fam = FAMILIES[family]
    with open(fam.tiny) as f:
        cc = json.load(f)
    core = fam.core(cc)
    return cc, core, cc["hidden_size"] if core.kc.in_proj else TRUNK_FEATURES


def shapes_by_path(tree):
    return {jax.tree_util.keystr(p): list(v.shape)
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


# ------------------------------------------- the agent with a tiny core
def tiny_config(tmp_path, family, **kw):
    """The R2D2 agent's `Config` of the training cases: the fused trainer on
    freeway, the family's tiny core behind the trunk."""
    from rainbow_iqn_apex_tpu.config import Config

    base = dict(
        env_id="jaxgame:freeway", architecture="r2d2", role="anakin",
        core_config=FAMILIES[family].tiny, compute_dtype="float32",
        history_length=2, hidden_size=32, r2d2_burn_in=4, r2d2_seq_len=8,
        r2d2_overlap=4, batch_size=4, learning_rate=1e-3, multi_step=2,
        gamma=0.9, memory_capacity=12 * 40, learn_start=12 * 8,
        frames_per_learn=2, target_update_period=100, num_envs_per_actor=4,
        anakin_segment_ticks=8, learner_devices=1, metrics_interval=1,
        eval_interval=0, checkpoint_interval=0, eval_episodes=2,
        max_grad_norm=1e6,
        results_dir=str(tmp_path / "results"),
        checkpoint_dir=str(tmp_path / "ckpt"), seed=3,
    )
    base.update(kw)
    return Config(**base)


def metric_rows(results_dir, run_id):
    path = os.path.join(str(results_dir), run_id, "metrics.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f]


def _cli(tmp_path, run_id, core, *flags):
    """`train_agent_apex.py --architecture r2d2 --core-config <tiny file>` at
    tiny widths; the run's rows."""
    import train_agent_apex

    rc = train_agent_apex.main([
        "--architecture", "r2d2", "--core-config", CORES[core],
        "--compute-dtype", "float32", "--history-length", "2",
        "--hidden-size", "32", "--r2d2-overlap", "4", "--batch-size", "4",
        "--multi-step", "2", "--frames-per-learn", "2",
        "--num-envs-per-actor", "4", "--anakin-segment-ticks", "8",
        "--eval-episodes", "1", "--eval-interval", "0",
        "--checkpoint-interval", "0", "--metrics-interval", "1",
        "--run-id", run_id, "--results-dir", str(tmp_path / "results"),
        "--checkpoint-dir", str(tmp_path / "ckpt"), *flags])
    assert rc == 0
    return metric_rows(tmp_path / "results", run_id)


def run_fused_cli(tmp_path, core):
    """A fused run of ten 8-tick dispatches of 4 lanes; its `learn` rows."""
    rows = _cli(
        tmp_path, "cli", core, "--role", "anakin",
        "--env-id", "jaxgame:freeway", "--r2d2-burn-in", "4",
        "--r2d2-seq-len", "8", "--memory-capacity", "480",
        "--learn-start", "96", "--learner-devices", "1", "--t-max", "320")
    return [r for r in rows if r["kind"] == "learn"]


def host_fed_role_trains_with_the_core(tmp_path, role, learners, core):
    """The host-fed anakin loop, `train_r2d2` and the apex R2D2 driver carry
    the core's state pytree per lane and a ring without stored state."""
    rows = _cli(
        tmp_path, role, core, "--role", role, "--env-id", "toy:catch",
        "--r2d2-burn-in", "2", "--r2d2-seq-len", "6",
        "--memory-capacity", "800", "--learn-start", "64",
        "--learner-devices", str(learners), "--t-max", "160")
    # a row logged before its step's loss came back carries null
    losses = [r["loss"] for r in rows
              if r["kind"] == "learn" and r["loss"] is not None]
    assert losses and all(np.isfinite(x) for x in losses)
