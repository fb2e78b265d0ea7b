"""Building-block layers for the TPU-native Rainbow-IQN network.

Parity: reference `rainbowiqn/model.py` (SURVEY.md §2 row 3) — NoisyLinear with
factorised Gaussian noise (sigma0=0.5, Fortunato et al. arXiv:1706.10295) and
the IQN cosine tau embedding (Dabney et al. arXiv:1806.06923).

TPU-first design notes:
- Noise is never hidden module state (the torch pattern of `.reset_noise()`
  mutating buffers).  It is drawn from an explicit PRNG key per call via the
  flax "noise" RNG collection, so noisy forward passes are pure functions that
  jit/vmap/shard_map cleanly and noise-resampling semantics are decided by
  whoever supplies the key (SURVEY.md §7 "NoisyNet semantics under jit/pmap").
- Matmuls run in a configurable compute dtype (bfloat16 by default) with fp32
  parameters, so the MXU sees bf16 operands while optimizer state stays fp32.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from rainbow_iqn_apex_tpu.obs import device_scopes

Dtype = Any


def _f(x: jnp.ndarray) -> jnp.ndarray:
    """Factorised-noise squashing f(x) = sign(x) * sqrt(|x|)."""
    return jnp.sign(x) * jnp.sqrt(jnp.abs(x))


class NoisyLinear(nn.Module):
    """Factorised-Gaussian noisy linear layer.

    y = (w_mu + w_sigma * (f(eps_out) f(eps_in)^T)) x + (b_mu + b_sigma * f(eps_out))

    When ``use_noise`` is False (evaluation), only the mu parameters are used —
    matching the reference's eval-time behaviour of acting without noise.
    """

    features: int
    sigma0: float = 0.5
    use_noise: bool = True
    compute_dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        in_features = x.shape[-1]
        bound = 1.0 / float(in_features) ** 0.5

        def _mu_init(key, shape, dtype=jnp.float32):
            return jax.random.uniform(key, shape, dtype, -bound, bound)

        w_mu = self.param("w_mu", _mu_init, (in_features, self.features), jnp.float32)
        b_mu = self.param("b_mu", _mu_init, (self.features,), jnp.float32)
        sigma_init = self.sigma0 / float(in_features) ** 0.5
        w_sigma = self.param(
            "w_sigma",
            nn.initializers.constant(sigma_init),
            (in_features, self.features),
            jnp.float32,
        )
        b_sigma = self.param(
            "b_sigma",
            nn.initializers.constant(sigma_init),
            (self.features,),
            jnp.float32,
        )

        xc = x.astype(self.compute_dtype)
        y = jnp.dot(xc, w_mu.astype(self.compute_dtype), preferred_element_type=jnp.float32)
        if self.use_noise:
            key = self.make_rng("noise")
            k_in, k_out = jax.random.split(key)
            eps_in = _f(jax.random.normal(k_in, (in_features,), jnp.float32))
            eps_out = _f(jax.random.normal(k_out, (self.features,), jnp.float32))
            # The noise is rank-1, so the noisy term factorises exactly:
            #   x @ (w_sigma * eps_in eps_out^T) == ((x * eps_in) @ w_sigma) * eps_out
            # — two GEMMs and two row/col scalings, never materialising the
            # [in, out] noise matrix in HBM.
            noisy = jnp.dot(
                xc * eps_in.astype(self.compute_dtype),
                w_sigma.astype(self.compute_dtype),
                preferred_element_type=jnp.float32,
            )
            y = y + noisy * eps_out
            b = b_mu + b_sigma * eps_out
        else:
            b = b_mu
        return y + b  # fp32 accumulate + fp32 bias


class CosineTauEmbedding(nn.Module):
    """IQN tau embedding: psi(tau)_j = ReLU(Linear(cos(pi * i * tau), i=1..n)).

    Input taus [..., N] -> output [..., N, features].
    """

    features: int
    num_cosines: int = 64
    compute_dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, taus: jnp.ndarray) -> jnp.ndarray:
        i = jnp.arange(1, self.num_cosines + 1, dtype=jnp.float32)
        cos = jnp.cos(jnp.pi * taus[..., None] * i)  # [..., N, num_cosines]
        dense = nn.Dense(
            self.features,
            dtype=self.compute_dtype,
            param_dtype=jnp.float32,
            name="embed",
        )
        return nn.relu(dense(cos.astype(self.compute_dtype)))


def stack_history(frames: jnp.ndarray, before: jnp.ndarray) -> jnp.ndarray:
    """Single frames [B, T, H, W, 1] and the h-1 frames before them
    [B, h-1, H, W, 1] -> [B, T, H, W, h]: channel k of step t is the frame
    of step t-(h-1-k)."""
    steps = frames.shape[1]
    x = jnp.concatenate([before, frames], axis=1)
    return jnp.concatenate(
        [x[:, k:k + steps] for k in range(before.shape[1] + 1)], axis=-1)


def unit_frames(frames: jnp.ndarray, dtype: Dtype) -> jnp.ndarray:
    """uint8 frames -> [0, 1] in `dtype`, as the nets scale stacked input."""
    if frames.dtype == jnp.uint8:
        return frames.astype(dtype) * (1.0 / 255.0)
    return frames.astype(dtype)


class StemConv(nn.Module):
    """The trunk's first conv (kernel `size`, stride `stride`, VALID): one
    kernel `[size, size, history, features]`, read in one of two ways by
    what the input is.

    Stacked input `[N, H, W, history]`: the plain strided conv, as `nn.Conv`
    computes it; `[N, H', W', features]`.

    Single frames `[B, T, H, W, 1]` with the history-1 frames before them
    (`before`; R2D2's stored sequences, the stride dividing H, W and `size`):
    the history is never laid out per pixel.  The frames are cast once and
    brought once to the order the TPU runs this conv in, which is H, W, C
    with the batch innermost: cut into stride x stride blocks
    (space-to-depth, the block's pixels as channels) and the batch folded
    time-major, `[H/stride, W/stride, stride^2, (T+history-1)*B]`.  There a
    step back in time is a shift along the innermost axis, so the history is
    `history` slices laid along the channels, and the same kernel, re-indexed
    `[size/stride, size/stride, history*stride^2, features]`, runs as a
    stride-1 conv: the same products under the same float32 accumulation, in
    another order.  The result is folded time-major too:
    `[T*B, H', W', features]`, row t*B + b.
    """

    features: int
    size: int
    stride: int
    compute_dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray, before: Optional[jnp.ndarray] = None):
        size, stride, dt = self.size, self.stride, self.compute_dtype
        history = x.shape[-1] if before is None else before.shape[1] + 1
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (size, size, history, self.features), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,), jnp.float32)
        kernel, bias = kernel.astype(dt), bias.astype(dt)
        if before is None:
            return jax.lax.conv_general_dilated(
                x, kernel, (stride, stride), "VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC")) + bias

        lanes, steps, height, width, _ = x.shape
        x = jnp.concatenate([before, x], axis=1)
        cells, taps = (height // stride, width // stride), size // stride
        z = unit_frames(x.reshape(
            lanes, -1, cells[0], stride, cells[1], stride), dt)
        z = z.transpose(2, 4, 3, 5, 1, 0).reshape(*cells, stride * stride, -1)
        x = jnp.concatenate([z[..., k * lanes:(k + steps) * lanes]
                             for k in range(history)], axis=2)
        kernel = kernel.reshape(taps, stride, taps, stride, history, -1)
        kernel = kernel.transpose(0, 2, 4, 1, 3, 5).reshape(
            taps, taps, history * stride * stride, -1)
        y = jax.lax.conv_general_dilated(
            x, kernel, (1, 1), "VALID",
            dimension_numbers=("HWCN", "HWIO", "HWCN")) + bias[:, None]
        return y.transpose(3, 0, 1, 2)


class ConvTrunk(nn.Module):
    """Canonical DQN conv trunk (32x8x8/4, 64x4x4/2, 64x3x3/1) in NHWC.

    `x` is a batch of stacked frames `[N, H, W, C]`, float in [0, 1]; or,
    with `before`, single frames `[B, T, H, W, 1]` (uint8 or float) and the
    C-1 frames before them `[B, C-1, H, W, 1]`, from which the first conv
    reads the history without a stack of them being made (`StemConv`; where
    `stem_reads_frames` says no they are stacked and the plain conv runs).  Features `[N, F]` or `[B*T, F]`.  The parameter tree
    is the same either way.

    NHWC keeps channels off the spatial axes and leaves the physical order
    to XLA, which on a TPU runs these narrow convs with the batch innermost
    (`{0,3,2,1}`) and relayouts whatever arrives in another order: on a
    learn step's 4x frame stack that was two copies of the whole stack
    (PERF.md, PR 33), which is why stored frames take the other reading.
    """

    compute_dtype: Dtype = jnp.bfloat16

    STEM = (32, 8, 4)  # the first conv: features, kernel size, stride

    @classmethod
    def stem_reads_frames(
        cls, height: int, width: int, chips: Optional[int] = None
    ) -> bool:
        """Whether single frames of this size take `StemConv`'s reading from
        frames: the stride has to divide them, and no mesh may split the
        batch (`chips`: by default what the trace can see of one,
        `parallel/mesh.traced_under`).  That reading folds the batch into the
        conv's innermost axis behind time, and GSPMD follows a batch split
        over chips there only by gathering every chip's frames onto each
        (no-chip compile for four chips, PERF.md PR 33)."""
        if chips is None:
            chips = jax.sharding.get_abstract_mesh().size
        stride = cls.STEM[2]  # which divides the kernel's size
        return chips <= 1 and height % stride == 0 and width % stride == 0

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, before: Optional[jnp.ndarray] = None
    ) -> jnp.ndarray:
        lanes = x.shape[0]
        from_frames = before is not None and self.stem_reads_frames(
            *x.shape[2:4])
        if before is not None and not from_frames:
            x = unit_frames(stack_history(x, before), self.compute_dtype)
            x = x.reshape(-1, *x.shape[2:])
        with jax.named_scope(device_scopes.NET_STEM):
            stem = StemConv(*self.STEM, self.compute_dtype, name="Conv_0")
            x = (stem(x, before) if from_frames
                 else stem(x.astype(self.compute_dtype)))
        x = nn.relu(x)
        for i, (features, kernel, stride) in enumerate(((64, 4, 2), (64, 3, 1))):
            x = nn.Conv(
                features,
                (kernel, kernel),
                strides=(stride, stride),
                padding="VALID",
                dtype=self.compute_dtype,
                param_dtype=jnp.float32,
                name=f"Conv_{i + 1}",
            )(x)
            x = nn.relu(x)
        x = x.reshape(x.shape[0], -1)  # [N, 3136] for 84x84x4
        if from_frames:  # the stem folds time-major: row t*B + b
            x = x.reshape(-1, lanes, x.shape[-1]).swapaxes(0, 1)
            x = x.reshape(-1, x.shape[-1])
        return x
