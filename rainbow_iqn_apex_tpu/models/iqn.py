"""Dueling noisy-net IQN Q-network (flax), the framework's flagship model.

Parity: reference `rainbowiqn/model.py` (SURVEY.md §2 row 3, §3.3) — conv trunk
-> phi(s); tau ~ U[0,1] -> 64-cosine embedding -> psi(tau); Hadamard phi ⊙ psi;
dueling NoisyLinear value/advantage heads; output Z_tau(s, a) per sampled tau.

TPU-first design notes:
- The tau dimension is folded into the batch for every head matmul, so the MXU
  sees one [B*N, F] x [F, H] GEMM instead of N small ones.
- The number of tau samples is a static (trace-time) constant, so each role
  (actor K=32, learner N=64/N'=64) compiles exactly one XLA program.
- uint8 frames are shipped to the device and normalised on-chip (u8 -> bf16
  * 1/255), cutting host->HBM traffic 4x vs fp32 frames.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from rainbow_iqn_apex_tpu.models.layers import ConvTrunk, CosineTauEmbedding, NoisyLinear
from rainbow_iqn_apex_tpu.obs import device_scopes

Dtype = Any


class RainbowIQN(nn.Module):
    """Implicit Quantile Network with dueling + noisy heads.

    Call signature:
        quantiles, taus = model.apply(params, obs, num_taus,
                                      rngs={"taus": k1, "noise": k2})

    obs:       [B, H, W, C] uint8 (or float already in [0, 1])
    quantiles: [B, num_taus, num_actions] fp32 quantile values Z_tau(s, a)
    taus:      [B, num_taus] fp32, the sampled quantile fractions
    """

    num_actions: int
    hidden_size: int = 512
    num_cosines: int = 64
    noisy_sigma0: float = 0.5
    dueling: bool = True
    use_noise: bool = True
    compute_dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(
        self,
        obs: jnp.ndarray,
        num_taus: int,
        taus: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        batch = obs.shape[0]
        if obs.dtype == jnp.uint8:
            obs = obs.astype(self.compute_dtype) * (1.0 / 255.0)

        with jax.named_scope(device_scopes.NET_TRUNK):
            phi = ConvTrunk(compute_dtype=self.compute_dtype)(obs)  # [B, F]
        feat = phi.shape[-1]

        if taus is None:
            taus = jax.random.uniform(
                self.make_rng("taus"), (batch, num_taus), jnp.float32
            )
        with jax.named_scope(device_scopes.IQN_HEAD):
            psi = CosineTauEmbedding(
                features=feat,
                num_cosines=self.num_cosines,
                compute_dtype=self.compute_dtype,
            )(taus)  # [B, N, F]

            # Hadamard merge, then fold taus into batch: [B*N, F] for one big GEMM.
            h = phi[:, None, :].astype(self.compute_dtype) * psi
            h = h.reshape(batch * num_taus, feat)

            def head(name: str, out_dim: int) -> jnp.ndarray:
                h1 = NoisyLinear(
                    self.hidden_size,
                    sigma0=self.noisy_sigma0,
                    use_noise=self.use_noise,
                    compute_dtype=self.compute_dtype,
                    name=f"{name}_hidden",
                )(h)
                h1 = nn.relu(h1)
                return NoisyLinear(
                    out_dim,
                    sigma0=self.noisy_sigma0,
                    use_noise=self.use_noise,
                    compute_dtype=self.compute_dtype,
                    name=f"{name}_out",
                )(h1)

            if self.dueling:
                value = head("value", 1)  # [B*N, 1]
                adv = head("advantage", self.num_actions)  # [B*N, A]
                q = value + adv - adv.mean(axis=-1, keepdims=True)
            else:
                q = head("q", self.num_actions)

        quantiles = q.reshape(batch, num_taus, self.num_actions).astype(jnp.float32)
        return quantiles, taus


def q_values(quantiles: jnp.ndarray) -> jnp.ndarray:
    """Mean over the tau dimension: [B, N, A] -> [B, A] expected Q."""
    return quantiles.mean(axis=1)


def greedy_action(quantiles: jnp.ndarray) -> jnp.ndarray:
    """Greedy action from quantile means: [B, N, A] -> [B] int32."""
    return jnp.argmax(q_values(quantiles), axis=-1).astype(jnp.int32)
