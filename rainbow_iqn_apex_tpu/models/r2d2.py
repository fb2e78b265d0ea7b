"""R2D2 recurrent Q-network (flax): conv trunk -> recurrent core -> dueling
noisy head.  The core is the LSTM unless one is given (models/cores.py).

Parity: the reference's R2D2 stretch configuration (BASELINE.json:10,
SURVEY.md §7 step 7; Kapturowski et al., "Recurrent Experience Replay in
Distributed Reinforcement Learning", R2D2) — an LSTM Q-network trained on
stored-state replay sequences with burn-in.  R2D2 uses a plain (scalar)
dueling Q head rather than IQN quantiles; noisy layers keep the Rainbow
exploration story.

TPU-first notes:
- Time unrolling is inside one jit: [B, T, H, W, C] -> conv trunk applied as
  one [B*T] batch (one conv a layer over all steps), the LSTM's input product
  over all steps at once, then a `lax.scan` that carries only the small LSTM
  state through the hidden side of the gates (`cores.LSTMCore`).
- A learn step hands the trunk the ring's single frames [B, T, H, W, 1] with
  the history-1 frames before them (`frames_before`), and the first conv
  reads the history from those (`layers.StemConv`): stacking them per pixel
  first cost the step four passes over a 4x copy of the batch (PERF.md,
  PR 33).  The actor's tick feeds one already-stacked step and takes the
  plain conv; both read the one `Conv_0` kernel.
- Recurrent state is an explicit (c, h) pair the caller owns — nothing hidden
  in module state, so actor-side stored-state replay and burn-in are pure
  data plumbing.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from rainbow_iqn_apex_tpu.models.cores import LSTMCore
from rainbow_iqn_apex_tpu.models.layers import (
    ConvTrunk,
    NoisyLinear,
    unit_frames,
)
from rainbow_iqn_apex_tpu.obs import device_scopes

Dtype = Any


class R2D2Net(nn.Module):
    """Recurrent dueling noisy Q-network over frame sequences."""

    num_actions: int
    lstm_size: int = 512
    hidden_size: int = 512
    noisy_sigma0: float = 0.5
    dueling: bool = True
    use_noise: bool = True
    compute_dtype: Dtype = jnp.bfloat16
    core: Any = None  # models/cores.py; None is LSTMCore(lstm_size)

    def the_core(self):
        return self.core if self.core is not None else LSTMCore(self.lstm_size)

    def initial_state(self, batch: int):
        return self.the_core().initial_state(batch)

    @nn.compact
    def __call__(
        self,
        obs_seq: jnp.ndarray,  # [B, T, H, W, C] uint8 (or float in [0,1])
        state: Any,
        resets: Optional[jnp.ndarray] = None,  # [B, T] bool: reset state BEFORE step t
        frames_before: Optional[jnp.ndarray] = None,  # [B, C-1, H, W, 1]
    ) -> Tuple[jnp.ndarray, Any]:
        """Returns (q_values [B, T, A] fp32, the core's final state).

        With `frames_before`, `obs_seq` is the single frames [B, T, H, W, 1]
        of a stored sequence and `frames_before` the history-1 frames that
        precede its first step (zeros at a sequence's start): the trunk reads
        the history from them and no stack is made (`layers.StemConv`)."""
        B, T = obs_seq.shape[:2]
        if frames_before is None:
            if obs_seq.dtype == jnp.uint8:
                obs_seq = unit_frames(obs_seq, self.compute_dtype)
            obs_seq = obs_seq.reshape(B * T, *obs_seq.shape[2:])

        # conv trunk over the folded [B*T] batch: one large GEMM per layer
        with jax.named_scope(device_scopes.NET_TRUNK):
            phi = ConvTrunk(compute_dtype=self.compute_dtype)(
                obs_seq, frames_before)
        phi = phi.reshape(B, T, -1).astype(jnp.float32)  # cores carry fp32
        if resets is None:
            resets = jnp.zeros((B, T), bool)
        outs, final_state = self.the_core()(phi, state, resets)
        feat = outs.reshape(B * T, outs.shape[-1])  # [B*T, L]

        def head(name: str, out_dim: int) -> jnp.ndarray:
            h1 = NoisyLinear(
                self.hidden_size,
                sigma0=self.noisy_sigma0,
                use_noise=self.use_noise,
                compute_dtype=self.compute_dtype,
                name=f"{name}_hidden",
            )(feat)
            h1 = nn.relu(h1)
            return NoisyLinear(
                out_dim,
                sigma0=self.noisy_sigma0,
                use_noise=self.use_noise,
                compute_dtype=self.compute_dtype,
                name=f"{name}_out",
            )(h1)

        if self.dueling:
            value = head("value", 1)
            adv = head("advantage", self.num_actions)
            q = value + adv - adv.mean(axis=-1, keepdims=True)
        else:
            q = head("q", self.num_actions)
        return q.reshape(B, T, self.num_actions).astype(jnp.float32), final_state
