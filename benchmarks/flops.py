"""Model FLOPs of one learn step, from shapes.  A multiply-add is two.

These are the operations the algorithm needs, not what XLA executes: every
forward pass once, and for the pass that is differentiated twice that again
for the backward pass, less the first convolution's input gradient (the
frames take none).  Recomputed operations do not count.
"""

from __future__ import annotations


def conv_out(size: int, kernel: int, stride: int) -> int:
    return (size - kernel) // stride + 1


def trunk_flops(h: int, w: int, channels: int):
    """(forward FLOPs of the DQN trunk on one frame stack, of which the first
    layer, flattened features)."""
    total, first, cin = 0, 0, channels
    for i, (cout, k, s) in enumerate(((32, 8, 4), (64, 4, 2), (64, 3, 1))):
        h, w = conv_out(h, k, s), conv_out(w, k, s)
        layer = 2 * h * w * cout * k * k * cin
        total += layer
        first = layer if i == 0 else first
        cin = cout
    return total, first, h * w * cin


def noisy_flops(n_in: int, n_out: int) -> int:
    """A factorised noisy layer is two matmuls (mu and sigma)."""
    return 2 * 2 * n_in * n_out


def heads_flops(feat: int, hidden: int, actions: int) -> int:
    """Dueling noisy heads on one feature row."""
    return (2 * noisy_flops(feat, hidden) + noisy_flops(hidden, 1)
            + noisy_flops(hidden, actions))


def r2d2_learn_flops(fields: dict, frame_shape, actions: int) -> float:
    """One R2D2 learn step: the online net over burn-in (forward only) and
    the trained slice (forward and backward), the target net over both
    (forward only); heads on the trained slice alone."""
    h, w = frame_shape
    trunk, first, feat = trunk_flops(h, w, fields["history_length"])
    m = fields["lstm_size"]
    lstm = 4 * 2 * (feat + m) * m
    heads = heads_flops(m, fields["hidden_size"], actions)
    burn, train = fields["r2d2_burn_in"], fields["r2d2_seq_len"]
    body = trunk + lstm
    online = burn * body + train * (3 * (body + heads) - first)
    target = (burn + train) * body + train * heads
    return float(fields["batch_size"] * (online + target))
