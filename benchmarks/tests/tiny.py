"""Tiny CPU sizes of the configuration, for the benchmark's own tests:
the published geometry's shape (burn-in + trained slice, n-step, cadence)
at widths a CPU test can hold.  float32 compute, so the program and the
plain reference agree to rounding."""

from __future__ import annotations

from benchmarks.harness import load_json as load


def r2d2_fields() -> dict:
    f = load("configs", "r2d2-atari-1chip")["fields"]
    f.update(compute_dtype="float32", lstm_size=32, hidden_size=32,
             r2d2_burn_in=4, r2d2_seq_len=8, r2d2_overlap=4, batch_size=4,
             multi_step=2, learn_start=12 * 64, memory_capacity=12 * 64)
    return f


def traffic(name: str, lanes: int = 4, ticks: int = 8) -> dict:
    t = load("traffic", name)
    t["fields"].update(num_envs_per_actor=lanes, anakin_segment_ticks=ticks)
    return t
