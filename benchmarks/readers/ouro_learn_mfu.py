"""`learn_mfu` of the cell `ouro-r2d2-fused`: the driver's own model FLOPs of
one learn step (benchmarks/flops_ouro_core.py, every pass counted) times the
learn steps per second of the traced window, over chips times the bf16 peak:
the share of the whole step, end to end, no roofline share.  Under a name of
its own because the cells' FLOP counts are different functions; the reading
is `learn_mfu`'s."""

from benchmarks.readers.learn_mfu import read  # noqa: F401
