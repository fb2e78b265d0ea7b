"""`learn_mfu` of the cell `laguna-xs2-r2d2-fused`: the driver's own model
FLOPs of one learn step (benchmarks/flops_laguna_core.py, which counts the
keys a query may see, the band's live columns, and not the columns a block
computes: a block's size cannot move it) times the learn steps per second of
the traced window, over chips times the bf16 peak: the share of the whole
step, end to end, no roofline share.  Under a name of its own because the
cells' FLOP counts are different functions; the reading is `learn_mfu`'s."""

from benchmarks.readers.learn_mfu import read  # noqa: F401
