"""`learn_mfu` of the cells whose recurrent core is not the LSTM: the driver's
own model FLOPs of one learn step (benchmarks/flops_kimi_core.py) times the
learn steps per second of the traced window, over chips times the bf16 peak.
Under a name of its own because the two cells' FLOP counts are different
functions; the reading is `learn_mfu`'s."""

from benchmarks.readers.learn_mfu import read  # noqa: F401
