"""Device self time a learn step of the leading layer's dense SwiGLU
(`dense_ffn` inside `learn_step`): three products of 2048 x 7168 a token,
forward and backward, as many FLOPs as the four expert layers' grouped
products together.  What `ouro_ffn_device_ms` reads in its cell (its reading)
and `dense_ffn_device_ms` in theirs.  None where the program has no such
scope."""

from benchmarks.readers.ouro_ffn_device_ms import read  # noqa: F401
