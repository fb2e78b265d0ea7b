"""Device self time a learn step of the leading layer's dense SwiGLU
(`dense_ffn` inside `learn_step`): three products of 2048 x 8192 a token,
forward and backward.  What `ouro_ffn_device_ms` reads in its cell (its
reading).  None where the program has no such scope."""

from benchmarks.readers.ouro_ffn_device_ms import read  # noqa: F401
