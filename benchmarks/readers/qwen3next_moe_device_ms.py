"""Device self time a learn step of the four expert layers: softmax router
and sort (`moe_route`), gather, grouped products and scatter-add
(`moe_experts`), the gated shared expert (`moe_shared`), inside `learn_step`:
what `moe_ffn_device_ms` reads in its cell, under a name of its own because
the expert geometry is another.  None where the program has no such scopes."""

from benchmarks.readers.moe_ffn_device_ms import read  # noqa: F401
