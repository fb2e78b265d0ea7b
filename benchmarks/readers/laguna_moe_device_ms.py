"""Device self time a learn step of the four expert layers: sigmoid router
over 256, top-8 and the sort of 32,768 assignment keys a layer and pass
(`moe_route`), gather, grouped products and scatter-add of the held
assignments (`moe_experts`) and the shared expert (`moe_shared`), inside
`learn_step`: what `moe_ffn_device_ms` reads in its cells.  None where the
program has no such scopes."""

from benchmarks.readers.moe_ffn_device_ms import read  # noqa: F401
