"""Device self time a learn step of routing alone in the four expert layers
(`moe_route` inside `learn_step`): the sigmoid over 32 experts, the top-4 and
the sort of 30,720 assignment keys a layer, where `qwen3next_moe_route_
device_ms`, whose reading this is, times a softmax over 512 and 76,800 keys.
None where the program has no such scope."""

from benchmarks.readers.qwen3next_moe_route_device_ms import read  # noqa: F401
