"""Device self time a learn step of the four expert layers: sigmoid router,
top-4 and the sort of 30,720 assignment keys a layer (`moe_route`), gather,
grouped products and scatter-add of the 7,680 held assignments in a
15,360-row buffer (`moe_experts`), inside `learn_step`.  There is no shared
expert, so no `moe_shared` (which `moe_ffn_device_ms` adds in its cells).
The grouped products are custom calls that bear the TPU compiler's label
(`ragged-dot-none`) where the program's name stood; the program puts them
down to what feeds them, the expert layer's rows and kernels
(`obs/device_scopes.instruction_scopes`, PR 43), so they count here,
forwards and backwards.  None where the program has no such scopes."""

from benchmarks import scopes


def read(ctx):
    parts = [scopes.ms_per(ctx, "steps", "learn_step", scope)
             for scope in ("moe_route", "moe_experts")]
    return None if None in parts else sum(parts) or None
