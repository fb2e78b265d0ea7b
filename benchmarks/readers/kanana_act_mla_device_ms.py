"""Device self time a tick of MLA in the act path (`mla_proj` + `mla_attn`,
which holds `mla_rope`, inside `tick_act`): each tick projects the whole
window's latents to keys and values again (`kv_b` over 121 slots a lane and
layer), which is what an absorbed decode step would not do.  None where the
program has no such scopes."""

from benchmarks import scopes


def read(ctx):
    parts = [scopes.ms_per(ctx, "segments", "tick_act", *path,
                           every=ctx.driver.ticks)
             for path in (("mla_proj",), ("mla_attn",), ("mla_attn", "mla_rope"))]
    return None if None in parts or not all(parts) else parts[0] + parts[1]
