"""Device self time a learn step of MLA's attention over the latent window in
the five layers: the rope dimensions turned by their slot (`mla_rope`, inside
`mla_attn`), scores, mask, softmax and values, forward and backward (`mla_attn`
inside `learn_step`).  None where the program has no such scopes: a core that
does not rotate has no `mla_rope`, and what it reads under `mla_attn` is
another metric's (`mla_attn_device_ms`)."""

from benchmarks import scopes


def read(ctx):
    if not scopes.ms_per(ctx, "steps", "learn_step", "mla_attn", "mla_rope"):
        return None
    return scopes.ms_per(ctx, "steps", "learn_step", "mla_attn")
