"""Device self time a learn step of the draw and the gather inside the fused
segment (`replay_draw` + `replay_gather`), where `replay_sample_ms` times
them from outside with a relayout the segment hoists out of its loop."""

from benchmarks import scopes


def read(ctx):
    parts = [scopes.ms_per(ctx, "steps", scope)
             for scope in ("replay_draw", "replay_gather")]
    return None if None in parts else sum(parts)
