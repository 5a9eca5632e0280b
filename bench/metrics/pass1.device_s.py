"""Device seconds per fit of pass 1, the screen's support count: the fused
kernel (``kernels/tspm_fused``) or, at H > 14, its jnp block fallback,
whose eager ops carry no common program name.  Pass 1 is what the device
ran in the traced fit before pass 2's first pair-generation program (a
name containing ``pairgen``); the slice traces one fit."""

PASS2_FIRST = "pairgen"


def read(ctx):
    if ctx.kind != "fit" or ctx.units != 1:
        return None
    return ctx.device_seconds_before(PASS2_FIRST)
