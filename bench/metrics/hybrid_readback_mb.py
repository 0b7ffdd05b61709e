"""MB (10^6 bytes) brought to the host per sweep, from the program's
``hybrid_readback_bytes`` counter over the window (program counter)."""


def read(ctx):
    total, sweeps = ctx.get("hybrid_readback_bytes"), ctx.get("sweeps")
    if not total or not sweeps:
        return None
    return total / sweeps / 1e6
