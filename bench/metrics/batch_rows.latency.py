"""Mean rows per admission batch (program counters): requests answered
in the window divided by the batches the pipeline finalized for them."""


def read(ctx):
    batches = ctx.get("batches")
    if not batches:
        return None
    return ctx["verdicts"] / batches
