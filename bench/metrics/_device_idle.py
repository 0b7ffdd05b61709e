"""Shared by the device_idle.* readers: the share of the traced window
in which no operation ran on the device."""


def idle_percent(ctx):
    summary = ctx.get("trace")
    if summary is None or not summary.devices:
        return None
    return 100.0 * summary.idle_share()
