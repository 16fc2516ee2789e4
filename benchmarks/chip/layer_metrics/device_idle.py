"""Device: the share of the traced window in which no operation ran on the
chip, in percent."""
NAME = "device.idle"


def read(ctx: dict):
    device = ctx["device"]
    if device is None:
        return None
    return 100.0 * (1.0 - device.busy_s() / device.window_s)
