"""Device, mining: percent of the traced window in which no op ran."""


def read(r):
    return 100.0 * r.device.idle_share() if r.device.window_s > 0 else None
