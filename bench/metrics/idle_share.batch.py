"""Device: share of the traced window in which the chip ran no operation
(1 - union of device op intervals / window)."""


def read(r):
    if r.device is None or r.device.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.device.busy_s() / r.device.window_s)
