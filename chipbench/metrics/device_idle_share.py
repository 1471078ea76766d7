"""device_idle_share (device, TPU v5e): share of the traced call's wall
time in which no op ran on the device (1 - union of op intervals over the
call's span)."""


def read(run):
    if run.window_s <= 0 or not run.reading.ops:
        return None
    return 100.0 * (1.0 - run.reading.busy_s / run.window_s)
