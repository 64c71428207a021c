"""Device: 1 - union of device-op intervals over the traced window, on the
device where that share is largest."""


def read(run, trace):
    d = trace.worst
    if d is None or not d.window_s:
        return None
    return 100.0 * (1.0 - d.busy_s / d.window_s)
