"""Share of the traced window in which no operation ran on the device
(1 - busy over window, the first chip's union of operation intervals).
The traced run carries the Python tracer's overhead on the host, so this
is an upper bound of the untraced idle share. Layer: device."""


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
