"""Share of the traced stretch in which no operation ran on the device."""


def read(run):
    if not run.device_trace or run.trace_window_s <= 0:
        return None
    return 100.0 * (1.0 - run.device_trace["busy_s"] / run.trace_window_s)
