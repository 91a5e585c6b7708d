"""Share of the traced window in which no operation ran on the device: 1
minus the union of the device's op intervals over the window length."""


def read(run):
    ts = run.trace_summary
    if not ts or ts["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ts["busy_s"] / ts["window_s"])
