"""Share of the traced window in which no operation ran on the device."""


def read(run):
    red = run.get("trace")
    return 100.0 * (1 - red["busy_s"] / run["trace_window_s"]) if red \
        else None
