"""Layer device. Share of the traced window in which no operation ran on
the chip: 1 - union of the device-operation intervals over the window, mean
over the chips used; from the profiler's trace."""


def read(run):
    tr = run["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
