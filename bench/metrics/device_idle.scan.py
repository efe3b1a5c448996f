"""Share of the profiled stretch in which no operation ran on the device,
in %: 1 - busy / window, busy averaged over the chips used."""


def read(run):
    if run.trace is None or run.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.busy_s / run.window_s)
