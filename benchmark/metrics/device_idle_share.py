"""device_idle_share: the share of a traced run's window in which no
program ran on the device: 1 - busy union / window."""


def read(run):
    if run.trace is None:
        return None
    return 1 - run.trace["busy_s"] / run.trace["window_s"]
