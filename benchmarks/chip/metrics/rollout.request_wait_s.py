"""Mean seconds a generation request waited between submission to the
proxy and its admission into an engine slot (``RequestLifecycle``), over
the requests admitted in the window."""


def read(run):
    waits = [lc.t_admit - lc.t_submit for lc in run.lifecycles]
    return sum(waits) / len(waits) if waits else None
