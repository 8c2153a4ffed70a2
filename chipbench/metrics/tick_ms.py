"""Mean host time of one ``PartitionService.step()`` in the window, in
milliseconds (the benchmark's span around each call)."""


def read(run):
    n = run.spans.count("tick")
    return 1e3 * run.spans.total("tick") / n if n else None
