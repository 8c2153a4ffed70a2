"""Host seconds per solve spent in the ``bench.initial`` span (the
benchmark's wrapper around the service's call of
``initial_partition_population``), over the solves answered in the
window."""


def read(run):
    if not run.spans.count("initial") or not run.solves:
        return None
    return run.spans.total("initial") / run.solves
