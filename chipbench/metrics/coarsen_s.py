"""Host seconds per solve spent in the ``bench.coarsen`` span (the
benchmark's wrapper around the service's call of ``build_hierarchy``),
over the solves answered in the window."""


def read(run):
    if not run.spans.count("coarsen") or not run.solves:
        return None
    return run.spans.total("coarsen") / run.solves
