"""Host seconds per solve spent in the ``bench.refine`` span (the
benchmark's wrapper around the service's call of
``instances.refine_grouped``), over the solves answered in the window."""


def read(run):
    if not run.spans.count("refine") or not run.solves:
        return None
    return run.spans.total("refine") / run.solves
