"""Mean loader data-wait per window step: the loader's own clock around
``dataset.get`` (``StepMetrics.data_wait_s``)."""


def read(run):
    return sum(m.data_wait_s for m in run.steps) / len(run.steps) * 1e3
