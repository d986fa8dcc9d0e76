"""Share of window steps during which the trainer requested the next batch
while the step ran on the device (``StepMetrics.overlapped``), in percent.
Reports nothing where the program keeps no such counter."""


def read(run):
    overlapped = [getattr(m, "overlapped", None) for m in run.steps]
    if not overlapped or None in overlapped:
        return None
    return 100 * sum(overlapped) / len(overlapped)
