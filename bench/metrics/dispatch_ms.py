"""Mean host time per window step in the trainer's ``train.dispatch`` span:
the call of the jitted step until it returns (``StepMetrics.dispatch_s``).
Reports nothing where the program keeps no such counter."""


def read(run):
    seconds = [getattr(m, "dispatch_s", None) for m in run.steps]
    if not seconds or None in seconds:
        return None
    return sum(seconds) / len(seconds) * 1e3
