"""Mean host time per window step in the trainer's ``train.prepare`` span:
decoding, stacking and the copy to the device (``StepMetrics.prepare_s``).
Reports nothing where the program keeps no such counter."""


def read(run):
    seconds = [getattr(m, "prepare_s", None) for m in run.steps]
    if not seconds or None in seconds:
        return None
    return sum(seconds) / len(seconds) * 1e3
