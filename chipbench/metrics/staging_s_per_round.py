"""Host seconds per traced round spent staging LocalTrain's inputs: the
microbatches sampled and stacked in NumPy and uploaded to the device
(self time of the program's ``stage`` span, ``fl/executor.py``)."""
from harness import program_spans


def read(run):
    return program_spans.seconds_per_round(run, ["stage"])
