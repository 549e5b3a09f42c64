"""Host seconds per traced round blocked on LocalTrain: after launching
a knob group's training program, the wait for its losses on the host
(self time of the program's ``local_train_wait`` span,
``fl/executor.py``)."""
from harness import program_spans


def read(run):
    return program_spans.seconds_per_round(run, ["local_train_wait"])
