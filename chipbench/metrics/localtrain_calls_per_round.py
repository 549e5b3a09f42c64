"""LocalTrain launches per traced round: one per group of clients that
share their knobs (the program's ``localtrain_calls`` counter,
``fl/executor.py``)."""
from harness import program_spans


def read(run):
    return program_spans.count_per_round(run, "localtrain_calls")
