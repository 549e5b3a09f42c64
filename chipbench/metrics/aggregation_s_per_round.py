"""Host seconds per traced round in aggregation: the aggregator's submit
and flush of the round's reports and the server update applied (self
time of the program's ``aggregate`` span, ``fl/engine.py``)."""
from harness import program_spans


def read(run):
    return program_spans.seconds_per_round(run, ["aggregate"])
