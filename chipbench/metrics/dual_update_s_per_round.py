"""Host seconds per traced round in the dual update and its constraint
reports (self time of the program's ``dual_update`` span,
``fl/engine.py``)."""
from harness import program_spans


def read(run):
    return program_spans.seconds_per_round(run, ["dual_update"])
