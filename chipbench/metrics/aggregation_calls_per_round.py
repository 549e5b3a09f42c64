"""Aggregation program launches per traced round: each step of the
fold over the round's reports and each server update applied (the
program's ``aggregate_calls`` counter, ``core/aggregation.py``)."""
from harness import program_spans


def read(run):
    return program_spans.count_per_round(run, "aggregate_calls")
