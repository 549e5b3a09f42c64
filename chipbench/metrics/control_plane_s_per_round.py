"""Host seconds per traced round in the control plane outside the dual
update: evaluation, cohort composition, the clients' reports and the
constraint accounting (self time of the program's ``eval``, ``compose``,
``report`` and ``accounting`` spans, ``fl/engine.py``)."""
from harness import program_spans


def read(run):
    return program_spans.seconds_per_round(
        run, ["eval", "compose", "report", "accounting"])
