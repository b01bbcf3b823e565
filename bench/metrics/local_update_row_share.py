"""Share of the client stack's rows that the local update runs over, in %:
100 x the summed ``train_rows`` over the summed ``rows`` of the window's
``fed.segment.stage`` (a staged layout) and ``fed.llm.call`` (an LLM
experiment) spans.  Byzantine rows under an update-level attack and pad
rows do not train.  None where no span in the window carries
``train_rows`` (a program that trains every row)."""

from bench.program_spans import window_records


def read(r):
    recs = window_records(r, "fed.segment.stage", "fed.llm.call")
    counted = [s.attrs for s in recs or () if "train_rows" in s.attrs]
    if not counted:
        return None
    return 100.0 * sum(a["train_rows"] for a in counted) / sum(a["rows"] for a in counted)
