"""The program's own host spans (``repro.utils.spans``) inside the measured
window.  They are timed on ``time.perf_counter()``, the clock of the
harness's ``window`` span, so a record is in the window when it starts and
ends inside it.  A program without that module gives nothing: its readers
return None."""

from __future__ import annotations


def window_records(r, *names) -> list | None:
    """Records of the spans named ``names`` inside the window, or None
    where the program records no spans."""
    try:
        from repro.utils import spans
    except ImportError:
        return None
    (_, lo, hi), = [s for s in r.spans.records if s[0] == "window"]
    return [s for s in spans.records()
            if s.name in names and s.t0 >= lo and s.t1 <= hi]


def per_experiment(r, total: float) -> float | None:
    """``total`` over the window's experiments (None without any)."""
    exps = getattr(r.driver, "experiments", None)
    return total / len(exps) if exps else None
