"""The program's own span recorder (``repro.trace``), for the per-layer
readers of the spans the program records about itself.

A reader gets the recorder only in a traced run (``--trace 1``: the run
whose result line carries per-layer metrics), and never from a program
that has no recorder, so the reader then finds nothing and the line
leaves its metric out."""


def recorder(rec: dict):
    """``repro.trace``, or ``None`` outside a traced run or without it."""
    if not rec.get("trace"):
        return None
    try:
        from repro import trace
    except ImportError:                  # a program without the recorder
        return None
    return trace
