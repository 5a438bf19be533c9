"""Share of the prefilled positions that are padding, in %: 1 - the sum of
the prompts' lengths over the sum of the positions each prefill ran
(``prompt_len`` and ``positions`` of the program's ``serve/prefill``
spans, ``repro.trace``), over every prefill of the run."""
from bench.program_spans import recorder


def read(rec):
    trace = recorder(rec)
    if trace is None:
        return None
    rs = trace.records("serve/prefill")
    positions = sum(r.attrs["positions"] for r in rs)
    if not positions:
        return None
    return 100.0 * (1.0 - sum(r.attrs["prompt_len"] for r in rs) / positions)
