"""Useful model FLOPs of the window's engine steps (true prompt tokens of
each prefill, active slots of each decode; ``bench/flops.py``) over the
steps' summed wall time times the chip's bf16 peak, in %."""


def read(rec):
    return rec.get("mfu_serve_step")
