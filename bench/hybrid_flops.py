"""Operations and bytes that serving a Granite-4.0-H hybrid needs (Mamba-2
and attention mixers, held experts beside a shared one), computed from the
configuration file's shapes (``reference/granite_hybrid_lm.shape``).

Useful work counts the mixers (projections, the depthwise conv, the SSM
recurrence's state update and read-out, attention over the cache), the
router, the shared expert, and the routed pairs that land on the held
experts: on average ``top_k x held / router width`` a token.  The per-layer
readers divide these by device or host time; nothing here reads a clock
or the program.
"""
from __future__ import annotations

from bench.reference.granite_hybrid_lm import shape


def _layers(s: dict) -> tuple[int, int]:
    """(Mamba-2 layers, attention layers)."""
    n_attn = sum(1 for t in s["types"] if t == "attention")
    return s["L"] - n_attn, n_attn


def _mixer_params(s: dict) -> tuple[int, int]:
    """Matmul weights of one Mamba-2 layer and of one attention layer."""
    d = s["d"]
    mamba = d * (s["Ei"] + s["conv"] + s["H"]) + s["Ei"] * d
    q, kv = s["hq"] * s["dh"], s["hkv"] * s["dh"]
    return mamba, 2 * d * q + 2 * d * kv


def token_matmul_params(config: dict) -> float:
    """Weights one token meets in the layers' matrix multiplications, the
    routed experts counted at the share of pairs that land here."""
    s = shape(config)
    n_m, n_a = _layers(s)
    mamba, attn = _mixer_params(s)
    d = s["d"]
    routed = s["k"] * s["E"] / s["W"] * 3 * d * s["f"]
    moe = d * s["W"] + 3 * d * s["fs"] + routed
    return n_m * mamba + n_a * attn + s["L"] * moe


def _token_mixer_flops(s: dict) -> float:
    """Non-matmul mixer work of one token: the conv, and the recurrence's
    state update (3 per state entry) and read-out (2 per entry)."""
    n_m, _ = _layers(s)
    return n_m * (2.0 * s["K"] * s["conv"] + 5.0 * s["H"] * s["P"] * s["N"])


def _attn_flops(s: dict, positions: float) -> float:
    """Scores and weighted sum of one query over ``positions`` keys, over
    the attention layers."""
    _, n_a = _layers(s)
    return 4.0 * n_a * s["hq"] * s["dh"] * positions


def prefill_flops(config: dict, prompt_len: int) -> float:
    """Useful FLOPs of prefilling a prompt: every true prompt token through
    the layers, causal attention, and the logits of the last position."""
    s, p = shape(config), prompt_len
    return p * (2.0 * token_matmul_params(config) + _token_mixer_flops(s)) \
        + _attn_flops(s, p * (p + 1) / 2) + 2.0 * s["d"] * s["V"]


def decode_flops(config: dict, context: int) -> float:
    """Useful FLOPs of one decoded token whose query sees ``context``
    positions (itself included)."""
    s = shape(config)
    return 2.0 * token_matmul_params(config) + _token_mixer_flops(s) \
        + _attn_flops(s, context) + 2.0 * s["d"] * s["V"]


def weight_bytes(config: dict, itemsize: int = 2) -> int:
    """Bytes of every weight held (bfloat16), the tied embedding once."""
    s = shape(config)
    n_m, n_a = _layers(s)
    mamba, attn = _mixer_params(s)
    d = s["d"]
    small = 2 * d                                   # the two norms
    mamba += s["K"] * s["conv"] + s["conv"] + 3 * s["H"] + s["Ei"]
    moe = d * s["W"] + 3 * d * s["fs"] + s["E"] * 3 * d * s["f"]
    return itemsize * (s["V"] * d + d + n_m * mamba + n_a * attn
                       + s["L"] * (moe + small))


def decode_bytes(config: dict, slots: int, live_positions: float) -> float:
    """Bytes one decode step must move: the held weights once, every
    slot's conv and SSM state (float32) read and written, and the K and V
    (bfloat16) of ``live_positions`` cached positions read."""
    s = shape(config)
    n_m, n_a = _layers(s)
    state = 4 * (s["H"] * s["P"] * s["N"] + (s["K"] - 1) * s["conv"])
    kv = 2 * 2 * s["hkv"] * s["dh"]
    return weight_bytes(config) + 2.0 * n_m * slots * state \
        + n_a * kv * live_positions
