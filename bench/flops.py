"""Operations and bytes that serving a request needs, from a configuration's
shapes alone (never from the program or a trace).

Counts are what the algorithm needs, not what the program happens to do:
causal attention counts the pairs at or below the diagonal, decode reads the
valid cache prefix and not the whole bucket, the LM head runs on the tokens
whose logits are used. A multiply-add is 2 operations. Weights and cache are
2 bytes an element (bf16).

``for_config(family, sizes)`` returns the counter of a family; a family that
is not here brings ``bench/flops_<family>.py`` with a ``Counts`` class.
"""
from __future__ import annotations

import os

BYTES = 2  # bf16


class DenseCounts:
    """Decoder-only transformer; ``s`` is ``adapters/dense.sizes(cfg)``."""

    def __init__(self, s: dict):
        self.s = s
        L, d, H, K, hd, ff, V = (s[k] for k in ("L", "d", "H", "K", "hd", "ff", "V"))
        self.layer_params = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff
        self.head_params = d * V
        norms = 2 * d + (2 * hd if s["qk_norm"] else 0)
        # bytes of weights one decode step reads: every layer, the final norm,
        # the LM head, and one embedding row (inside the head when tied)
        self.step_weight_bytes = BYTES * (
            L * (self.layer_params + norms) + d + V * d + (0 if s["tied"] else d))
        self.kv_row_bytes = BYTES * L * 2 * K * hd  # one position, all layers

    def _attn(self, pairs: int) -> int:
        s = self.s
        return s["L"] * 2 * 2 * s["H"] * s["hd"] * pairs

    def prefill_flops(self, S: int) -> int:
        """Prompt of S tokens: every layer on every token, causal attention,
        logits of the last token."""
        return (2 * S * self.s["L"] * self.layer_params
                + self._attn(S * (S + 1) // 2) + 2 * self.head_params)

    def prefill_positions(self, S: int) -> int:
        """Positions the prefill executable runs over: the prompt."""
        return S

    def decode_step_flops(self, ctx: int) -> int:
        """One token with ``ctx`` valid keys (itself included)."""
        return 2 * (self.s["L"] * self.layer_params + self.head_params) + self._attn(ctx)

    def decode_step_bytes(self, ctx: int) -> int:
        """All weights once, the valid cache prefix read, one row written."""
        return self.step_weight_bytes + self.kv_row_bytes * (ctx + 1)

    def decode_ctx(self, S: int, T: int) -> range:
        """Valid keys at each decode step that T served tokens need after a
        prompt of S: the prefill's logits give the first token, and each of
        the T - 1 steps after it writes one position, from position S on."""
        return range(S + 1, S + T)

    def request_flops(self, S: int, T: int) -> int:
        return self.prefill_flops(S) + sum(self.decode_step_flops(c) for c in self.decode_ctx(S, T))

    def decode_floor_s(self, S: int, T: int, peak: dict) -> float:
        """Least time the chip could take for the T decode steps: each step
        bound by the larger of its operations over peak FLOP/s and its bytes
        over peak bandwidth."""
        f, b = peak["bf16_flops_per_s"], peak["hbm_bytes_per_s"]
        return sum(max(self.decode_step_flops(c) / f, self.decode_step_bytes(c) / b)
                   for c in self.decode_ctx(S, T))


def for_config(family: str, sizes: dict):
    if family == "dense":
        return DenseCounts(sizes)
    from bench.spec import load_module

    here = os.path.dirname(os.path.abspath(__file__))
    mod = load_module(os.path.join(here, f"flops_{family}.py"), f"flops_{family}")
    return mod.Counts(sizes)
