"""Operations and bytes that serving a request needs, for a mixture-of-experts
decoder with window and full attention layers (``adapters/moe.sizes``), from
the configuration's shapes alone (never from the program or a trace).

As in ``flops.py``: causal attention counts the pairs at or below the
diagonal, and a window layer only the last ``window`` keys of each query;
decode reads the valid cache prefix (a window layer at most ``window``
slots); the LM head runs on the tokens whose logits are used; a multiply-add
is 2 operations; weights and cache are 2 bytes an element (bf16).

Experts: a token picks ``top_k`` of the ``E`` routed experts, and this chip
computes the picks that land on its ``E_held``: on average
``top_k * E_held / E`` a token (2 for 8 of 64 with 16 held). Expert work is
that average; expert bytes are the held experts that ``n`` tokens are
expected to touch, ``E_held * (1 - (1 - top_k / E) ** n)``: 2 for one decode
token, all 16 for a prompt of thousands.
"""
from __future__ import annotations

BYTES = 2  # bf16


class Counts:
    def __init__(self, s: dict):
        self.s = s
        d, H, K, hd, V = (s[k] for k in ("d", "H", "K", "hd", "V"))
        self.attn_params = d * H * hd + 2 * d * K * hd + H * hd * d
        self.router_params = d * s["E"]
        self.expert_params = 3 * d * s["de"]
        self.head_params = d * V
        self.picks = s["top_k"] * s["E_held"] / s["E"]  # held picks a token, on average
        norms = 2 * d + (2 * hd if s["qk_norm"] else 0)
        L = s["L"]
        # every weight but the experts': layers, final norm, head, one embedding row
        self.dense_bytes = BYTES * (L * (self.attn_params + self.router_params + norms)
                                    + d + V * d + (0 if s["tied"] else d))
        self.kv_pos_bytes = BYTES * 2 * K * hd  # one position of one layer
        self.n_window = sum(k == "window" for k in s["kinds"])
        self.n_full = L - self.n_window

    def experts_touched(self, tokens: int) -> float:
        """Held experts of one layer that ``tokens`` tokens are expected to use."""
        s = self.s
        return s["E_held"] * (1.0 - (1.0 - s["top_k"] / s["E"]) ** tokens)

    def _token_layer_flops(self) -> float:
        """One token through one layer's projections, router and held experts."""
        return 2 * (self.attn_params + self.router_params + self.picks * self.expert_params)

    def _pair_flops(self) -> int:
        return 2 * 2 * self.s["H"] * self.s["hd"]

    def _window_keys(self, ctx: int) -> int:
        return min(ctx, self.s["window"])

    def prefill_flops(self, S: int) -> float:
        """Prompt of S tokens: every layer on every token, causal attention
        (window layers over the last ``window`` keys), last-token logits."""
        W = self.s["window"]
        full_pairs = S * (S + 1) // 2
        window_pairs = full_pairs - (S - W) * (S - W + 1) // 2 if S > W else full_pairs
        return (S * self.s["L"] * self._token_layer_flops()
                + self._pair_flops() * (self.n_full * full_pairs + self.n_window * window_pairs)
                + 2 * self.head_params)

    def prefill_bytes(self, S: int) -> float:
        """Every weight once (the experts the prompt is expected to touch),
        the prompt's keys and values written (a window layer's last
        ``window``)."""
        return (self.dense_bytes
                + BYTES * self.s["L"] * self.experts_touched(S) * self.expert_params
                + self.kv_pos_bytes * (self.n_full * S + self.n_window * self._window_keys(S)))

    def prefill_positions(self, S: int) -> int:
        return S

    def prefill_floor_s(self, S: int, peak: dict) -> float:
        return max(self.prefill_flops(S) / peak["bf16_flops_per_s"],
                   self.prefill_bytes(S) / peak["hbm_bytes_per_s"])

    def decode_step_flops(self, ctx: int) -> float:
        """One token with ``ctx`` valid keys (itself included)."""
        return (self.s["L"] * self._token_layer_flops() + 2 * self.head_params
                + self._pair_flops() * (self.n_full * ctx + self.n_window * self._window_keys(ctx)))

    def decode_step_bytes(self, ctx: int) -> float:
        """The weights a token needs once (its expected held experts), the
        valid cache read, one row written."""
        return (self.dense_bytes
                + BYTES * self.s["L"] * self.experts_touched(1) * self.expert_params
                + self.kv_pos_bytes * (self.n_full * (ctx + 1)
                                       + self.n_window * (self._window_keys(ctx) + 1)))

    def decode_ctx(self, S: int, T: int) -> range:
        """Valid keys at each of the T - 1 decode steps after a prompt of S
        (the prefill's logits give the first token)."""
        return range(S + 1, S + T)

    def request_flops(self, S: int, T: int) -> float:
        return self.prefill_flops(S) + sum(self.decode_step_flops(c) for c in self.decode_ctx(S, T))

    def decode_floor_s(self, S: int, T: int, peak: dict) -> float:
        """Least time for the T - 1 decode steps: each bound by the larger of
        its operations over peak FLOP/s and its bytes over peak bandwidth."""
        f, b = peak["bf16_flops_per_s"], peak["hbm_bytes_per_s"]
        return sum(max(self.decode_step_flops(c) / f, self.decode_step_bytes(c) / b)
                   for c in self.decode_ctx(S, T))
