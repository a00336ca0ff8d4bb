"""One general generator for every traffic mix in ``bench/traffic/``.

A mix is a JSON file of parameters:

    arrival         {"kind": "backlog", "count": n}   (all due at t = 0)
    prompt_lens, prompt_weights, output_lens, output_weights
    block           sizes come in blocks of this many requests, each block
                    holding every length exactly weight * block times
    schedule_seed   fixes the sizes and their order

The schedule (sizes and order) is a function of the mix file alone, so every
``--seed`` gets the same work; ``--seed`` draws the prompt token ids (and, in
the harness, the weights). Open arrival processes come with the first cell
that sends them (PERF.md, Open questions).
"""
from __future__ import annotations

import dataclasses
import itertools

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    due_s: float
    prompt_len: int
    max_new_tokens: int
    prompt: np.ndarray  # (prompt_len,) int32, drawn from --seed


def _rng(*keys: int) -> np.random.Generator:
    """Generator from any non-negative ints (seeds may exceed 32 bits)."""
    return np.random.default_rng([int(k) for k in keys])


def _block(lens: list[int], weights: list[float], block: int) -> np.ndarray:
    counts = np.asarray(weights, np.float64) * block
    if np.any(np.abs(counts - np.round(counts)) > 1e-9) or round(counts.sum()) != block:
        raise ValueError(f"weights {weights} do not split a block of {block} exactly")
    return np.repeat(np.asarray(lens, np.int64), np.round(counts).astype(int))


def shapes(mix: dict) -> list[tuple[int, int]]:
    """Every (prompt length, new tokens) pair the mix can send."""
    return list(itertools.product(mix["prompt_lens"], mix["output_lens"]))


def _sizes(mix: dict, rng: np.random.Generator, n: int) -> tuple[np.ndarray, np.ndarray]:
    block = int(mix["block"])
    ps = _block(mix["prompt_lens"], mix["prompt_weights"], block)
    os_ = _block(mix["output_lens"], mix["output_weights"], block)
    P, T = [], []
    for _ in range(-(-n // block)):  # block by block: a longer run extends a shorter one
        P.append(rng.permutation(ps))
        T.append(rng.permutation(os_))
    return np.concatenate(P)[:n], np.concatenate(T)[:n]


def schedule(mix: dict, *, seed: int, vocab: int) -> list[Request]:
    """The backlog of one run: every request due at the window's opening."""
    arrival = mix["arrival"]
    if arrival["kind"] != "backlog":
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    n = int(arrival["count"])
    P, T = _sizes(mix, _rng(mix["schedule_seed"]), n)
    tok = _rng(seed, 0x70)
    return [Request(i, 0.0, int(P[i]), int(T[i]),
                    tok.integers(0, vocab, int(P[i]), dtype=np.int32))
            for i in range(n)]


def warmup(mix: dict, *, seed: int, vocab: int) -> list[Request]:
    """One request of each shape the mix can send."""
    tok = _rng(seed, 0x77)
    return [Request(-1 - i, 0.0, s, t, tok.integers(0, vocab, s, dtype=np.int32))
            for i, (s, t) in enumerate(shapes(mix))]
