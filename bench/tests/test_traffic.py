"""The traffic generator: a pure function of the mix file and --seed."""
import collections

import numpy as np
import pytest

from bench import spec, traffic

MIXES = ["model-gen-batch", "model-docs-batch"]


def _load(name):
    return spec.Bench(spec.ROOT).traffic(name)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = _load(name)
    a = traffic.schedule(mix, seed=2**33 + 5, vocab=1000)
    b = traffic.schedule(mix, seed=2**33 + 5, vocab=1000)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert (x.due_s, x.prompt_len, x.max_new_tokens) == (y.due_s, y.prompt_len, y.max_new_tokens)
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_seed_changes_tokens_not_work(name):
    """Every seed gets the same sizes in the same order; only the token ids move."""
    mix = _load(name)
    a = traffic.schedule(mix, seed=1, vocab=1000)
    b = traffic.schedule(mix, seed=2, vocab=1000)
    assert [(r.due_s, r.prompt_len, r.max_new_tokens) for r in a] == \
           [(r.due_s, r.prompt_len, r.max_new_tokens) for r in b]
    assert not np.array_equal(np.concatenate([r.prompt for r in a]),
                              np.concatenate([r.prompt for r in b]))


@pytest.mark.parametrize("name", MIXES)
def test_every_block_holds_the_stated_mix(name):
    mix = _load(name)
    reqs = traffic.schedule(mix, seed=3, vocab=1000)
    B = mix["block"]
    for k in range(len(reqs) // B):
        blk = reqs[k * B:(k + 1) * B]
        got = collections.Counter(r.prompt_len for r in blk)
        want = {s: round(w * B) for s, w in zip(mix["prompt_lens"], mix["prompt_weights"])}
        assert got == collections.Counter(want)
        got = collections.Counter(r.max_new_tokens for r in blk)
        want = {s: round(w * B) for s, w in zip(mix["output_lens"], mix["output_weights"])}
        assert got == collections.Counter(want)


def test_backlog_is_due_at_once_and_warmup_covers_every_shape():
    mix = _load("model-docs-batch")
    reqs = traffic.schedule(mix, seed=0, vocab=10)
    assert len(reqs) == mix["arrival"]["count"] and all(r.due_s == 0.0 for r in reqs)
    shapes = {(r.prompt_len, r.max_new_tokens) for r in traffic.warmup(mix, seed=0, vocab=10)}
    assert shapes == set(traffic.shapes(mix)) >= {(r.prompt_len, r.max_new_tokens) for r in reqs}


def test_an_arrival_process_without_a_generator_is_refused():
    mix = dict(_load("model-gen-batch"), arrival={"kind": "poisson", "rate_per_s": 5.0})
    with pytest.raises(ValueError):
        traffic.schedule(mix, seed=0, vocab=10)


def test_weights_that_do_not_split_a_block_are_refused():
    mix = dict(_load("model-gen-batch"), block=7)
    with pytest.raises(ValueError):
        traffic.schedule(mix, seed=0, vocab=10)
