"""The serve engine's admission: one prefill program per prompt length that
takes the live batch cache donated and writes the prompt's cache row and
first token into it in place, for a Mamba2 model and a Mamba2 + attention
hybrid with experts."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Model
from repro.serve import Request, ServeConfig, ServeEngine

ARCHS = ["mamba2-1.3b", "granite-4.0-h-small"]
S = 16  # a whole number of SSD chunks


def _engine(arch: str, slots: int = 3, max_new_tokens: int = 2) -> ServeEngine:
    model = Model(get_config(arch).smoke())
    params = model.init(jax.random.PRNGKey(0))
    return ServeEngine(model, params, ServeConfig(batch_slots=slots, cache_len=40, max_new_tokens=max_new_tokens))


def _admit(eng: ServeEngine, slot: int, prompt) -> Request:
    r = Request(rid=slot, prompt=np.asarray(prompt, np.int32))
    eng._prefill_into(slot, r)
    eng.slots[slot] = r
    return r


def _parent_splice(cache_leaf, row_leaf, slot: int):
    """The row written out of place with ``.at[].set`` on the batch axis:
    the reference the engine's in-place write is held to."""
    for ax in range(cache_leaf.ndim):
        if row_leaf.shape[ax] == 1 and cache_leaf.shape[ax] != 1:
            idx = [slice(None)] * cache_leaf.ndim
            idx[ax] = slice(slot, slot + 1)
            return cache_leaf.at[tuple(idx)].set(row_leaf.astype(cache_leaf.dtype))
    return cache_leaf.at[slot].set(row_leaf.reshape(-1)[0].astype(cache_leaf.dtype))


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_writes_the_cache_in_place(arch):
    """Compiled with the cache donated, the prefill program writes the row
    into every cache leaf: no whole-leaf copy, nor a whole-leaf broadcast
    initialising a fresh buffer (the one whole-batch copy allowed is of
    ``tok``, the token vector, which is not donated).  An admission
    consumes the cache buffers it was given."""
    eng = _engine(arch)
    given = jax.tree_util.tree_leaves(eng.cache)
    _admit(eng, 1, np.arange(S) % eng.model.cfg.vocab_size)
    assert all(x.is_deleted() for x in given)
    assert not any(x.is_deleted() for x in jax.tree_util.tree_leaves(eng.cache))

    n_in = len(jax.tree_util.tree_leaves((eng.params, {"tokens": 0})))
    n_cache = len(given)
    text = (
        eng._prefill_jits[S]
        .lower(eng.params, {"tokens": jnp.zeros((1, S), jnp.int32)}, eng.cache, eng._tok, np.int32(0))
        .compile()
        .as_text()
    )
    tok_param = re.search(r"%%(\S+) = s32\[\d+\]\S* parameter\(%d\)" % (n_in + n_cache), text).group(1)
    for leaf in given:
        dims = ",".join(map(str, leaf.shape))
        whole = re.compile(r"= \w+\[%s\]\S* (copy|copy-start|broadcast)\(" % dims)
        offenders = [
            line.strip()
            for line in text.splitlines()
            if whole.search(line) and f"copy(%{tok_param})" not in line
        ]
        assert not offenders, offenders


@pytest.mark.parametrize("arch", ARCHS)
def test_admissions_match_the_eager_splice_bit_for_bit(arch):
    """Prompts admitted into slots in a scrambled order, a freed slot
    refilled after decode steps: after each admission the engine's cache
    and tokens are those the prefill row and first token give when spliced
    with ``.at[].set``, bit for bit."""
    eng = _engine(arch, max_new_tokens=3)
    V, cache_len = eng.model.cfg.vocab_size, eng.cfg.cache_len
    prefill = jax.jit(lambda p, b: eng.model.prefill(p, b, cache_len)[:2])
    rng = np.random.default_rng(5)

    def admit_and_check(slot, n):
        prompt = rng.integers(0, V, size=(n,)).astype(np.int32)
        before, tok = jax.device_get(eng.cache), np.asarray(eng._tok)
        logits, row = prefill(eng.params, {"tokens": jnp.asarray(prompt[None])})
        first = jnp.argmax(logits[0, 0, :V]).astype(jnp.int32)
        want = jax.tree_util.tree_map(lambda c, v: _parent_splice(jnp.asarray(c), v, slot), before, row)
        r = _admit(eng, slot, prompt)
        assert r.out_tokens == [int(first)]
        np.testing.assert_array_equal(np.asarray(eng._tok), np.asarray(jnp.asarray(tok).at[slot].set(first)))
        jax.tree_util.tree_map(np.testing.assert_array_equal, jax.device_get(eng.cache), jax.device_get(want))

    admit_and_check(2, S)
    admit_and_check(0, 2 * S)
    eng._decode_active()
    admit_and_check(1, S)
    eng._decode_active()  # the first two requests finish: slots 2 and 0 free
    assert eng.slots[0] is None and eng.slots[2] is None
    admit_and_check(0, S)
    admit_and_check(2, 2 * S)
    eng._decode_active()
    assert sum(s is not None for s in eng.slots) == 2
