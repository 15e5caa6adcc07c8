"""Faults planted in the timed path, to show that ``correct`` catches them.

Each takes the engine after it is built and breaks one thing underneath the
loop; the run goes on as usual.  ``tests/test_correct.py`` drives each at a
size the CPU holds, and ``calibrate.py --fault <name>`` on the chip at a
cell's own size.

The launches own the page pools they are given (``donate_argnums``), so a
fault that keeps the pools unchanged copies them before the launch and
hands back the copies.
"""
from __future__ import annotations

import jax.numpy as jnp


def alter_tokens(eng) -> None:
    """Every decoded token is replaced by its successor in the vocabulary
    where the decode launch hands it out."""
    kv = eng.kv
    decode = kv.decode
    vocab = eng.cfg.vocab_size

    def altered():
        blocks = decode()
        return (blocks + 1) % vocab
    kv.decode = altered


def _keeps_pools(launch):
    """``launch`` as it runs, returning copies of the page pools it was
    given in place of the pools it wrote."""
    def unchanged(params, k_pool, v_pool, *rest):
        kept = (jnp.copy(k_pool), jnp.copy(v_pool))
        out = launch(params, k_pool, v_pool, *rest)
        return kept + tuple(out[2:])
    return unchanged


def decode_keeps_state(eng) -> None:
    """The decode launch returns the page pools it was given, unchanged."""
    eng.kv._decode_slots = _keeps_pools(eng.kv._decode_slots)


def prefill_keeps_state(eng) -> None:
    """Each prefill or extend launch returns the page pools it was given,
    unchanged."""
    eng.kv._extend = _keeps_pools(eng.kv._extend)


ALL = {f.__name__: f for f in (alter_tokens, decode_keeps_state,
                               prefill_keeps_state)}
