"""Faults planted in the timed path, to show that ``correct`` catches them.

Each takes the engine after it is built and breaks one thing underneath the
loop; the run goes on as usual.  ``tests/test_correct.py`` drives each at a
size the CPU holds, and ``calibrate.py --fault <name>`` on the chip at a
cell's own size.
"""
from __future__ import annotations


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


def decode_keeps_state(eng) -> None:
    """The decode launch returns the page pools it was given, unchanged."""
    kv = eng.kv
    launch = kv._decode_slots

    def unchanged(params, k_pool, v_pool, *rest):
        out = launch(params, k_pool, v_pool, *rest)
        return (k_pool, v_pool) + tuple(out[2:])
    kv._decode_slots = unchanged


def prefill_keeps_state(eng) -> None:
    """Each prefill or extend launch returns the page pools it was given,
    unchanged."""
    kv = eng.kv
    launch = kv._extend

    def unchanged(params, k_pool, v_pool, *rest):
        out = launch(params, k_pool, v_pool, *rest)
        return (k_pool, v_pool) + tuple(out[2:])
    kv._extend = unchanged


ALL = {f.__name__: f for f in (alter_tokens, decode_keeps_state,
                               prefill_keeps_state)}
