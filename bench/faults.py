"""Faults planted in the timed path, for showing that the output check
refuses them.

Each fault takes ``setattr(obj, name, value)`` and replaces one function of
the program with a broken one: pytest's ``monkeypatch.setattr`` in the
tests, or ``Planted.setattr`` in ``bench/control.py --fault`` on the chip.
The engine jits its step functions when it is built, so a fault is planted
before the engine is.
"""
from __future__ import annotations


def token_altered(setattr):
    """Every decode token altered where the decode pass produces it."""
    from repro.engine.core import EngineCore
    real = EngineCore._decode_bookkeeping

    def altered(self, new_tok, logits):
        return real(self, (new_tok + 1) % self.cfg.vocab_size, logits)
    setattr(EngineCore, "_decode_bookkeeping", altered)


def state_unchanged(setattr):
    """The decode step returns its KV state unchanged."""
    from repro.models import steps
    real = steps.serve_step

    def unchanged(params, tokens, caches, cfg, **kw):
        tok, logits, _ = real(params, tokens, caches, cfg, **kw)
        return tok, logits, caches
    setattr(steps, "serve_step", unchanged)


FAULTS = {"token-altered": token_altered, "state-unchanged": state_unchanged}


class Planted:
    """Plants faults by ``setattr`` and takes them out again on ``undo``."""

    def __init__(self):
        self._saved = []

    def setattr(self, obj, name, value):
        self._saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def undo(self):
        while self._saved:
            obj, name, value = self._saved.pop()
            setattr(obj, name, value)
