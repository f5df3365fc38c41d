"""Faults planted underneath the timed path, each of which ``correct``
has to read as false.

A fault takes a ``setattr(target, name, value)`` (pytest's
``monkeypatch.setattr``, or ``Patch.setattr`` here, which can be undone)
and is planted before the driver is built. A driver lists the faults its
cells can have in ``FAULTS``; ``calibrate.py`` reads them on the chip at
the cell's own size, and ``tests/chipbench`` at a small size.
"""
from __future__ import annotations


class Patch:
    """``setattr`` with an undo."""

    def __init__(self):
        self._undo = []

    def setattr(self, target, name: str, value) -> None:
        self._undo.append((target, name, getattr(target, name)))
        setattr(target, name, value)

    def undo(self) -> None:
        while self._undo:
            setattr(*self._undo.pop())


def half_batch(setattr) -> None:
    """Half of the clients left out of every round."""
    from repro.core.engine import FederationEngine
    run = FederationEngine.run
    setattr(FederationEngine, "run", lambda self, X, d: run(
        self, X[:len(X) // 2], d[:len(d) // 2]))


def altered_answer(setattr) -> None:
    """The largest entry of ``W`` off by 1 % where the solve produces it."""
    import jax.numpy as jnp
    from repro.core.wire import GramWire
    solve = GramWire.solve

    def altered(self, st, lam=1e-3):
        W = solve(self, st, lam)
        return W.reshape(-1).at[jnp.argmax(jnp.abs(W))].multiply(
            1.01).reshape(W.shape)
    setattr(GramWire, "solve", altered)


def unchanged_state(setattr) -> None:
    """Ledger events that leave its state as it was (only the first
    admission of each client goes in)."""
    from repro.core.ledger import FederationLedger
    join = FederationLedger.join

    def frozen(self, *a, **kw):
        return None

    def join_new(self, cid, stats):
        if cid not in self.registry:
            return join(self, cid, stats)
    setattr(FederationLedger, "revise", frozen)
    setattr(FederationLedger, "leave", frozen)
    setattr(FederationLedger, "join", join_new)
