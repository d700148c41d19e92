"""Pytest bootstrap: make src/ and the tests dir importable everywhere.

Keeps `PYTHONPATH=src python -m pytest` (the tier-1 command) and a bare
`pytest` invocation equivalent, and lets test modules import helpers from
one another regardless of pytest's import mode. Also holds the fixture
that swaps the search's per-lane references in for its batched phases.
"""

import contextlib
import os
import sys

import pytest

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(os.path.dirname(_HERE), "src")
for p in (_HERE, _SRC):
    if p not in sys.path:
        sys.path.insert(0, p)


@contextlib.contextmanager
def _per_lane_references(game, *, descent: bool = False,
                         playout: bool = False):
    """For the length of a ``with``, run the search's selection
    (``descent``) and/or leaf evaluation (``playout``) through its per-lane
    reference: ``jax.vmap`` of ``gscpm.select_one`` in place of
    ``gscpm.select_batch``, and of ``game.playout_scalar`` in place of the
    game class's ``playout_batch``. JAX's compiled programs are dropped on
    the way in and out, so the search is traced afresh with the references
    and no cached production program is reused."""
    import jax

    from repro.core import gscpm

    def select_per_lane(tree, board, g, cp, keys, scale, vloss=None):
        return jax.vmap(lambda k: gscpm.select_one(
            tree, board, g, cp, k, scale, vloss))(keys)

    def playout_per_lane(self, boards, to_move, keys):
        return jax.vmap(self.playout_scalar)(boards, to_move, keys)

    swaps = []
    if descent:
        swaps.append((gscpm, "select_batch", select_per_lane))
    if playout:
        swaps.append((type(game), "playout_batch", playout_per_lane))
    saved = [(owner, name, getattr(owner, name)) for owner, name, _ in swaps]
    for owner, name, fn in swaps:
        setattr(owner, name, fn)
    jax.clear_caches()
    try:
        yield
    finally:
        for owner, name, fn in saved:
            setattr(owner, name, fn)
        jax.clear_caches()


@pytest.fixture
def per_lane_references():
    """``_per_lane_references``: whole searches with the per-lane
    selection and playout references swapped in, to compare against the
    batched production round."""
    return _per_lane_references
