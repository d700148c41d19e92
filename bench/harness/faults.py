"""Deliberately broken programs, planted for the length of a ``with``: the
control that has to fail the check, and the faults the check has to catch.

Each replaces a function of the program in the module that calls it and
drops JAX's compiled programs on the way in and out, so the planted version
is traced afresh and leaves nothing behind.

- ``control``: the search's statistics (visits, wins) held in bfloat16, the
  nearest precision below the float32 the configurations state; counts
  above 256 then lose playouts;
- ``state_unchanged``: a round that returns its tree as it got it;
- ``half_batch``: half of each iteration's lanes left out of the backup;
- ``no_exchange``: the forest's root merge left out;
- ``flipped_winner``: one lane's playout result inverted where it is
  produced.
"""

from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch", "no_exchange", "flipped_winner")


@contextlib.contextmanager
def _patched(module, name: str, replacement):
    import jax

    original = getattr(module, name)
    setattr(module, name, replacement(original))
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(module, name, original)
        jax.clear_caches()


def round_bf16(x):
    """float32 rounded to the nearest bfloat16 (ties to even), by its bits:
    a pair of converts may be dropped by the compiler as excess precision,
    integer arithmetic may not."""
    import jax
    import jax.numpy as jnp

    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + (jnp.uint32(0x7FFF) + ((u >> 16) & 1))) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def control():
    from repro.core import gscpm

    def wrap(backup):
        def lower(tree, paths, values, weights):
            t = backup(tree, paths, values, weights)
            return t._replace(visits=round_bf16(t.visits),
                              wins=round_bf16(t.wins))
        return lower

    return _patched(gscpm, "backup_paths", wrap)


def state_unchanged():
    from repro.core import gscpm, root_parallel

    stack = contextlib.ExitStack()
    stack.enter_context(_patched(
        gscpm, "run_chunk", lambda f: lambda tree, *a, **k: tree))
    stack.enter_context(_patched(
        root_parallel, "run_chunk_forest", lambda f: lambda tree, *a, **k: tree))
    stack.enter_context(_patched(
        root_parallel, "_sharded_chunk", lambda f: lambda tree, *a, **k: tree))
    return stack


def half_batch():
    from repro.core import gscpm

    def wrap(backup):
        def half(tree, paths, values, weights):
            W = weights.shape[0]
            return backup(tree, paths, values, weights.at[W // 2:].set(0.0))
        return half

    return _patched(gscpm, "backup_paths", wrap)


def no_exchange():
    from repro.core import root_parallel

    return _patched(root_parallel, "sync_root_stats",
                    lambda f: lambda forest, state, n_moves: (forest, state))


def flipped_winner():
    from repro.core.hex import HexGame

    def wrap(playout):
        def flip(self, boards, to_move, keys):
            w = playout(self, boards, to_move, keys)
            return w.at[0].set(3 - w[0])
        return flip

    return _patched(HexGame, "playout_batch", wrap)


def plant(name: str):
    return {"control": control, "state_unchanged": state_unchanged,
            "half_batch": half_batch, "no_exchange": no_exchange,
            "flipped_winner": flipped_winner}[name]()
