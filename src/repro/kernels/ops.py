"""Public jit'd wrappers over the Pallas kernels.

``interpret`` defaults to auto. For ``flash_attention``/``rmsnorm`` that
means compiled on TPU, interpret-mode (pure Python execution of the kernel
body) everywhere else — which is how this CPU container validates them.
``uct_select`` and ``hex_winner`` sit on the search hot path, so their auto
mode never runs interpret-mode Pallas: compiled Pallas on TPU, the jitted
jnp reference on every other backend (interpret mode remains available for
validation via ``interpret=True``). This module is also the per-game eval
dispatch point of the Game seam (DESIGN.md §13): ``hex_winner`` for Hex,
``gomoku_winner`` / ``gomoku_first_winner`` for Gomoku (single jnp body on
all backends until a Pallas twin lands — ROADMAP). Call sites
(models/attention.py, core/gscpm.py, core/hex.py, core/gomoku.py,
serve/mcts_decode.py) go through these wrappers only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import hex_winner as _hw
from repro.kernels import ref as _ref
from repro.kernels import rmsnorm as _rn
from repro.kernels import uct_select as _us


def _auto_interpret(interpret: bool | None) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, scale: float | None = None,
                    interpret: bool | None = None,
                    layout: str = "bshd") -> jnp.ndarray:
    """Flash attention. layout 'bshd' (models) or 'bhsd' (kernel-native)."""
    it = _auto_interpret(interpret)
    if layout == "bshd":
        q, k, v = (t.swapaxes(1, 2) for t in (q, k, v))
    out = _fa.flash_attention(q, k, v, causal=causal, scale=scale,
                              interpret=it)
    return out.swapaxes(1, 2) if layout == "bshd" else out


def uct_select(wins, visits, vloss, parent_total, valid, cp,
               noise=None, lane_mask=None, interpret: bool | None = None):
    """Batched UCT child selection — the search hot path's dispatch point.

    interpret=None (the default) picks the fast path per backend: the
    compiled Pallas kernel on TPU, the jitted jnp reference elsewhere
    (interpret-mode Pallas executes the kernel body in pure Python — it is
    a validation tool, never a serving/benchmark path). Pass interpret=True
    to force the interpret-mode kernel for validation. ``cp`` is traced on
    every path: sweeping it never recompiles.
    """
    if interpret is None and jax.default_backend() != "tpu":
        return _jitted_ref_uct_select(wins, visits, vloss, parent_total,
                                      valid, cp, noise, lane_mask)
    return _us.uct_select(wins, visits, vloss, parent_total, valid, cp,
                          noise=noise, lane_mask=lane_mask,
                          interpret=_auto_interpret(interpret))


@jax.jit
def _jitted_ref_uct_select(wins, visits, vloss, parent_total, valid, cp,
                           noise, lane_mask):
    return _ref.uct_select(wins, visits, vloss, parent_total, valid, cp,
                           noise=noise, lane_mask=lane_mask)


def hex_winner(boards, size: int, interpret: bool | None = None):
    """Batched Hex winner evaluation — the playout phase's dispatch point.

    boards: (W, size*size) FILLED boards; returns (W,) int8 winners.
    interpret=None (the default) picks the fast path per backend exactly
    like ``uct_select``: on TPU the compiled Pallas kernel, a roll-dilation
    flood fill with a fixed ``n_cells - 1`` steps; elsewhere the jitted
    batched flood fill, the same dilation with a convergence check
    (DESIGN.md §12). Pass interpret=True to force the interpret-mode kernel
    for validation (never a timing path); the pointer-doubling jnp
    reference stays in ``kernels.ref`` as an independent oracle.
    """
    if interpret is None and jax.default_backend() != "tpu":
        return _jitted_flood_hex_winner(boards, size)
    return _hw.hex_winner(boards, size,
                          interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("size",))
def _jitted_flood_hex_winner(boards, size: int):
    from repro.core import hex as hx
    return hx.winner_flood_batch(boards, hx.HexSpec(size))


def gomoku_winner(boards, size: int, interpret: bool | None = None):
    """Batched Gomoku terminal winner — the per-game eval dispatch twin of
    ``hex_winner`` (DESIGN.md §13).

    boards: (W, size*size) TERMINAL boards; returns (W,) int8 in
    {0 draw, 1, 2}. Unlike Hex — whose connectivity solve has a Pallas
    body on TPU and a jnp loop elsewhere — the five-in-a-row test is four
    static-roll window scans that lower to plain vector shifts/ANDs on
    every backend, so a single jitted jnp body serves TPU and CPU alike. A
    dedicated Pallas kernel slot stays open in ROADMAP.md; ``interpret`` is
    accepted for signature symmetry.
    """
    del interpret  # no Pallas body yet — one jnp path on all backends
    return _jitted_gomoku_winner(boards, size)


@functools.partial(jax.jit, static_argnames=("size",))
def _jitted_gomoku_winner(boards, size: int):
    from repro.core import gomoku as gm
    return gm.winner_scan_batch(boards, gm.GomokuSpec(size))


def gomoku_first_winner(filled, times, size: int,
                        interpret: bool | None = None):
    """Fused Gomoku playout outcome: completion-time resolution over a
    random fill (the playout phase's dispatch point for the ``gomoku``
    game, as ``hex_winner`` is for ``hex``).

    filled: (W, size*size) int8 fully-filled boards; times: (W, size*size)
    int32 fill rank per cell (-1 for pre-playout stones). Returns (W,) int8
    outcomes {0 draw, 1, 2}: the color of the monochrome 5-window whose
    last cell has the minimal fill rank (see ``core/gomoku.py``).
    """
    del interpret
    return _jitted_gomoku_first_winner(filled, times, size)


@functools.partial(jax.jit, static_argnames=("size",))
def _jitted_gomoku_first_winner(filled, times, size: int):
    from repro.core import gomoku as gm
    return gm.first_completion_winner(filled, times, gm.GomokuSpec(size))


def rmsnorm(x, w, eps: float = 1e-5, interpret: bool | None = None):
    return _rn.rmsnorm(x, w, eps=eps, interpret=_auto_interpret(interpret))
