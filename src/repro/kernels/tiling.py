"""Block tiling of static attention masks, shared by the Pallas attention
kernels (the forward in :mod:`repro.kernels.flash_attention`, the backward
in :mod:`repro.kernels.attention_bwd`).

Both sides tile queries and keys into blocks of the same ``b`` rows and
work on the transposed scores ``sᵀ`` of a (q block, k block) pair: keys
down, queries across.  Everything here is written with ``lax`` primitives,
not jnp's functions or operators: jnp's jitted helpers cache their traced
jaxprs with the source locations of their first caller, so a helper first
traced by one kernel would carry that kernel's file and function names into
the other, and a Pallas kernel is known in a profile by the names in its
locations.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


class Tiling:
    """Which (q block, k block) pairs hold visible scores, for static
    ``causal``/``window`` masks: ``nq`` blocks of ``b`` queries, the first
    at position ``q_offset``, against ``nk`` blocks of ``b`` keys of which
    the first ``sk`` are real."""

    def __init__(self, *, causal, window, sk, b, nq, nk, q_offset=0):
        self.causal, self.window, self.sk, self.b = causal, window, sk, b
        self.nq, self.nk, self.q_offset = nq, nk, q_offset

    def k_range(self, qi):
        """First and last k block that q block ``qi`` sees."""
        lo, hi = 0, self.nk - 1
        if self.window > 0:       # a negative first key truncates to 0
            lo = lax.max(lax.div(lax.sub(lax.mul(qi, self.b),
                                         self.window - 1 - self.q_offset),
                                 self.b), 0)
        if self.causal:
            if self.q_offset or self.nq > self.nk:
                last = lax.add(lax.mul(qi, self.b), self.q_offset + self.b - 1)
                hi = lax.min(lax.div(last, self.b), hi)
            else:
                hi = qi
        return lo, hi

    def q_range(self, ki):
        """First and last q block that sees k block ``ki``.  Self-attention
        only (``q_offset`` 0, ``nq == nk``): the backward's grid."""
        assert not self.q_offset and self.nq == self.nk
        lo, hi = 0, self.nq - 1
        if self.causal:
            lo = ki
        if self.window > 0:
            last = lax.add(lax.mul(ki, self.b), self.b + self.window - 2)
            hi = lax.min(lax.div(last, self.b), hi)
        return lo, hi

    def sees(self, qi, ki, by_k=True):
        """Whether the pair holds a visible score: from ``k_range(qi)``, or
        from ``q_range(ki)`` where ``by_k`` is False."""
        lo, hi = self.k_range(qi) if by_k else self.q_range(ki)
        i = ki if by_k else qi
        return lax.bitwise_and(lax.le(lo, i), lax.le(i, hi))

    def full(self, qi, ki):
        """Every score of the pair is visible: no mask to build."""
        k_last = lax.add(lax.mul(ki, self.b), self.b - 1)
        out = lax.lt(k_last, self.sk)
        if self.causal:                     # the last key <= the first query
            if self.q_offset:
                out = lax.bitwise_and(out, lax.le(
                    k_last, lax.add(lax.mul(qi, self.b), self.q_offset)))
            else:
                out = lax.bitwise_and(out, lax.lt(ki, qi))
        if self.window > 0:
            q_last = lax.add(lax.mul(qi, self.b), self.b - 1 + self.q_offset)
            out = lax.bitwise_and(out, lax.gt(lax.mul(ki, self.b),
                                              lax.sub(q_last, self.window)))
        return out

    def mask(self, st, qi, ki):
        """``sᵀ`` of the pair with its hidden scores at -inf.  Key ``i`` of
        the pair lies ``i - j - (qi - ki) b - q_offset`` after its query
        ``j``."""
        i = lax.broadcasted_iota(jnp.int32, st.shape, 0)
        ahead = lax.sub(i, lax.broadcasted_iota(jnp.int32, st.shape, 1))
        offset = lax.mul(lax.sub(qi, ki), self.b)
        if self.q_offset:
            offset = lax.add(offset, self.q_offset)
        visible = None
        if self.causal:                     # key <= query
            visible = lax.le(ahead, offset)
        if self.window > 0:                 # key > query - window
            behind = lax.gt(ahead, lax.sub(offset, self.window))
            visible = behind if visible is None else lax.bitwise_and(
                visible, behind)
        if self.nk * self.b > self.sk:      # keys padded to blocks
            real = lax.lt(i, lax.sub(self.sk, lax.mul(ki, self.b)))
            visible = real if visible is None else lax.bitwise_and(
                visible, real)
        if visible is None:
            return st
        return lax.select(visible, st, lax.full_like(st, -jnp.inf))

    def when_needed(self, needed, qi, ki, step):
        """Run ``step(masked)`` on the pair where ``needed``, unmasked
        where every score of it is visible."""
        full = self.full(qi, ki)
        pl.when(lax.bitwise_and(needed, full))(lambda: step(False))
        pl.when(lax.bitwise_and(needed, lax.bitwise_not(full)))(
            lambda: step(True))


def block_size(s, most):
    """Rows of one block over ``s`` positions: ``most``, or the whole
    (8-aligned) sequence where it is shorter."""
    return min(-(-s // 8) * 8, most)


def clamp(i, lo, hi):
    return lax.min(lax.max(i, lo), hi)


def vmem_limit(b):
    """Room for about six f32 score blocks of temporaries, and 16 MiB for
    the double-buffered operand blocks and the accumulators."""
    return 6 * b * b * 4 + (16 << 20)
